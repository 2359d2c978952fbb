"""Pallas TPU kernel: write-gated flash attention (training hot path).

FlashAttention-style streaming softmax with the paper's log-space gate bias
(§3.2): inside the local window the bias is 0, outside it is log(g_j+eps),
above the causal diagonal -inf. Grid (n_streams, n_q_blocks, n_kv_blocks)
with the kv dimension innermost; running (m, l, acc) live in VMEM scratch
and the output tile is written on the last kv step. Fully-masked kv blocks
(strictly above the diagonal) are skipped via ``pl.when`` — the vertical-
slash sparsity of the gate shows up as early-exit bandwidth savings on TPU.

Tiling: q [Bq, hd], k/v [Bk, hd], g [Bk] — MXU-aligned (multiples of 128 in
production; tests sweep smaller tiles in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, g_ref, o_ref, m_ref, l_ref, acc_ref, *,
            w_local: int, eps: float, bq: int, bk: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks strictly above the causal diagonal
    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _compute():
        q = q_ref[0]                   # [Bq, hd]
        k = k_ref[0]                   # [Bk, hd]
        g = g_ref[0]                   # [Bk]
        hd = q.shape[-1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (hd ** -0.5)
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        causal = rows >= cols
        in_win = causal & (rows - cols < w_local)
        logg = jnp.log(g.astype(jnp.float32) + eps)[None, :]
        bias = jnp.where(in_win, 0.0, logg)
        s = s + jnp.where(causal, bias, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)


def gated_flash(q, k, v, g, *, w_local: int, eps: float = 1e-6,
                bq: int = 128, bk: int = 128, interpret: bool):
    """q: [N, S, hd]; k, v: [N, S, hd]; g: [N, S] -> [N, S, hd]."""
    n, s, hd = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_q, n_kv = s // bq, s // bk
    kernel = functools.partial(_kernel, w_local=w_local, eps=eps, bq=bq,
                               bk=bk, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(n, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(q, k, v, g)
