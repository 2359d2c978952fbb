"""Pallas TPU kernel: budgeted vertical-slash prefill attention (paper §4.2).

TPU adaptation of MInference's vertical-slash CUDA kernel: TPU has no
warp-level gather, and the MXU wants dense 128-aligned tiles, so the
admitted ("vertical") tokens are pre-gathered into a contiguous budgeted
buffer [C, hd] outside the kernel (ops.py), and the kernel streams dense
tiles over [slash(prev) | slash(cur) | global tiles] with one flash-style
softmax.

Grid: (n_streams, n_q_blocks, 2 + C/Bc) with the kv-source dimension
innermost:
  step 0 — previous slash block (k block b-1; masked out for b == 0)
  step 1 — current slash block  (k block b)
  steps 2.. — global tiles of the gathered buffer, visibility
              gpos_j <= i - W (strictly older than the window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, kp_ref, vp_ref, kc_ref, vc_ref, kg_ref, vg_ref, gpos_ref,
            o_ref, m_ref, l_ref, acc_ref, *, w: int, bc: int, n_src: int):
    qb = pl.program_id(1)
    src = pl.program_id(2)

    @pl.when(src == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [W, hd]
    hd = q.shape[-1]
    scale = hd ** -0.5
    qi = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)  # in-block query row

    def flash_update(s, v):
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # rows with every key masked so far: keep p/alpha at exact zero
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jnp.logical_and(src == 0, qb > 0))
    def _slash_prev():
        k = kp_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kj = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) - w  # rel offsets
        ok = (qi >= kj) & (qi - kj < w)
        flash_update(jnp.where(ok, s, NEG_INF), vp_ref[0])

    @pl.when(src == 1)
    def _slash_cur():
        k = kc_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kj = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        ok = (qi >= kj) & (qi - kj < w)
        flash_update(jnp.where(ok, s, NEG_INF), vc_ref[0])

    @pl.when(src >= 2)
    def _vertical():
        k = kg_ref[0]  # [Bc, hd]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qabs = qb * w + qi                                   # [W, 1]
        gp = gpos_ref[0][None, :]                            # [1, Bc]
        ok = gp <= qabs - w
        flash_update(jnp.where(ok, s, NEG_INF), vg_ref[0])

    @pl.when(src == n_src - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def vertical_slash(q, k, v, kg, vg, gpos, *, w_local: int, bc: int = 128,
                   interpret: bool):
    """q, k, v: [N, S, hd]; kg, vg: [N, C, hd]; gpos: [N, C] int32.
    S % w_local == 0 and C % bc == 0 required. Returns [N, S, hd]."""
    n, s, hd = q.shape
    c = kg.shape[1]
    w = w_local
    assert s % w == 0, (s, w)
    bc = min(bc, c)
    assert c % bc == 0, (c, bc)
    nb = s // w
    n_src = 2 + c // bc
    kernel = functools.partial(_kernel, w=w, bc=bc, n_src=n_src)

    def prev_map(b, i, j):
        return (b, jnp.maximum(i - 1, 0), 0)

    def cur_map(b, i, j):
        return (b, i, 0)

    def glob_map(b, i, j):
        return (b, jnp.maximum(j - 2, 0), 0)

    return pl.pallas_call(
        kernel,
        grid=(n, nb, n_src),
        in_specs=[
            pl.BlockSpec((1, w, hd), cur_map),            # q
            pl.BlockSpec((1, w, hd), prev_map),           # k prev slash
            pl.BlockSpec((1, w, hd), prev_map),           # v prev slash
            pl.BlockSpec((1, w, hd), cur_map),            # k cur slash
            pl.BlockSpec((1, w, hd), cur_map),            # v cur slash
            pl.BlockSpec((1, bc, hd), glob_map),          # k global tile
            pl.BlockSpec((1, bc, hd), glob_map),          # v global tile
            pl.BlockSpec((1, bc), lambda b, i, j: (b, jnp.maximum(j - 2, 0))),
        ],
        out_specs=pl.BlockSpec((1, w, hd), cur_map),
        out_shape=jax.ShapeDtypeStruct((n, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((w,), jnp.float32),
            pltpu.VMEM((w,), jnp.float32),
            pltpu.VMEM((w, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, k, v, kg, vg, gpos)
