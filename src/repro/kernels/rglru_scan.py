"""Pallas TPU kernel: blocked RG-LRU linear scan.

h_t = a_t * h_{t-1} + b_t, elementwise over the recurrence width. The grid
is (batch, d_blocks, time_blocks) with time innermost; the carry h lives in
VMEM scratch across time steps, and each grid step processes a [Bt, Bd]
tile sequentially within the tile (fori over Bt rows) while staying fully
parallel across (batch, d) — the TPU-friendly decomposition of a scan whose
parallel dimension (channels) is wide and whose sequential dimension is
blocked for VMEM residency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_ref, *, bt: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]  # [Bt, Bd]
    b = b_ref[0]

    def body(i, h):
        h = a[i] * h + b[i]
        o_ref[0, i, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, bt, body, h_ref[...])


def rglru_scan_pallas(a, b, *, bt: int = 128, bd: int = 128,
                      interpret: bool):
    """a, b: [B, S, D] float32 -> h [B, S, D]."""
    bsz, s, d = a.shape
    bt = min(bt, s)
    bd = min(bd, d)
    assert s % bt == 0 and d % bd == 0, (s, d, bt, bd)
    kernel = functools.partial(_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(bsz, d // bd, s // bt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda i, jd, jt: (i, jt, jd)),
            pl.BlockSpec((1, bt, bd), lambda i, jd, jt: (i, jt, jd)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda i, jd, jt: (i, jt, jd)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((bd,), jnp.float32)],
        interpret=interpret,
    )(a, b)
