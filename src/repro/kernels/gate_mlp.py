"""Pallas TPU kernel: fused Write-Gate MLP (paper §3.2 overhead analysis).

Computes g = sigmoid(W2 @ gelu(W1 @ x + b1) + b2) per kv-head in one VMEM
pass: the feature tile [Bs, F] and both weight tiles stay resident, so the
gate adds a single HBM round-trip per key tile (the paper's "negligible
overhead" claim, realized as fusion on TPU).

Grid: (H, S / Bs).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    x = x_ref[0]            # [Bs, F]
    w1 = w1_ref[0]          # [F, M]
    h = jnp.dot(x, w1, preferred_element_type=jnp.float32) + b1_ref[0]
    h = jax.nn.gelu(h)
    y = jnp.dot(h.astype(w2_ref.dtype), w2_ref[0],
                preferred_element_type=jnp.float32) + b2_ref[0]
    o_ref[0] = jax.nn.sigmoid(y[..., 0]).astype(o_ref.dtype)


def gate_mlp(x, w1, b1, w2, b2, *, bs: int = 256, interpret: bool):
    """x: [H, S, F]; w1: [H, F, M]; b1: [H, M]; w2: [H, M, 1]; b2: [H, 1]
    -> g [H, S] float32."""
    h, s, f = x.shape
    m = w1.shape[-1]
    bs = min(bs, s)
    assert s % bs == 0
    return pl.pallas_call(
        _kernel,
        grid=(h, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, f), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, f, m), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, m), lambda i, j: (i, 0)),
            pl.BlockSpec((1, m, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, s), jnp.float32),
        interpret=interpret,
    )(x, w1, b1, w2, b2)
