"""jit'd wrappers around the Pallas kernels, handling model-level shapes
(GQA head folding, global-token gathering, dual-cache paging).

Every wrapper takes its ``interpret`` flag from :func:`interpret_mode`:
kernel bodies run under the Pallas interpreter on the CPU (tests) and
compile to Mosaic kernels on the TPU. Any other backend is an error, so a
run that lost its chip never carries on interpreted in silence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gate_mlp import gate_mlp
from repro.kernels.gated_flash import gated_flash
from repro.kernels.paged_decode import paged_decode
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.vertical_slash import vertical_slash


def interpret_mode() -> bool:
    """``interpret`` for the Pallas kernels on the default backend: True on
    the CPU, False on the TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel mode for backend {backend!r} "
                       "(expected 'cpu' or 'tpu')")


def _fold_gqa(q, k, v):
    """q: [B,Hq,S,hd]; k/v: [B,Hkv,S,hd] -> per-(b,kv-head,group) streams
    [B*Hkv*G, S, hd] with k/v broadcast across the group."""
    b, hq, s, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, s, hd).reshape(b * hkv * g, s, hd)
    kf = jnp.repeat(k.reshape(b * hkv, s, hd), g, axis=0)
    vf = jnp.repeat(v.reshape(b * hkv, s, hd), g, axis=0)
    return qf, kf, vf, (b, hq, s, hd, g)


@functools.partial(jax.jit, static_argnames=("w_local", "bq", "bk"))
def gated_flash_attention(q, k, v, g, *, w_local: int, bq: int = 128,
                          bk: int = 128):
    """Model-level write-gated attention. q: [B,Hq,S,hd]; k/v: [B,Hkv,S,hd];
    g: [B,Hkv,S] -> [B,Hq,S,hd]."""
    qf, kf, vf, (b, hq, s, hd, grp) = _fold_gqa(q, k, v)
    gf = jnp.repeat(g.reshape(-1, s), grp, axis=0)
    of = gated_flash(qf, kf, vf, gf, w_local=w_local, bq=bq, bk=bk,
                     interpret=interpret_mode())
    return of.reshape(b, hq, s, hd)


@functools.partial(jax.jit, static_argnames=("w_local", "bc"))
def vertical_slash_attention(q, k, v, kg, vg, gpos, *, w_local: int,
                             bc: int = 128):
    """Budgeted vertical-slash prefill. q: [B,Hq,S,hd]; k/v: [B,Hkv,S,hd];
    kg/vg: [B,Hkv,C,hd]; gpos: [B,Hkv,C] -> [B,Hq,S,hd]."""
    qf, kf, vf, (b, hq, s, hd, grp) = _fold_gqa(q, k, v)
    c = kg.shape[2]
    kgf = jnp.repeat(kg.reshape(-1, c, hd), grp, axis=0)
    vgf = jnp.repeat(vg.reshape(-1, c, hd), grp, axis=0)
    gpf = jnp.repeat(gpos.reshape(-1, c), grp, axis=0)
    of = vertical_slash(qf, kf, vf, kgf, vgf, gpf, w_local=w_local, bc=bc,
                        interpret=interpret_mode())
    return of.reshape(b, hq, s, hd)


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, page_table, lengths):
    """Head-folded paged decode (paper Appendix B). q: [B,Hq,hd]; pools
    [P,page,hd]; page_table: [B,Hkv,max_pages]; lengths: [B,Hkv]
    -> [B,Hq,hd]."""
    b, hq, hd = q.shape
    hkv, mp = page_table.shape[1], page_table.shape[2]
    g = hq // hkv
    qf = q.reshape(b * hkv * g, hd)
    tf = jnp.repeat(page_table.reshape(b * hkv, mp), g, axis=0)
    lf = jnp.repeat(lengths.reshape(b * hkv), g, axis=0)
    of = paged_decode(qf, k_pool, v_pool, tf, lf,
                      interpret=interpret_mode())
    return of.reshape(b, hq, hd)


@functools.partial(jax.jit, static_argnames=("bt", "bd"))
def rglru_linear_scan(a, b, *, bt: int = 128, bd: int = 128):
    """[B,S,D] linear recurrence via the blocked Pallas scan."""
    return rglru_scan_pallas(a, b, bt=bt, bd=bd,
                             interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bs",))
def write_gate(x, w1, b1, w2, b2, *, bs: int = 256):
    """Fused Write-Gate MLP. x: [B,H,S,F] (features) with per-head weights
    [H,F,M]/[H,M]/[H,M,1]/[H,1] -> g [B,H,S] float32."""
    b, h, s, f = x.shape
    xf = x.reshape(b * h, s, f)
    w1f = jnp.tile(w1, (b, 1, 1))
    b1f = jnp.tile(b1, (b, 1))
    w2f = jnp.tile(w2, (b, 1, 1))
    b2f = jnp.tile(b2, (b, 1))
    g = gate_mlp(xf, w1f, b1f, w2f, b2f, bs=bs,
                 interpret=interpret_mode())
    return g.reshape(b, h, s)
