"""Pallas TPU kernel: the dual cache's decode-step write, in place.

One call does a layer's whole lazy-promotion write for every row of a
stacked cache (``[L, B, ...]`` leaves, layer ``li``): read the victim at
the ring pointer, promote it into the global cache where its gate score
admits it and the budget holds, fold it into its page's Quest metadata,
and write the new token into the ring. Every leaf stays in HBM, aliased
to its output, and the kernel moves only what a position touches: for
each (row, kv-head) the ring's tile around the pointer, the global
cache's tile around the append slot and the page metadata's tile around
that page, and the layer's small per-slot leaves; an inactive row
changes nothing and moves nothing. HBM tiles the two
minor axes in (8, 128), so the kernel reads and writes whole ``R``-row
tiles and changes one row of each. All reads are issued before any
write, so no two copies of one tile are in flight at once.

Arithmetic is data movement and ``min``/``max``: the result is
bit-identical to the jnp write in ``core/dual_cache.py``.

Scalar prefetch ``sc`` (int32): ``[li, ptr[B], t[B], gcnt[B*H], active[B]]``
of layer ``li`` before the write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a tile: HBM tiles the two minor axes in (8, 128)
ROWS = 8


def _tile(n: int) -> int:
    """Rows a copy moves of an axis of ``n`` slots: a whole HBM tile, or
    the whole axis where it is not a multiple of one."""
    return ROWS if n % ROWS == 0 else n


def _pick(x, mask):
    """The one element (or row) of ``x`` under ``mask``, as float32 with
    the reduced axes kept: a sum of one value and zeros, so exact."""
    v = jnp.where(mask, x.astype(jnp.float32), 0.0)
    while v.ndim > 2:
        v = v.sum(0)
    return v


def _kernel(sc, knew, vnew, gnew,
            lk, lv, gk, gv, pmin, pmax, lg, lpos, gpos, gcnt, ovf,
            lk_o, lv_o, gk_o, gv_o, pmin_o, pmax_o, lg_o, lpos_o, gpos_o,
            gcnt_o, ovf_o,
            rk, rv, tk, tv, tlo, thi, lg_v, lpos_v, gpos_v, gcnt_v, ovf_v,
            sem, *, b_n: int, h_n: int, tau: float, big: float, page: int,
            r_ring: int, r_glob: int, r_page: int):
    li = sc[0]
    c = gpos_v.shape[-1]
    w = lg_v.shape[-1]
    ptr = lambda b: sc[1 + b]
    dest = lambda b, h: jnp.minimum(sc[1 + 2 * b_n + b * h_n + h], c - 1)
    active = lambda b: sc[1 + 2 * b_n + b_n * h_n + b] != 0

    def at(r, slot):
        return pl.ds(pl.multiple_of(slot // r * r, r), r)

    def tiles(b, h, out: bool):
        """(HBM window, VMEM tile) of the six leaves (row b, head h) moves."""
        ring, glob = at(r_ring, ptr(b)), at(r_glob, dest(b, h))
        meta = at(r_page, dest(b, h) // page)
        hbm = ((lk_o, lv_o, gk_o, gv_o, pmin_o, pmax_o) if out else
               (lk, lv, gk, gv, pmin, pmax))
        return [(a.at[li, b, h, s], v.at[b, h]) for a, v, s in zip(
            hbm, (rk, rv, tk, tv, tlo, thi),
            (ring, ring, glob, glob, meta, meta))]

    def layer(out: bool):
        """The small leaves: layer li of the per-slot ones, and the whole
        of the per-head counters (a layer's [B, H] is narrower than the
        128-lane tile a copy may cut)."""
        hbm = ((lg_o, lpos_o, gpos_o, gcnt_o, ovf_o) if out else
               (lg, lpos, gpos, gcnt, ovf))
        return [(a.at[li], v) for a, v in zip(hbm[:3], (lg_v, lpos_v, gpos_v))
                ] + [(a, v) for a, v in zip(hbm[3:], (gcnt_v, ovf_v))]

    def move(out: bool):
        """Copy every touched window in (HBM -> VMEM) or out, then wait."""
        def pairs(i):
            ps = tiles(i // h_n, i % h_n, out)
            return [(v, a) for a, v in ps] if out else ps

        def each(i, go):
            # an inactive row changes nothing: its tiles are not moved
            @pl.when(active(i // h_n))
            def _():
                for src, dst in pairs(i):
                    cp = pltpu.make_async_copy(src, dst, sem.at[0])
                    cp.start() if go else cp.wait()

        small = layer(out)
        small = [(v, a) for a, v in small] if out else small
        for src, dst in small:
            pltpu.make_async_copy(src, dst, sem.at[0]).start()
        jax.lax.fori_loop(0, b_n * h_n, lambda i, _: each(i, True), None)
        for src, dst in small:
            pltpu.make_async_copy(src, dst, sem.at[0]).wait()
        jax.lax.fori_loop(0, b_n * h_n, lambda i, _: each(i, False), None)

    move(out=False)
    rows = lambda r: jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    head_w = jax.lax.broadcasted_iota(jnp.int32, (h_n, w), 0)
    row_w = jax.lax.broadcasted_iota(jnp.int32, (b_n, w), 0)
    head_c = jax.lax.broadcasted_iota(jnp.int32, (h_n, c), 0)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (h_n, c), 1)
    row_h = jax.lax.broadcasted_iota(jnp.int32, (b_n, h_n), 0)
    head_h = jax.lax.broadcasted_iota(jnp.int32, (b_n, h_n), 1)
    head_d = jax.lax.broadcasted_iota(jnp.int32, (h_n, 1), 0)

    def write(i):
        b, h = i // h_n, i % h_n
        p, g = ptr(b), sc[1 + 2 * b_n + b * h_n + h]
        d = jnp.minimum(g, c - 1)
        at_ptr = rows(r_ring) == p % r_ring                 # [R, 1]
        # ---- victim (read before anything of this row is written) ------
        vk = _pick(rk[b, h], at_ptr).sum(0, keepdims=True)  # [1, hd]
        vv = _pick(rv[b, h], at_ptr).sum(0, keepdims=True)
        vg = _pick(lg_v[b], (head_w == h) & (lane_w == p)).sum(
            1, keepdims=True).sum(0, keepdims=True)         # [1, 1]
        vpos = _pick(lpos_v[...], (row_w == b) & (lane_w == p)).sum(
            1, keepdims=True).sum(0, keepdims=True).astype(jnp.int32)
        promote = (vpos >= 0) & (vg >= tau)                 # [1, 1]
        can = promote & (g < c)
        # ---- promotion into the global tile, its position, its page ----
        at_dest = rows(r_glob) == d % r_glob
        tk[b, h] = jnp.where(at_dest & can, vk.astype(tk.dtype), tk[b, h])
        tv[b, h] = jnp.where(at_dest & can, vv.astype(tv.dtype), tv[b, h])
        gpos_v[b] = jnp.where((head_c == h) & (lane_c == d) & can, vpos,
                              gpos_v[b])
        at_page = rows(r_page) == (d // page) % r_page
        fresh = (d % page) == 0
        kn = vk.astype(tlo.dtype).astype(jnp.float32)
        lo = jnp.where(fresh, big,
                       _pick(tlo[b, h], at_page).sum(0, keepdims=True))
        hi = jnp.where(fresh, -big,
                       _pick(thi[b, h], at_page).sum(0, keepdims=True))
        tlo[b, h] = jnp.where(at_page & can, jnp.minimum(lo, kn).astype(
            tlo.dtype), tlo[b, h])
        thi[b, h] = jnp.where(at_page & can, jnp.maximum(hi, kn).astype(
            thi.dtype), thi[b, h])
        here = (row_h == b) & (head_h == h)
        gcnt_v[li] = gcnt_v[li] + jnp.where(here & can, 1, 0)
        ovf_v[li] = ovf_v[li] + jnp.where(here & promote & ~can, 1, 0)
        # ---- the new token into the ring --------------------------------
        rk[b, h] = jnp.where(at_ptr, _pick(knew[b], head_d == h).sum(
            0, keepdims=True).astype(rk.dtype), rk[b, h])
        rv[b, h] = jnp.where(at_ptr, _pick(vnew[b], head_d == h).sum(
            0, keepdims=True).astype(rv.dtype), rv[b, h])
        g_here = _pick(gnew[...], here).sum(1, keepdims=True).sum(
            0, keepdims=True)
        lg_v[b] = jnp.where((head_w == h) & (lane_w == p), g_here,
                            lg_v[b])

        # the row's position goes in after every head has read its victim's
        @pl.when(h == h_n - 1)
        def _():
            lpos_v[...] = jnp.where((row_w == b) & (lane_w == p),
                                    sc[1 + b_n + b], lpos_v[...])

    def write_active(i, _):
        @pl.when(active(i // h_n))
        def _():
            write(i)

    jax.lax.fori_loop(0, b_n * h_n, write_active, None)
    move(out=True)


def dual_cache_write(sc, k_new, v_new, g_new, lk, lv, gk, gv, pkmin, pkmax,
                     lg, lpos, gpos, gcnt, overflow, *, tau: float,
                     page: int, big: float, interpret: bool):
    """In-place decode write of layer ``sc[0]`` of stacked dual-cache
    leaves (shapes as ``DualCache`` with a leading layer axis). ``k_new``,
    ``v_new`` [B, H, hd] in the cache's dtype; ``g_new`` [B, H] float32.
    Returns the eleven leaves, updated, in argument order."""
    _, b_n, h_n, w, d = lk.shape
    c = gk.shape[3]
    r_ring, r_glob, r_page = _tile(w), _tile(c), _tile(pkmin.shape[3])
    kernel = functools.partial(_kernel, b_n=b_n, h_n=h_n, tau=float(tau),
                               big=float(big), page=page, r_ring=r_ring,
                               r_glob=r_glob, r_page=r_page)
    args = (lk, lv, gk, gv, pkmin, pkmax, lg, lpos, gpos, gcnt, overflow)
    # the leaves stay in HBM: the kernel copies in only what it touches
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(),
        in_specs=[vmem, vmem, vmem] + [hbm] * len(args),
        out_specs=[hbm] * len(args),
        scratch_shapes=[
            pltpu.VMEM((b_n, h_n, r_ring, d), lk.dtype),
            pltpu.VMEM((b_n, h_n, r_ring, d), lv.dtype),
            pltpu.VMEM((b_n, h_n, r_glob, d), gk.dtype),
            pltpu.VMEM((b_n, h_n, r_glob, d), gv.dtype),
            pltpu.VMEM((b_n, h_n, r_page, d), pkmin.dtype),
            pltpu.VMEM((b_n, h_n, r_page, d), pkmax.dtype),
            pltpu.VMEM(lg.shape[1:], lg.dtype),
            pltpu.VMEM(lpos.shape[1:], lpos.dtype),
            pltpu.VMEM(gpos.shape[1:], gpos.dtype),
            pltpu.VMEM(gcnt.shape, gcnt.dtype),
            pltpu.VMEM(overflow.shape, overflow.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # outputs (and the inputs aliased to them) pinned to HBM, where
        # XLA would otherwise stage the per-slot leaves in VMEM around the
        # call; the counters (7 KB each) may stay wherever XLA keeps them
        out_shape=[pltpu.HBM(a.shape, a.dtype) for a in args[:-2]] + [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[-2:]],
        # operand 0 is sc, 1-3 the new token: the leaves follow
        input_output_aliases={4 + i: i for i in range(len(args))},
        interpret=interpret,
    )(sc, k_new, v_new, g_new, *args)
