"""Dual-cache (Local ring + budgeted Global) with Lazy Promotion (paper §4).

The logical view per attention layer and kv-head is:
  * Local Cache — ring buffer of the last ``W_local`` tokens (k, v, g, pos);
    unconditional retention (grace period for "transient utility").
  * Global Cache — budgeted region of admitted tokens; grows via Lazy
    Promotion: when the ring overwrites a victim, the victim is promoted
    iff its stored gate score g >= tau.

All shapes are static (XLA-friendly): the Global Cache has fixed capacity
``C`` with a per-head valid count ``gcnt`` (ragged lengths across heads,
exactly the paper's Fig. 4 problem, handled logically here and physically
by serving/paged.py). Overflowing promotions are counted in ``overflow``
and are what the composable SnapKV eviction (core/eviction.py) relieves.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admission import select_global
from repro.core.selection import (
    META_BIG, PAGE_SIZE, build_page_meta, init_page_meta,
    update_page_meta_on_write,
)
from repro.kernels.cache_write import dual_cache_write
from repro.kernels.ops import interpret_mode


class DualCache(NamedTuple):
    lk: jax.Array      # [B, H, W, hd] local keys (post-RoPE)
    lv: jax.Array      # [B, H, W, hd]
    lg: jax.Array      # [B, H, W]    gate score of local entries
    lpos: jax.Array    # [B, W] int32 absolute positions (-1 = empty slot)
    gk: jax.Array      # [B, H, C, hd]
    gv: jax.Array      # [B, H, C, hd]
    gpos: jax.Array    # [B, H, C] int32
    gcnt: jax.Array    # [B, H] int32 valid entries in global cache
    t: jax.Array       # [B] int32 next absolute position
    ptr: jax.Array     # [B] int32 ring pointer (next victim slot)
    overflow: jax.Array  # [B, H] int32 promotions dropped for lack of budget
    # Quest page metadata over the global cache (ceil(C/PAGE_SIZE) pages),
    # maintained incrementally: delta-folded on promote, recomputed only at
    # the (rare) eviction compaction. Empty lanes hold ±META_BIG sentinels.
    pkmin: jax.Array   # [B, H, P, hd]
    pkmax: jax.Array   # [B, H, P, hd]

    @property
    def w_local(self) -> int:
        return self.lk.shape[2]

    @property
    def budget(self) -> int:
        return self.gk.shape[2]

    def memory_tokens(self) -> jax.Array:
        """Current per-head resident token count: [B, H]."""
        local = jnp.minimum(self.t, self.w_local)[:, None]
        return self.gcnt + local


def init_dual_cache(
    batch: int,
    n_kv_heads: int,
    head_dim: int,
    *,
    w_local: int,
    budget: int,
    dtype=jnp.float32,
) -> DualCache:
    b, h, w, c, d = batch, n_kv_heads, w_local, budget, head_dim
    pkmin, pkmax = init_page_meta(b, h, c, d, dtype=dtype)
    return DualCache(
        pkmin=pkmin,
        pkmax=pkmax,
        lk=jnp.zeros((b, h, w, d), dtype),
        lv=jnp.zeros((b, h, w, d), dtype),
        lg=jnp.zeros((b, h, w), jnp.float32),
        lpos=jnp.full((b, w), -1, jnp.int32),
        gk=jnp.zeros((b, h, c, d), dtype),
        gv=jnp.zeros((b, h, c, d), dtype),
        gpos=jnp.zeros((b, h, c), jnp.int32),
        gcnt=jnp.zeros((b, h), jnp.int32),
        t=jnp.zeros((b,), jnp.int32),
        ptr=jnp.zeros((b,), jnp.int32),
        overflow=jnp.zeros((b, h), jnp.int32),
    )


def prefill_populate(
    cache: DualCache,
    k: jax.Array,  # [B, H, S, hd] post-RoPE keys
    v: jax.Array,
    g: jax.Array,  # [B, H, S]
    *,
    tau: float,
    sink: int = 0,
) -> DualCache:
    """Initial cache population (paper §4.2): final W tokens -> Local Cache
    (ring layout: token at absolute pos p occupies slot p % W); earlier
    tokens -> Global Cache iff admitted (g >= tau), up to the budget."""
    b, h, s, d = k.shape
    w = cache.w_local
    # ---- local: last min(W, S) tokens at slots pos % W -------------------
    n_local = min(w, s)
    local_pos = jnp.arange(s - n_local, s)  # absolute positions
    slots = local_pos % w
    lk = cache.lk.at[:, :, slots].set(k[:, :, s - n_local:])
    lv = cache.lv.at[:, :, slots].set(v[:, :, s - n_local:])
    lg = cache.lg.at[:, :, slots].set(g[:, :, s - n_local:].astype(jnp.float32))
    lpos = cache.lpos.at[:, slots].set(local_pos[None].astype(jnp.int32))
    # ---- global: admitted tokens before the local window -----------------
    sel = select_global(
        g, budget=cache.budget, tau=tau, sink=sink, exclude_from=s - n_local
    )
    bidx = jnp.arange(b)[:, None, None]
    hidx = jnp.arange(h)[None, :, None]
    gk = jnp.where(sel.valid[..., None], k[bidx, hidx, sel.idx], 0).astype(cache.gk.dtype)
    gv = jnp.where(sel.valid[..., None], v[bidx, hidx, sel.idx], 0).astype(cache.gv.dtype)
    gpos = jnp.where(sel.valid, sel.idx, 0)
    if gk.shape[2] < cache.budget:
        # short prefill (S < capacity): pad to the static budget
        pad = cache.budget - gk.shape[2]
        gk = jnp.pad(gk, ((0, 0), (0, 0), (0, pad), (0, 0)))
        gv = jnp.pad(gv, ((0, 0), (0, 0), (0, pad), (0, 0)))
        gpos = jnp.pad(gpos, ((0, 0), (0, 0), (0, pad)))
    # page metadata: one O(C) rebuild at population time (the per-step
    # decode path only ever delta-updates it — see lazy_promote_and_write)
    gvalid = jnp.arange(cache.budget)[None, None] < sel.count[..., None]
    meta = build_page_meta(gk, gvalid)
    return cache._replace(
        lk=lk, lv=lv, lg=lg, lpos=lpos,
        gk=gk, gv=gv, gpos=gpos, gcnt=sel.count,
        pkmin=meta.kmin.astype(cache.pkmin.dtype),
        pkmax=meta.kmax.astype(cache.pkmax.dtype),
        t=jnp.full_like(cache.t, s),
        ptr=jnp.full_like(cache.ptr, s % w),
    )


def layer_of(stack, layer):
    """Layer ``layer`` of a stacked cache pytree (``[L, B, ...]`` leaves)."""
    return jax.tree.map(lambda a: a[layer], stack)


def take_at(a: jax.Array, idx: Tuple[jax.Array, ...]) -> jax.Array:
    """``a[idx]`` at distinct indices known to lie in bounds (no clamp)."""
    return a.at[idx].get(mode="promise_in_bounds", unique_indices=True)


def put_at(a: jax.Array, idx: Tuple[jax.Array, ...], val: jax.Array
           ) -> jax.Array:
    """``a[idx] = val`` in place at distinct indices; an index past its
    axis drops that update, which is how a masked write keeps what the
    slot held without reading it."""
    return a.at[idx].set(val, mode="drop", unique_indices=True)


def lazy_promote_and_write(
    cache: DualCache,
    k_new: jax.Array,  # [B, H, hd] post-RoPE key of the freshly generated token
    v_new: jax.Array,
    g_new: jax.Array,  # [B, H]
    *,
    tau: float,
    layer: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,
    kernel: bool = False,
) -> DualCache:
    """Decode-phase cache update (paper Fig. 6d):

    1. inspect the victim at the ring pointer;
    2. promote it to the Global Cache iff its stored g >= tau (per head);
    3. overwrite the slot with the new token; advance the pointer.

    ``cache`` is one layer (``[B, ...]`` leaves) or, with ``layer`` set, a
    stack of layers (``[L, B, ...]`` leaves) written at index ``layer`` in
    place; the one-layer form is the stacked form with L = 1. Only the
    slots a position touches change: the victim's global slot, its page's
    metadata and the ring slot at the pointer. ``active`` [B] bool
    masks rows at the write: an inactive row's writes are dropped and its
    counters kept, so its state stays bit-identical.

    ``kernel``: write with the Pallas kernel (``kernels/cache_write.py``,
    one custom call a layer in place of some forty small scatters,
    gathers and selects). The caller sets it for a program on one TPU
    device; the jnp scatters below are what the SPMD partitioner splits
    over a mesh and what the CPU runs fast. The two are bit-identical.
    """
    if layer is None:
        out = lazy_promote_and_write(
            jax.tree.map(lambda a: a[None], cache), k_new, v_new, g_new,
            tau=tau, layer=jnp.zeros((), jnp.int32), active=active,
            kernel=kernel)
        return layer_of(out, 0)
    with jax.named_scope("cache_write"):
        _, b, h, w, d = cache.lk.shape
        c = cache.gk.shape[3]
        if active is None:
            active = jnp.ones((b,), bool)
        li = layer.astype(jnp.int32)
        ptr, t, gcnt = cache.ptr[li], cache.t[li], cache.gcnt[li]
        if kernel:
            return _kernel_write(cache, k_new, v_new, g_new, tau=tau, li=li,
                                 active=active, ptr=ptr, t=t, gcnt=gcnt)
        bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, h))
        hi = jnp.broadcast_to(jnp.arange(h)[None, :], (b, h))
        # per-head indices on every axis but hd, as for the global scatter
        # below: the ring keeps its layout across the write
        at_ring = jnp.broadcast_to(ptr[:, None], (b, h))
        # ---- victim ------------------------------------------------------
        vk = take_at(cache.lk, (li, bi, hi, at_ring))     # [B, H, hd]
        vv = take_at(cache.lv, (li, bi, hi, at_ring))
        # the small per-slot leaves (lg, lpos, gpos: no hd axis) are read
        # and written through a one-hot select over the layer's slice
        # (<= 128 KB a layer): a scatter of single elements along their
        # minor axis makes XLA's TPU backend move the whole stacked leaf
        # through on-chip memory at every layer
        at_ptr = jnp.arange(w)[None, :] == ptr[:, None]   # [B, W]
        lg_l, lpos_l = cache.lg[li], cache.lpos[li]
        vg = jnp.where(at_ptr[:, None], lg_l, 0.0).sum(-1)  # [B, H]
        vpos = jnp.where(at_ptr, lpos_l, 0).sum(-1)       # [B]
        victim_valid = (vpos >= 0) & active               # [B]
        promote = victim_valid[:, None] & (vg >= tau)     # [B, H]
        can_write = promote & (gcnt < c)
        # ---- promotion: true scatter (touches one slot per head, not the
        # whole cache — the jnp analogue of the paged in-place page write;
        # §Perf P3 iteration: the previous one-hot `where` rewrote the entire
        # global cache every step, tripling decode HBM traffic); a head
        # that does not promote aims past the budget and is dropped -------
        dest = jnp.minimum(gcnt, c - 1)                   # [B, H]
        at_dest = jnp.arange(c) == dest[..., None]        # [B, H, C]
        gpos_l = cache.gpos[li]
        to = (li, bi, hi, jnp.where(can_write, dest, c))
        gk = put_at(cache.gk, to, vk.astype(cache.gk.dtype))
        gv = put_at(cache.gv, to, vv.astype(cache.gv.dtype))
        gpos = cache.gpos.at[li].set(jnp.where(
            at_dest & can_write[..., None], vpos[:, None, None], gpos_l))
        gcnt = cache.gcnt.at[li].set(gcnt + can_write.astype(jnp.int32))
        overflow = cache.overflow.at[li].add(
            (promote & ~can_write).astype(jnp.int32))
        # incremental Quest metadata: fold the promoted key into the one page
        # its append lands in (same touched-slot discipline as the gk scatter)
        pkmin, pkmax = update_page_meta_on_write(
            cache.pkmin, cache.pkmax, dest, vk, can_write, layer=li)
        # ---- write the new token into the ring (scatter at ptr); an
        # inactive row aims past the ring and is dropped -------------------
        at_new = (li, bi, hi, jnp.where(active[:, None], at_ring, w))
        lk = put_at(cache.lk, at_new, k_new.astype(cache.lk.dtype))
        lv = put_at(cache.lv, at_new, v_new.astype(cache.lv.dtype))
        put = at_ptr & active[:, None]                    # [B, W]
        lg = cache.lg.at[li].set(jnp.where(
            put[:, None], g_new.astype(jnp.float32)[..., None], lg_l))
        lpos = cache.lpos.at[li].set(jnp.where(put, t[:, None], lpos_l))
        return cache._replace(
            lk=lk, lv=lv, lg=lg, lpos=lpos,
            gk=gk, gv=gv, gpos=gpos, gcnt=gcnt, overflow=overflow,
            pkmin=pkmin, pkmax=pkmax,
            t=cache.t.at[li].set(jnp.where(active, t + 1, t)),
            ptr=cache.ptr.at[li].set(jnp.where(active, (ptr + 1) % w, ptr)),
        )


def _kernel_write(cache: DualCache, k_new, v_new, g_new, *, tau, li, active,
                  ptr, t, gcnt) -> DualCache:
    """:func:`lazy_promote_and_write` of stacked leaves as one kernel
    call; the row counters ``t``/``ptr`` advance here."""
    w = cache.lk.shape[3]
    sc = jnp.concatenate([li[None], ptr, t, gcnt.reshape(-1),  # jaxlint: allow-concat(one scalar-prefetch vector of a layer's counters - no batch axis)
                          active.astype(jnp.int32)])
    (lk, lv, gk, gv, pkmin, pkmax, lg, lpos, gpos, gcnt_, overflow
     ) = dual_cache_write(
        sc, k_new.astype(cache.lk.dtype), v_new.astype(cache.lv.dtype),
        g_new.astype(jnp.float32), cache.lk, cache.lv, cache.gk, cache.gv,
        cache.pkmin, cache.pkmax, cache.lg, cache.lpos, cache.gpos,
        cache.gcnt, cache.overflow, tau=tau, page=PAGE_SIZE,
        big=float(np.asarray(META_BIG, cache.pkmin.dtype)),
        interpret=interpret_mode())
    return cache._replace(
        lk=lk, lv=lv, lg=lg, lpos=lpos, gk=gk, gv=gv, gpos=gpos, gcnt=gcnt_,
        overflow=overflow, pkmin=pkmin, pkmax=pkmax,
        t=cache.t.at[li].set(jnp.where(active, t + 1, t)),
        ptr=cache.ptr.at[li].set(jnp.where(active, (ptr + 1) % w, ptr)))


def cache_kv_for_attention(cache: DualCache) -> Tuple[jax.Array, ...]:
    """Concatenate [global | local] K/V with validity mask for decode
    attention. Returns (k [B,H,C+W,hd], v, valid [B,H,C+W])."""
    with jax.named_scope("kv_read"):
        k = jnp.concatenate([cache.gk, cache.lk], axis=2)
        v = jnp.concatenate([cache.gv, cache.lv], axis=2)
        c = cache.budget
        gvalid = jnp.arange(c)[None, None] < cache.gcnt[..., None]   # [B,H,C]
        lvalid = (cache.lpos >= 0)[:, None, :]                       # [B,1,W]
        lvalid = jnp.broadcast_to(lvalid, cache.lg.shape)
        return k, v, jnp.concatenate([gvalid, lvalid], axis=2)
