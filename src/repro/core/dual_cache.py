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

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.admission import select_global
from repro.core.selection import (
    build_page_meta, init_page_meta, update_page_meta_on_write,
)


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


def lazy_promote_and_write(
    cache: DualCache,
    k_new: jax.Array,  # [B, H, hd] post-RoPE key of the freshly generated token
    v_new: jax.Array,
    g_new: jax.Array,  # [B, H]
    *,
    tau: float,
) -> DualCache:
    """Decode-phase cache update (paper Fig. 6d):

    1. inspect the victim at the ring pointer;
    2. promote it to the Global Cache iff its stored g >= tau (per head);
    3. overwrite the slot with the new token; advance the pointer.
    """
    with jax.named_scope("cache_write"):
        b, h, w, d = cache.lk.shape
        c = cache.budget
        barange = jnp.arange(b)
        # ---- victim ------------------------------------------------------
        vk = cache.lk[barange, :, cache.ptr]              # [B, H, hd]
        vv = cache.lv[barange, :, cache.ptr]
        vg = cache.lg[barange, :, cache.ptr]              # [B, H]
        vpos = cache.lpos[barange, cache.ptr]             # [B]
        victim_valid = vpos >= 0                          # [B]
        promote = victim_valid[:, None] & (vg >= tau)     # [B, H]
        can_write = promote & (cache.gcnt < c)
        # ---- promotion: true scatter (touches one slot per head, not the
        # whole cache — the jnp analogue of the paged in-place page write;
        # §Perf P3 iteration: the previous one-hot `where` rewrote the entire
        # global cache every step, tripling decode HBM traffic) ----------
        dest = jnp.minimum(cache.gcnt, c - 1)             # [B, H]
        bi = barange[:, None].repeat(h, 1)                # [B, H]
        hi = jnp.arange(h)[None, :].repeat(b, 0)
        old_k = cache.gk[bi, hi, dest]
        old_v = cache.gv[bi, hi, dest]
        old_p = cache.gpos[bi, hi, dest]
        up_k = jnp.where(can_write[..., None], vk.astype(cache.gk.dtype), old_k)
        up_v = jnp.where(can_write[..., None], vv.astype(cache.gv.dtype), old_v)
        up_p = jnp.where(can_write, vpos[:, None], old_p)
        gk = cache.gk.at[bi, hi, dest].set(up_k)
        gv = cache.gv.at[bi, hi, dest].set(up_v)
        gpos = cache.gpos.at[bi, hi, dest].set(up_p)
        gcnt = cache.gcnt + can_write.astype(jnp.int32)
        overflow = cache.overflow + (promote & ~can_write).astype(jnp.int32)
        # incremental Quest metadata: fold the promoted key into the one page
        # its append lands in (same touched-slot discipline as the gk scatter)
        pkmin, pkmax = update_page_meta_on_write(
            cache.pkmin, cache.pkmax, dest, vk, can_write)
        # ---- write the new token into the ring (scatter at ptr) ----------
        lk = cache.lk.at[barange, :, cache.ptr].set(k_new.astype(cache.lk.dtype))
        lv = cache.lv.at[barange, :, cache.ptr].set(v_new.astype(cache.lv.dtype))
        lg = cache.lg.at[barange, :, cache.ptr].set(g_new.astype(jnp.float32))
        lpos = cache.lpos.at[barange, cache.ptr].set(cache.t)
        return cache._replace(
            lk=lk, lv=lv, lg=lg, lpos=lpos,
            gk=gk, gv=gv, gpos=gpos, gcnt=gcnt, overflow=overflow,
            pkmin=pkmin, pkmax=pkmax,
            t=cache.t + 1, ptr=(cache.ptr + 1) % w,
        )


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
