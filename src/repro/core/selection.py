"""Quest-style read-time KV Selection (paper §5.4 composability).

Quest (Tang et al., 2024) keeps page-level key min/max metadata and, per
query, attends only to the top-B pages ranked by an upper bound on the
page's attention score:  ub(page) = sum_d max(q_d * kmin_d, q_d * kmax_d).

Here selection operates either on a dense full cache ("Quest only") or on
the WG-KV global cache ("WG-KV + Quest") — admission shrinks the candidate
pool, selection then focuses the read.

Two consumption modes:

  * **mask** (``select_pages`` + ``token_mask_from_pages``): the original
    offline-composability surface — the full attention runs and losing
    pages are masked out. Zero FLOPs saved; useful for accuracy studies.
  * **gather** (``topk_page_ids`` + ``gather_pages``): the serving decode
    path — only the top-K pages' K/V rows are materialized into the
    attention einsum, so decode cost scales with the selection budget.
    Page metadata for this path lives as ``pkmin``/``pkmax`` leaves on
    the DualCache and is maintained *incrementally*
    (``update_page_meta_on_write``: a touched-page delta per promotion,
    not an O(C) rebuild per step).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

PAGE_SIZE = 16

# Sentinel filling empty page-metadata lanes: pkmin=+META_BIG,
# pkmax=-META_BIG. Any real key strictly shrinks the interval, so the
# incremental update needs no separate "page initialized" flag — and a
# from-scratch ``build_page_meta`` rebuild (which masks invalid lanes with
# the same sentinel) lands on identical values, which is what the
# incremental-vs-rebuild parity tests pin. Fits bfloat16 (max ~3.39e38).
META_BIG = 3e38


class PageMeta(NamedTuple):
    kmin: jax.Array  # [B, H, P, hd]
    kmax: jax.Array  # [B, H, P, hd]
    valid: jax.Array  # [B, H, P] page has >= 1 valid token


def n_pages(n_tokens: int, page_size: int = PAGE_SIZE) -> int:
    """Number of metadata pages covering ``n_tokens`` slots (ceil)."""
    return -(-n_tokens // page_size)


def init_page_meta(batch: int, n_kv_heads: int, n_tokens: int, head_dim: int,
                   *, page_size: int = PAGE_SIZE,
                   dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """Empty (pkmin, pkmax) leaves for a cache of ``n_tokens`` slots."""
    p = n_pages(n_tokens, page_size)
    big = jnp.asarray(META_BIG, dtype)
    return (jnp.full((batch, n_kv_heads, p, head_dim), big, dtype),
            jnp.full((batch, n_kv_heads, p, head_dim), -big, dtype))


def build_page_meta(k: jax.Array, valid: jax.Array,
                    page_size: int = PAGE_SIZE) -> PageMeta:
    """k: [B, H, S, hd]; valid: [B, H, S] -> page metadata. A ragged tail
    (S % page != 0) is padded internally with invalid lanes."""
    b, h, s, d = k.shape
    pad = (-s) % page_size
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, 0), (0, pad)))
    p = (s + pad) // page_size
    kp = k.reshape(b, h, p, page_size, d)
    vp = valid.reshape(b, h, p, page_size)
    big = jnp.asarray(META_BIG, k.dtype)
    kmin = jnp.where(vp[..., None], kp, big).min(axis=3)
    kmax = jnp.where(vp[..., None], kp, -big).max(axis=3)
    return PageMeta(kmin, kmax, vp.any(axis=3))


def page_valid_from_count(count: jax.Array, p: int,
                          page_size: int = PAGE_SIZE) -> jax.Array:
    """Contiguous-cache page validity: page i holds >= 1 valid token iff
    its first slot index is < count. count: [B, H] -> [B, H, P] bool."""
    first = jnp.arange(p, dtype=count.dtype) * page_size
    return first[None, None] < count[..., None]


def update_page_meta_on_write(
    pkmin: jax.Array,   # [B, H, P, hd], or [L, B, H, P, hd] with ``layer``
    pkmax: jax.Array,
    dest: jax.Array,    # [B, H] slot the appended entry lands in
    k_new: jax.Array,   # [B, H, hd] the appended key
    can_write: jax.Array,  # [B, H] bool: append actually happens
    *,
    page_size: int = PAGE_SIZE,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Incremental metadata maintenance for an append-only cache: fold one
    new key into the single page it touches (true scatter — O(hd) state
    touched per head, never an O(C) rebuild). A write at a page boundary
    starts the page fresh from the sentinel, so stale metadata from
    pre-eviction occupants can never widen the bound. With ``layer`` the
    metadata is a stack of layers, written at that index in place; a head
    that does not append leaves its page as it was."""
    b, h = dest.shape
    pg = dest // page_size
    fresh = (dest % page_size) == 0
    bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, h))
    hi = jnp.broadcast_to(jnp.arange(h)[None, :], (b, h))
    lead = () if layer is None else (layer,)
    at = lead + (bi, hi, pg)
    old_lo = pkmin.at[at].get(mode="promise_in_bounds", unique_indices=True)
    old_hi = pkmax.at[at].get(mode="promise_in_bounds", unique_indices=True)
    big = jnp.asarray(META_BIG, pkmin.dtype)
    base_lo = jnp.where(fresh[..., None], big, old_lo)
    base_hi = jnp.where(fresh[..., None], -big, old_hi)
    kn = k_new.astype(pkmin.dtype)
    # a head that does not append aims past the pages and is dropped
    to = lead + (bi, hi, jnp.where(can_write, pg, pkmin.shape[-2]))
    put = lambda a, v: a.at[to].set(v, mode="drop", unique_indices=True)
    return (put(pkmin, jnp.minimum(base_lo, kn)),
            put(pkmax, jnp.maximum(base_hi, kn)))


def page_upper_bound(q: jax.Array, meta: PageMeta) -> jax.Array:
    """q: [B, Hq, hd] (Hq = G * Hkv); meta per kv head. Returns ub scores
    aggregated over the query group: [B, Hkv, P]."""
    b, hq, d = q.shape
    hkv = meta.kmin.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    lo = jnp.einsum("bhgd,bhpd->bhgp", qg, meta.kmin.astype(q.dtype))
    hi = jnp.einsum("bhgd,bhpd->bhgp", qg, meta.kmax.astype(q.dtype))
    ub = jnp.maximum(lo, hi).sum(axis=2) / g  # mean over group
    return jnp.where(meta.valid, ub, -jnp.inf)


def select_pages(q: jax.Array, meta: PageMeta, budget_pages: int) -> jax.Array:
    """Top-``budget_pages`` page mask per kv head: [B, Hkv, P] bool."""
    ub = page_upper_bound(q, meta)
    p = ub.shape[-1]
    budget_pages = min(budget_pages, p)
    thresh = jax.lax.top_k(ub, budget_pages)[0][..., -1:]
    return (ub >= thresh) & jnp.isfinite(ub)


def token_mask_from_pages(page_mask: jax.Array,
                          page_size: int = PAGE_SIZE) -> jax.Array:
    """[B, H, P] -> [B, H, P*page_size]."""
    return jnp.repeat(page_mask, page_size, axis=-1)


def topk_page_ids(q: jax.Array, meta: PageMeta,
                  budget_pages: int) -> Tuple[jax.Array, jax.Array]:
    """Top-``budget_pages`` page IDs per kv head, sorted ascending:
    (ids [B, Hkv, K] int32, n_selected [B, Hkv] int32 — selected pages
    with a finite ub, i.e. actually-valid pages in the gather).

    Ascending order matters: when K covers every page the ID list is the
    identity permutation, so the gathered attention reduces over the same
    lanes in the same order as the full path — greedy streams stay
    byte-identical to selection-off (the parity acceptance axis)."""
    ub = page_upper_bound(q, meta)
    k = min(budget_pages, ub.shape[-1])
    scores, idx = jax.lax.top_k(ub, k)
    n_sel = jnp.isfinite(scores).sum(axis=-1).astype(jnp.int32)
    return jnp.sort(idx, axis=-1).astype(jnp.int32), n_sel


def gather_pages(gk: jax.Array, gv: jax.Array, gcnt: jax.Array,
                 page_ids: jax.Array, *, page_size: int = PAGE_SIZE
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize only the selected pages' K/V rows for attention.

    gk/gv: [B, H, C, hd] contiguous cache; gcnt: [B, H] valid counts;
    page_ids: [B, H, K] (sorted). Returns (k [B, H, K*page, hd], v,
    valid [B, H, K*page]) — attention cost now scales with K, not C."""
    b, h, c, _ = gk.shape
    tok = (page_ids[..., None] * page_size
           + jnp.arange(page_size, dtype=page_ids.dtype)[None, None, None])
    tok = tok.reshape(b, h, -1)                       # [B, H, K*page]
    valid = tok < gcnt[..., None]
    tokc = jnp.minimum(tok, c - 1)                    # clamp ragged tail
    k = jnp.take_along_axis(gk, tokc[..., None], axis=2)
    v = jnp.take_along_axis(gv, tokc[..., None], axis=2)
    return k, v, valid
