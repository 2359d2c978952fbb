"""SnapKV-like post-write Eviction (paper §5.4, Appendix K.1).

Importance of key j is scored from the most recent W_obs queries:
  A^(h)  = softmax(Q_obs^(h) K^T / sqrt(d))          per query head in group
  S_raw_j = sum_i max_h A[i, j]                       aggregate
  S       = maxpool(S_raw, W_pool)                    local smoothing
When the (global) cache exceeds its hard budget, the bottom ``evict_frac``
of valid entries are dropped and the cache is compacted.

Composability: WG-KV admission flattens cache growth so eviction triggers
less often and prunes *obsolete* rather than *critical* context (Fig. 2b).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.dual_cache import DualCache
from repro.core.selection import build_page_meta


class ObsWindow(NamedTuple):
    """Ring buffer of recent query vectors (per q-head)."""

    q: jax.Array    # [B, Hq, W_obs, hd]
    n: jax.Array    # [B] valid count (saturates at W_obs)

    @property
    def w_obs(self) -> int:
        return self.q.shape[2]


def init_obs(batch: int, n_q_heads: int, head_dim: int, w_obs: int = 256,
             dtype=jnp.float32) -> ObsWindow:
    return ObsWindow(
        q=jnp.zeros((batch, n_q_heads, w_obs, head_dim), dtype),
        n=jnp.zeros((batch,), jnp.int32),
    )


def push_query(obs: ObsWindow, q: jax.Array, *,
               active: Optional[jax.Array] = None,
               at: Tuple[jax.Array, ...] = ()) -> ObsWindow:
    """q: [B, Hq, hd] — append to ring, writing the one slot each row's
    count points at. ``at`` indexes the window in a stack of windows (its
    leading axes); ``active`` [B] bool masks rows: an inactive row writes
    back that slot's query and keeps its count."""
    w = obs.q.shape[len(at) + 2]
    n = obs.n[at]
    b = n.shape[0]
    if active is None:
        active = jnp.ones((b,), bool)
    idx = at + (jnp.arange(b), slice(None), n % w)
    qn = jnp.where(active[:, None, None], q.astype(obs.q.dtype), obs.q[idx])
    return ObsWindow(q=obs.q.at[idx].set(qn),
                     n=obs.n.at[at].set(jnp.where(active, n + 1, n)))


def snap_scores(obs: ObsWindow, k: jax.Array, valid: jax.Array,
                w_pool: int = 5) -> jax.Array:
    """k: [B, Hkv, N, hd]; valid: [B, Hkv, N]. Returns scores [B, Hkv, N]."""
    b, hq, w, d = obs.q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = obs.q.reshape(b, hkv, g, w, d)
    logits = jnp.einsum("bhgwd,bhnd->bhgwn", qg, k.astype(obs.q.dtype))
    logits = logits / jnp.sqrt(d).astype(logits.dtype)
    qvalid = (jnp.arange(w)[None] < jnp.minimum(obs.n, w)[:, None])  # [B, W]
    mask = valid[:, :, None, None, :] & qvalid[:, None, None, :, None]
    logits = jnp.where(mask, logits, -1e30)
    a = jax.nn.softmax(logits, axis=-1)
    a = jnp.where(mask, a, 0.0)
    raw = a.max(axis=2).sum(axis=2)  # max over group heads, sum over window
    # local smoothing: max-pool width w_pool along N
    pads = w_pool // 2
    padded = jnp.pad(raw, ((0, 0), (0, 0), (pads, pads)), constant_values=-jnp.inf)
    pooled = jnp.max(
        jnp.stack([padded[..., i:i + raw.shape[-1]] for i in range(w_pool)], 0), 0
    )
    return jnp.where(valid, pooled, -jnp.inf)


def evict_global(cache: DualCache, scores: jax.Array, *,
                 evict_frac: float = 0.10) -> DualCache:
    """Drop the bottom ``evict_frac`` of *valid* global entries per head and
    compact. scores: [B, Hkv, C] (−inf on invalid)."""
    b, h, c, d = cache.gk.shape
    n_evict = jnp.maximum((cache.gcnt * evict_frac).astype(jnp.int32), 1)
    n_evict = jnp.where(cache.gcnt > 0, n_evict, 0)
    # rank: keep highest-score entries, preserve relative position order
    keep_n = cache.gcnt - n_evict  # [B, H]
    order = jnp.argsort(-scores, axis=-1)  # descending score
    rank_of_slot = jnp.argsort(order, axis=-1)  # rank per original slot
    keep = rank_of_slot < keep_n[..., None]  # [B, H, C] keep mask
    # compact: stable-sort slots by (kept? position : +inf)
    poskey = jnp.where(keep, cache.gpos, jnp.iinfo(jnp.int32).max)
    perm = jnp.argsort(poskey, axis=-1)  # kept entries first, ascending pos
    take = lambda x: jnp.take_along_axis(x, perm[..., None], axis=2)
    newcnt = keep.sum(-1).astype(jnp.int32)
    valid = jnp.arange(c)[None, None] < newcnt[..., None]
    newgk = jnp.where(valid[..., None], take(cache.gk), 0)
    # compaction permutes every slot, so the Quest page metadata is rebuilt
    # here from scratch — eviction is the rare O(C log C) event already; the
    # per-step decode path stays delta-only (see lazy_promote_and_write)
    meta = build_page_meta(newgk, valid)
    return cache._replace(
        gk=newgk,
        gv=jnp.where(valid[..., None], take(cache.gv), 0),
        gpos=jnp.where(valid, jnp.take_along_axis(cache.gpos, perm, axis=2), 0),
        gcnt=newcnt,
        pkmin=meta.kmin.astype(cache.pkmin.dtype),
        pkmax=meta.kmax.astype(cache.pkmax.dtype),
    )


def maybe_evict(cache: DualCache, obs: ObsWindow, *, hard_budget: int,
                evict_frac: float = 0.10,
                active: Optional[jax.Array] = None
                ) -> tuple[DualCache, jax.Array]:
    """Trigger eviction when any head's global count reaches ``hard_budget``
    (in ``active`` [B] rows only, when given). Returns (cache, triggered
    [B, Hkv] bool); rows that did not trigger keep their values."""
    gvalid = jnp.arange(cache.budget)[None, None] < cache.gcnt[..., None]
    trig = cache.gcnt >= hard_budget  # [B, H]
    if active is not None:
        trig = trig & active[:, None]
    scores = snap_scores(obs, cache.gk, gvalid)
    evicted = evict_global(cache, scores, evict_frac=evict_frac)
    pick = lambda new, old: jnp.where(
        trig[..., None, None] if old.ndim == 4 else
        (trig[..., None] if old.ndim == 3 else trig), new, old)
    merged = cache._replace(
        gk=pick(evicted.gk, cache.gk),
        gv=pick(evicted.gv, cache.gv),
        gpos=pick(evicted.gpos, cache.gpos),
        gcnt=jnp.where(trig, evicted.gcnt, cache.gcnt),
        pkmin=pick(evicted.pkmin, cache.pkmin),
        pkmax=pick(evicted.pkmax, cache.pkmax),
    )
    return merged, trig
