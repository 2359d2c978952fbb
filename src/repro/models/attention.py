"""GQA attention with first-class WG-KV integration.

Modes:
  * train  — dense causal attention, optionally write-gated (log-space bias,
    paper §3.2) for gate training, or hard vertical-slash for eval.
  * prefill (budgeted, production) — banded local attention (the "slash")
    + budgeted global attention over admitted tokens (the "vertical"),
    sub-quadratic: O(S * (2*W + C)) instead of O(S^2). Populates the dual
    cache.
  * decode — one token vs. the dual cache (global ‖ local ‖ self) with
    lazy promotion, or vs. a dense cache for the full-attention baseline.

All paths are pure jnp (the pjit/dry-run path); Pallas TPU kernels in
repro/kernels mirror the train/prefill/decode hot loops and are validated
against these semantics.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import masks as M
from repro.core import selection as SEL
from repro.core.admission import GlobalSelection, select_global
from repro.core.dual_cache import (
    DualCache,
    cache_kv_for_attention,
    layer_of,
    lazy_promote_and_write,
)
from repro.core.gate import gate_scores, init_gate
from repro.models import layers as L

Params = Dict[str, jax.Array]


# ==========================================================================
# dense-cache baseline (full attention)
# ==========================================================================
class DenseCache(NamedTuple):
    k: jax.Array   # [B, Hkv, S_max, hd]
    v: jax.Array
    t: jax.Array   # [B] int32 current length


def init_dense_cache(batch: int, n_kv: int, head_dim: int, max_len: int,
                     dtype=jnp.float32) -> DenseCache:
    z = jnp.zeros((batch, n_kv, max_len, head_dim), dtype)
    return DenseCache(z, z, jnp.zeros((batch,), jnp.int32))


def dense_cache_append(cache: DenseCache, k_new: jax.Array, v_new: jax.Array,
                       *, layer: Optional[jax.Array] = None,
                       active: Optional[jax.Array] = None) -> DenseCache:
    """k_new: [B, H, hd] appended at per-batch position t (one scattered
    slot; a write at or past capacity is dropped). With ``layer`` the cache
    is a stack of layers written at that index in place; ``active`` [B]
    bool masks rows at the write (an inactive row writes back its slot
    and keeps its length)."""
    if layer is None:
        out = dense_cache_append(jax.tree.map(lambda a: a[None], cache),
                                 k_new, v_new, layer=jnp.zeros((), jnp.int32),
                                 active=active)
        return jax.tree.map(lambda a: a[0], out)
    b = k_new.shape[0]
    if active is None:
        active = jnp.ones((b,), bool)
    t = cache.t[layer]
    idx = (layer, jnp.arange(b), slice(None), t)
    a3 = active[:, None, None]
    k = cache.k.at[idx].set(
        jnp.where(a3, k_new.astype(cache.k.dtype), cache.k[idx]), mode="drop")
    v = cache.v.at[idx].set(
        jnp.where(a3, v_new.astype(cache.v.dtype), cache.v[idx]), mode="drop")
    return DenseCache(k, v, cache.t.at[layer].set(jnp.where(active, t + 1, t)))


# ==========================================================================
# parameter init
# ==========================================================================
def init_attention(key: jax.Array, cfg: ModelConfig, *, kind: str = "self",
                   with_gate: Optional[bool] = None) -> Params:
    """kind: "self" (causal), "cross" (enc-dec), "enc" (bidirectional)."""
    dt = jnp.dtype(cfg.param_dtype)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    p: Params = {
        "w_q": L.dense_init(ks[0], (d, hq * hd), dt),
        "w_k": L.dense_init(ks[1], (d, hkv * hd), dt),
        "w_v": L.dense_init(ks[2], (d, hkv * hd), dt),
        "w_o": L.dense_init(ks[3], (hq * hd, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    if with_gate is None:
        with_gate = cfg.wgkv.enabled and kind != "enc"
    if with_gate:
        p["gate"] = init_gate(ks[4], cfg)
    return p


# ==========================================================================
# projections
# ==========================================================================
def _heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    """[B, S, n*hd] -> [B, n, S, hd]"""
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd).transpose(0, 2, 1, 3)


def _qk_norm(p: Params, q, k):
    if "q_norm" in p:
        q = L.rmsnorm_nowt(q) * p["q_norm"].astype(q.dtype)
        k = L.rmsnorm_nowt(k) * p["k_norm"].astype(k.dtype)
    return q, k


def project_qkv(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (q_rope [B,Hq,S,hd], k_pre [B,Hkv,S,hd], k_rope, v).

    positions: [B, S] int32, or [3, B, S] for M-RoPE archs.
    """
    q = _heads(x @ p["w_q"].astype(x.dtype), cfg.n_heads, cfg.head_dim)
    k_pre = _heads(x @ p["w_k"].astype(x.dtype), cfg.n_kv_heads, cfg.head_dim)
    v = _heads(x @ p["w_v"].astype(x.dtype), cfg.n_kv_heads, cfg.head_dim)
    q, k_pre = _qk_norm(p, q, k_pre)
    if cfg.mrope and positions.ndim == 3:
        pos3q = positions[:, :, None, :]  # [3, B, 1, S] broadcast over heads
        q_r = L.apply_mrope(q, pos3q, cfg.rope_theta)
        k_r = L.apply_mrope(k_pre, pos3q, cfg.rope_theta)
    elif cfg.rope_theta > 0:
        posq = positions[:, None, :]  # [B, 1, S]
        q_r = L.apply_rope(q, posq, cfg.rope_theta)
        k_r = L.apply_rope(k_pre, posq, cfg.rope_theta)
    else:
        q_r, k_r = q, k_pre
    return q_r, k_pre, k_r, v


def compute_gates(p: Params, k_pre: jax.Array, k_rope: jax.Array) -> jax.Array:
    """g: [B, Hkv, S] (float32)."""
    return gate_scores(p["gate"], k_pre, k_rope)


# ==========================================================================
# scaled-dot-product attention with optional query chunking
# ==========================================================================
def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         bias_fn: Callable[[int, int], jax.Array],
         *, q_chunk: Optional[int] = None) -> jax.Array:
    """q: [B,Hq,Sq,hd]; k,v: [B,Hkv,Sk,hd]. ``bias_fn(q_start, q_len)``
    returns an additive f32 bias broadcastable to [B,Hkv,G,q_len,Sk]
    (use masks.NEG_INF for disallowed). Chunking bounds the materialized
    score tensor for long sequences (roofline-corrected; see
    roofline/analysis.py hidden-loop accounting)."""
    b, hq, sq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, hd)
    scale = hd ** -0.5

    def block(q_blk: jax.Array, q_start: int, q_len: int) -> jax.Array:
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k).astype(jnp.float32)
        logits = logits * scale + bias_fn(q_start, q_len)
        w = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(v.dtype), v)
        return o

    if q_chunk is None or q_chunk >= sq:
        out = block(qg, 0, sq)
    else:
        assert sq % q_chunk == 0, (sq, q_chunk)
        n = sq // q_chunk

        def body(carry, i):
            q_blk = jax.lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, axis=3)
            return carry, block(q_blk, i * q_chunk, q_chunk)

        # bias_fn must be traceable with dynamic q_start
        _, outs = jax.lax.scan(body, 0, jnp.arange(n))
        out = jnp.moveaxis(outs, 0, 3).reshape(b, hkv, g, sq, hd)
    return out.reshape(b, hq, sq, hd)


# ==========================================================================
# train-mode forward (dense; teacher / write-gated student / hard eval)
# ==========================================================================
def attn_train(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
               *, gate_mode: str = "off", window: Optional[int] = None,
               gate_override: Optional[jax.Array] = None,
               q_chunk: Optional[int] = None
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """gate_mode: "off" (full/windowed causal teacher), "gated"
    (differentiable log-space write-gate bias), "hard" (binary
    vertical-slash mask at tau). ``window``: sliding-window width for
    local_attn blocks (doubles as W_local in the gate bias)."""
    b, s, _ = x.shape
    q, k_pre, k_rope, v = project_qkv(p, cfg, x, positions)
    g = None
    if gate_mode != "off":
        g = gate_override if gate_override is not None else compute_gates(p, k_pre, k_rope)
    w_local = window if window is not None else cfg.wgkv.w_local

    def bias_fn(q_start, q_len):
        qi = jnp.arange(q_len)[:, None] + q_start
        kj = jnp.arange(s)[None, :]
        causal = qi >= kj
        in_win = causal & (qi - kj < w_local)
        if gate_mode == "off":
            vis = in_win if window is not None else causal
            return jnp.where(vis, 0.0, M.NEG_INF)[None, None, None]
        if gate_mode == "gated":
            logg = jnp.log(g + cfg.wgkv.log_eps)[:, :, None, None, :]  # [B,H,1,1,S]
            bias = jnp.where(in_win[None, None, None], 0.0, logg)
            return jnp.where(causal[None, None, None], bias, M.NEG_INF)
        if gate_mode == "hard":
            admitted = (g >= cfg.wgkv.tau) | (kj[0] < cfg.wgkv.sink)[None, None]
            vis = in_win[None, None, None] | admitted[:, :, None, None, :]
            return jnp.where(vis & causal[None, None, None], 0.0, M.NEG_INF)
        raise ValueError(gate_mode)

    out = sdpa(q, k_rope, v, bias_fn, q_chunk=q_chunk)
    b_, hq, s_, hd = out.shape
    y = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ p["w_o"].astype(x.dtype)
    return y, g


# ==========================================================================
# budgeted vertical-slash prefill (production, sub-quadratic)
# ==========================================================================
class PrefillResult(NamedTuple):
    out: jax.Array           # [B, S, D]
    k_rope: jax.Array        # [B, Hkv, S, hd]
    v: jax.Array
    g: jax.Array             # [B, Hkv, S]
    sel: GlobalSelection


def attn_prefill_budgeted(p: Params, cfg: ModelConfig, x: jax.Array,
                          positions: jax.Array, *, budget: int,
                          window: Optional[int] = None,
                          gate_override: Optional[jax.Array] = None,
                          block_chunk: Optional[int] = None) -> PrefillResult:
    """Vertical-slash attention (paper §4.2), budgeted for static shapes.

    Every query attends to (a) the slash: its local window of width W via
    banded block attention (key blocks b-1, b for query block b) and (b)
    the vertical: up to ``budget`` admitted tokens (g >= tau) strictly
    older than the window. One softmax over [2W | C] per query.
    """
    b, s, d_model = x.shape
    w = window if window is not None else cfg.wgkv.w_local
    assert s % w == 0, f"seq {s} must be a multiple of the window {w}"
    nb = s // w
    q, k_pre, k_rope, v = project_qkv(p, cfg, x, positions)
    g = gate_override if gate_override is not None else compute_gates(p, k_pre, k_rope)
    sel = select_global(g, budget=budget, tau=cfg.wgkv.tau, sink=cfg.wgkv.sink,
                        exclude_from=s - min(w, s))
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    grp = hq // hkv
    c = sel.idx.shape[-1]

    # gather the vertical (global) keys/values once: [B, Hkv, C, hd]
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(hkv)[None, :, None]
    kg = k_rope[bi, hi, sel.idx]
    vg = v[bi, hi, sel.idx]
    gpos = jnp.where(sel.valid, sel.idx, jnp.iinfo(jnp.int32).max)  # invalid -> never visible

    # block views
    qb = q.reshape(b, hkv, grp, nb, w, hd)
    kb = k_rope.reshape(b, hkv, nb, w, hd)
    vb = v.reshape(b, hkv, nb, w, hd)
    zeros = jnp.zeros_like(kb[:, :, :1])
    k_prev = jnp.concatenate([zeros, kb[:, :, :-1]], axis=2)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :, :1]), vb[:, :, :-1]], axis=2)
    k_band = jnp.concatenate([k_prev, kb], axis=3)   # [B,Hkv,nb,2W,hd]
    v_band = jnp.concatenate([v_prev, vb], axis=3)
    scale = hd ** -0.5

    def one_block(nb_idx_arr):
        """Compute attention for a slice of query blocks (indices array)."""
        qs = qb[:, :, :, nb_idx_arr]                     # [B,H,G,nbc,W,hd]
        ks = k_band[:, :, nb_idx_arr]                    # [B,H,nbc,2W,hd]
        vs = v_band[:, :, nb_idx_arr]
        # slash logits [B,H,G,nbc,W,2W]
        sl = jnp.einsum("bhgnqd,bhnkd->bhgnqk", qs, ks).astype(jnp.float32) * scale
        qi_rel = jnp.arange(w)[:, None]                  # in-block query offset
        kj_rel = jnp.arange(2 * w)[None, :] - w          # key offset rel. block start
        band_ok = (qi_rel >= kj_rel) & (qi_rel - kj_rel < w)
        # first block has no previous block
        first = (nb_idx_arr == 0)[:, None, None]         # [nbc,1,1]
        band_ok = band_ok[None] & (~first | (kj_rel >= 0)[None])
        sl = jnp.where(band_ok[None, None, None], sl, M.NEG_INF)
        # vertical logits [B,H,G,nbc,W,C]
        vl = jnp.einsum("bhgnqd,bhcd->bhgnqc", qs, kg).astype(jnp.float32) * scale
        qabs = nb_idx_arr[:, None] * w + jnp.arange(w)[None]   # [nbc, W]
        # global token j visible iff j <= i - W  (disjoint from the slash)
        vis = gpos[:, :, None, None, :] <= (qabs[..., None] - w)[None, None]
        vl = jnp.where(vis[:, :, None], vl, M.NEG_INF)
        logits = jnp.concatenate([sl, vl], axis=-1)
        wts = jax.nn.softmax(logits, axis=-1)
        o_sl = jnp.einsum("bhgnqk,bhnkd->bhgnqd", wts[..., : 2 * w].astype(vs.dtype), vs)
        o_vl = jnp.einsum("bhgnqc,bhcd->bhgnqd", wts[..., 2 * w:].astype(vg.dtype), vg)
        return o_sl + o_vl                               # [B,H,G,nbc,W,hd]

    if block_chunk is None or block_chunk >= nb:
        out = one_block(jnp.arange(nb))
    else:
        assert nb % block_chunk == 0

        def body(carry, i):
            idx = i * block_chunk + jnp.arange(block_chunk)
            return carry, one_block(idx)

        _, outs = jax.lax.scan(body, 0, jnp.arange(nb // block_chunk))
        # outs: [nchunks, B, H, G, block_chunk, W, hd] -> [B,H,G,nb,W,hd]
        out = jnp.moveaxis(outs, 0, 3).reshape(b, hkv, grp, nb, w, hd)
    y = out.reshape(b, hkv * grp, s, hd).transpose(0, 2, 1, 3)
    y = y.reshape(b, s, hq * hd) @ p["w_o"].astype(x.dtype)
    return PrefillResult(y, k_rope, v, g, sel)


def attn_prefill_full(p: Params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array, *, window: Optional[int] = None,
                      q_chunk: Optional[int] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-attention baseline prefill: dense causal (optionally windowed).
    Returns (out, k_rope, v)."""
    q, k_pre, k_rope, v = project_qkv(p, cfg, x, positions)
    b, s, _ = x.shape

    def bias_fn(q_start, q_len):
        qi = jnp.arange(q_len)[:, None] + q_start
        kj = jnp.arange(s)[None, :]
        ok = qi >= kj
        if window is not None:
            ok = ok & (qi - kj < window)
        return jnp.where(ok, 0.0, M.NEG_INF)[None, None, None]

    out = sdpa(q, k_rope, v, bias_fn, q_chunk=q_chunk)
    hq, hd = cfg.n_heads, cfg.head_dim
    y = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ p["w_o"].astype(x.dtype)
    return y, k_rope, v


# ==========================================================================
# decode
# ==========================================================================
def _rope_single(cfg: ModelConfig, x: jax.Array, t: jax.Array) -> jax.Array:
    """x: [B, H, hd] at per-batch position t [B]. (M-RoPE with equal
    (t,t,t) ids degenerates to standard RoPE — used for text decode.)"""
    if cfg.rope_theta <= 0:
        return x
    return L.apply_rope(x[:, :, None, :], t[:, None, None], cfg.rope_theta)[:, :, 0]


def attn_decode_wgkv(p: Params, cfg: ModelConfig, x_t: jax.Array,
                     cache: DualCache, *,
                     layer: Optional[jax.Array] = None,
                     active: Optional[jax.Array] = None,
                     write_kernel: bool = False,
                     gate_override: Optional[jax.Array] = None,
                     token_select_fn: Optional[Callable] = None,
                     select_pages_k: Optional[int] = None
                     ) -> Tuple[jax.Array, DualCache, jax.Array,
                                Optional[jax.Array]]:
    """One decode step against the dual cache. x_t: [B, D].

    Order matters for exact equivalence with the dense vertical-slash mask:
    the cache is updated FIRST (victim at age W promoted iff admitted, new
    token written into the ring), then attention runs over the updated
    cache — so the local window seen by query t is exactly {t-W+1..t} and
    the just-exited token is visible iff admitted, matching
    ``masks.vertical_slash_mask`` semantics token-for-token.

    ``token_select_fn(cache, q) -> [B, Hkv, C+W]``: optional read-time
    Selection mask (Quest composition) computed on the updated cache,
    further restricting visible entries — full-width einsum, no FLOPs
    saved.

    ``select_pages_k``: GATHERED read-time Selection — score the cache's
    incremental page metadata (pkmin/pkmax) against the live query, take
    the top-K pages, and run attention over only the gathered
    ``K*PAGE_SIZE + W`` entries, so decode cost scales with the selection
    budget instead of the admission budget. When K covers every page the
    sorted page-ID gather is the identity permutation and the output is
    bit-identical to the full path. Mutually exclusive with
    ``token_select_fn``.

    ``layer``: ``cache`` is a stack of layers (``[L, B, ...]`` leaves) and
    this step reads and writes layer ``layer`` of it in place; ``active``
    [B] bool masks rows at the write, and ``write_kernel`` makes it the
    Pallas kernel (``lazy_promote_and_write``'s ``kernel``).

    Returns (out [B, D], new cache, g_new [B, Hkv], sel_pages) where
    sel_pages is [B, Hkv] valid selected-page counts (None when the
    gathered path is off); the new cache is stacked when ``cache`` is."""
    b, d_model = x_t.shape
    t = cache.t if layer is None else cache.t[layer]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("proj"):
        q = (x_t @ p["w_q"].astype(x_t.dtype)).reshape(b, hq, hd)
        k_pre = (x_t @ p["w_k"].astype(x_t.dtype)).reshape(b, hkv, hd)
        v_new = (x_t @ p["w_v"].astype(x_t.dtype)).reshape(b, hkv, hd)
        q, k_pre = _qk_norm(p, q, k_pre)
        q = _rope_single(cfg, q, t)
        k_new = _rope_single(cfg, k_pre, t)
    if gate_override is not None:
        g_new = gate_override
    else:
        with jax.named_scope("write_gate"):
            g_new = gate_scores(p["gate"], k_pre[:, :, None],
                                k_new[:, :, None])[..., 0]

    # update first (promote victim, write self), then attend — see docstring
    new_stack = lazy_promote_and_write(cache, k_new, v_new, g_new,
                                       tau=cfg.wgkv.tau, layer=layer,
                                       active=active, kernel=write_kernel)
    new_cache = new_stack if layer is None else layer_of(new_stack, layer)
    sel_pages = None
    if select_pages_k is not None:
        assert token_select_fn is None, "mask and gather selection are exclusive"
        c = new_cache.budget
        assert c % SEL.PAGE_SIZE == 0, \
            "global budget must be page-aligned for gathered Quest selection"
        p_pages = c // SEL.PAGE_SIZE
        with jax.named_scope("kv_read"):
            meta = SEL.PageMeta(
                new_cache.pkmin, new_cache.pkmax,
                SEL.page_valid_from_count(new_cache.gcnt, p_pages))
            ids, sel_pages = SEL.topk_page_ids(q, meta, select_pages_k)
            gk_s, gv_s, gvalid = SEL.gather_pages(
                new_cache.gk, new_cache.gv, new_cache.gcnt, ids)
            k_all = jnp.concatenate([gk_s, new_cache.lk], axis=2)
            v_all = jnp.concatenate([gv_s, new_cache.lv], axis=2)
            lvalid = jnp.broadcast_to((new_cache.lpos >= 0)[:, None, :],
                                      new_cache.lg.shape)
            valid = jnp.concatenate([gvalid, lvalid], axis=2)
    else:
        k_all, v_all, valid = cache_kv_for_attention(new_cache)      # [B,H,C+W,*]
        if token_select_fn is not None:
            with jax.named_scope("kv_read"):
                valid = valid & token_select_fn(new_cache, q)
    with jax.named_scope("attention"):
        grp = hq // hkv
        qg = q.reshape(b, hkv, grp, hd)
        logits = jnp.einsum("bhgd,bhkd->bhgk", qg, k_all).astype(jnp.float32)
        logits = logits * (hd ** -0.5)
        logits = jnp.where(valid[:, :, None], logits, M.NEG_INF)
        wts = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgk,bhkd->bhgd", wts.astype(v_all.dtype), v_all)
    with jax.named_scope("proj"):
        y = o.reshape(b, hq * hd) @ p["w_o"].astype(x_t.dtype)
    return y, new_stack, g_new, sel_pages


def attn_decode_dense(p: Params, cfg: ModelConfig, x_t: jax.Array,
                      cache: DenseCache, *, window: Optional[int] = None,
                      layer: Optional[jax.Array] = None,
                      active: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, DenseCache]:
    """Full-attention baseline decode step. x_t: [B, D]. ``layer`` and
    ``active`` as in :func:`attn_decode_wgkv`."""
    b, _ = x_t.shape
    t = cache.t if layer is None else cache.t[layer]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("proj"):
        x = x_t[:, None, :]
        q = _heads(x @ p["w_q"].astype(x.dtype), hq, hd)[:, :, 0]
        k_pre = _heads(x @ p["w_k"].astype(x.dtype), hkv, hd)[:, :, 0]
        v_new = _heads(x @ p["w_v"].astype(x.dtype), hkv, hd)[:, :, 0]
        q, k_pre = _qk_norm(p, q[:, :, None], k_pre[:, :, None])
        q, k_pre = q[:, :, 0], k_pre[:, :, 0]
        q = _rope_single(cfg, q, t)
        k_new = _rope_single(cfg, k_pre, t)
    with jax.named_scope("cache_write"):
        new_stack = dense_cache_append(cache, k_new, v_new, layer=layer,
                                       active=active)
    cache = new_stack if layer is None else layer_of(new_stack, layer)
    with jax.named_scope("attention"):
        s = cache.k.shape[2]
        pos = jnp.arange(s)[None]                                   # [1, S]
        valid = pos < cache.t[:, None]
        if window is not None:
            valid = valid & (pos >= cache.t[:, None] - window)
        grp = hq // hkv
        qg = q.reshape(b, hkv, grp, hd)
        logits = jnp.einsum("bhgd,bhkd->bhgk", qg, cache.k).astype(jnp.float32)
        logits = logits * (hd ** -0.5)
        logits = jnp.where(valid[:, None, None], logits, M.NEG_INF)
        wts = jax.nn.softmax(logits, -1)
        o = jnp.einsum("bhgk,bhkd->bhgd", wts.astype(cache.v.dtype), cache.v)
    with jax.named_scope("proj"):
        y = o.reshape(b, hq * hd) @ p["w_o"].astype(x_t.dtype)
    return y, new_stack


# ==========================================================================
# cross-attention (whisper decoder); optional admission on encoder memory
# ==========================================================================
class CrossCache(NamedTuple):
    k: jax.Array      # [B, Hkv, S_enc_or_budget, hd]
    v: jax.Array
    valid: jax.Array  # [B, Hkv, S]


def build_cross_cache(p: Params, cfg: ModelConfig, enc_out: jax.Array, *,
                      budget: Optional[int] = None) -> CrossCache:
    """Precompute cross-attn K/V from encoder output; when ``budget`` is
    given and the layer has a gate, admit only the top-budget encoder
    tokens (learned encoder-memory pruning — WG-KV on the cross stream)."""
    b, s, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = _heads(enc_out @ p["w_k"].astype(enc_out.dtype), hkv, hd)
    v = _heads(enc_out @ p["w_v"].astype(enc_out.dtype), hkv, hd)
    if budget is not None and "gate" in p and budget < s:
        g = gate_scores(p["gate"], k, k)  # no RoPE on whisper cross keys
        sel = select_global(g, budget=budget, tau=cfg.wgkv.tau, sink=cfg.wgkv.sink)
        bi = jnp.arange(b)[:, None, None]
        hi = jnp.arange(hkv)[None, :, None]
        return CrossCache(k[bi, hi, sel.idx], v[bi, hi, sel.idx], sel.valid)
    return CrossCache(k, v, jnp.ones((b, hkv, s), bool))


def attn_cross(p: Params, cfg: ModelConfig, x: jax.Array, cc: CrossCache
               ) -> jax.Array:
    """x: [B, Sq, D] decoder stream attending to the (possibly budgeted)
    encoder memory."""
    b, sq, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _heads(x @ p["w_q"].astype(x.dtype), hq, hd)
    grp = hq // hkv
    qg = q.reshape(b, hkv, grp, sq, hd)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, cc.k).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    logits = jnp.where(cc.valid[:, :, None, None], logits, M.NEG_INF)
    wts = jax.nn.softmax(logits, -1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", wts.astype(cc.v.dtype), cc.v)
    y = o.reshape(b, hq, sq, hd).transpose(0, 2, 1, 3).reshape(b, sq, hq * hd)
    return y @ p["w_o"].astype(x.dtype)


def attn_encoder(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Bidirectional encoder self-attention (whisper)."""
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _heads(x @ p["w_q"].astype(x.dtype), hq, hd)
    k = _heads(x @ p["w_k"].astype(x.dtype), hkv, hd)
    v = _heads(x @ p["w_v"].astype(x.dtype), hkv, hd)
    out = sdpa(q, k, v, lambda qs, ql: jnp.zeros((1, 1, 1, ql, s), jnp.float32))
    y = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return y @ p["w_o"].astype(x.dtype)
