"""Inference paths: prefill (populate caches) and decode_step (one token).

Cache tree mirrors the parameter tree:
  {"t": [B] int32, "stem": (block_cache, ...), "blocks": <stacked>,
   "obs": <stacked ObsWindow> (only when eviction is enabled)}

Per-block caches:
  attn / attn_moe / local_attn — DualCache (WG-KV) or DenseCache (baseline)
  attn_cross                  — {"self": DualCache|DenseCache, "cross": CrossCache}
  rglru                       — RGLRUState;  mlstm/slstm — their states

Composability (paper §5.4): ``DecodeOptions.quest_pages`` applies Quest
read-time selection over the (global) cache as a MASK (full-width einsum,
accuracy studies); ``DecodeOptions.selection_policy = "quest:K"`` applies
it as a GATHER (top-K pages materialized, decode FLOPs scale with K — the
serving path); ``evict_hard_budget`` applies SnapKV-style eviction when a
head's global count hits the bound.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import baselines as BL
from repro.core import eviction as EV
from repro.core import selection as SEL
from repro.core.dual_cache import (DualCache, init_dual_cache, layer_of,
                                   prefill_populate)
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MoE
from repro.models import rglru as RG
from repro.models import xlstm as XL
from repro.models.transformer import _encode, _norm
from repro.sharding.rules import constrain_tokens

Params = Dict[str, Any]
CacheTree = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class DecodeOptions:
    quest_pages: Optional[int] = None      # read-time Selection budget (pages, MASK mode)
    # gathered read-time Selection: None | "quest:K" (top-K pages GATHERED
    # into the decode einsum — cost scales with K; parse_selection_policy)
    selection_policy: Optional[str] = None
    evict_hard_budget: Optional[int] = None  # post-write Eviction bound (tokens/head)
    evict_frac: float = 0.10
    w_obs: int = 256
    # static admission override (core/baselines.py): replaces the learned
    # write-gate with a position-only policy — "streaming_llm" (sinks only)
    # or "duo" (retrieval heads admit all; streaming heads sinks only).
    # None = learned gate. Fields stay hashable (tuple) for jit partials.
    admission_policy: Optional[str] = None
    admission_sink: int = 16
    duo_retrieval_heads: Tuple[int, ...] = ()
    # the dual cache's decode write as one Pallas kernel a layer
    # (kernels/cache_write.py) instead of the jnp scatters, bit-identical:
    # for a program on one TPU device, which the serving engine sets
    write_kernel: bool = False


def parse_selection_policy(policy: Optional[str]) -> Optional[int]:
    """"quest:K" -> K (page budget); None -> None."""
    if policy is None:
        return None
    kind, _, arg = policy.partition(":")
    if kind != "quest" or not arg.isdigit() or int(arg) < 1:
        raise ValueError(
            f"unknown selection policy {policy!r} (expected 'quest:K')")
    return int(arg)


def _static_gates(cfg: ModelConfig, opts: DecodeOptions,
                  positions: jax.Array) -> Optional[jax.Array]:
    """Static admission gates at ``positions`` ([B] decode / [B, S] prefill);
    None when the learned gate is in effect."""
    if opts.admission_policy is None:
        return None
    pos = positions if positions.ndim <= 2 else positions[0]  # M-RoPE stack
    return BL.gates_from_positions(
        opts.admission_policy, pos, cfg.n_kv_heads,
        sink=opts.admission_sink, retrieval_heads=opts.duo_retrieval_heads)


class PrefillOut(NamedTuple):
    logits: jax.Array          # [B, V] for the last position
    hidden: jax.Array          # [B, S, D]
    mean_admission: jax.Array  # scalar: fraction of tokens with g >= tau


# ==========================================================================
# per-block prefill
# ==========================================================================
def _attn_block_prefill(p, cfg: ModelConfig, bt: str, x, positions, *,
                        use_wgkv: bool, budget: int, max_len: int,
                        block_chunk, q_chunk, enc_out, moe_groups,
                        opts: DecodeOptions = None, gate_override=None):
    window = cfg.sliding_window if bt == "local_attn" else None
    xin = _norm(cfg, p["ln1"], x)
    b, s, _ = x.shape
    dt = jnp.dtype(cfg.dtype)
    adm = jnp.zeros((), jnp.float32)
    if use_wgkv:
        if gate_override is None and opts is not None:
            gate_override = _static_gates(cfg, opts, positions)
        w_ring = window if window is not None else cfg.wgkv.w_local
        r = A.attn_prefill_budgeted(
            p["attn"], cfg, xin, positions, budget=budget, window=window,
            block_chunk=block_chunk, gate_override=gate_override)
        cache = init_dual_cache(b, cfg.n_kv_heads, cfg.head_dim,
                                w_local=w_ring, budget=budget, dtype=dt)
        cache = prefill_populate(cache, r.k_rope, r.v, r.g,
                                 tau=cfg.wgkv.tau, sink=cfg.wgkv.sink)
        h = r.out
        adm = (r.g >= cfg.wgkv.tau).mean()
    else:
        h, k_rope, v = A.attn_prefill_full(p["attn"], cfg, xin, positions,
                                           window=window, q_chunk=q_chunk)
        cache = A.init_dense_cache(b, cfg.n_kv_heads, cfg.head_dim, max_len, dt)
        cache = cache._replace(
            k=cache.k.at[:, :, :s].set(k_rope.astype(dt)),
            v=cache.v.at[:, :, :s].set(v.astype(dt)),
            t=jnp.full((b,), s, jnp.int32),
        )
    x = x + h
    if bt == "attn_cross":
        xbudget = budget if use_wgkv else None
        cc = A.build_cross_cache(p["xattn"], cfg, enc_out, budget=xbudget)
        x = x + A.attn_cross(p["xattn"], cfg, _norm(cfg, p["ln_x"], x), cc)
        cache = {"self": cache, "cross": cc}
    if bt == "attn_moe":
        y, _ = MoE.moe_ffn(p["moe"], cfg, _norm(cfg, p["ln2"], x), groups=moe_groups)
        x = x + y
    elif bt == "attn_cross" or cfg.arch_type == "audio":
        x = x + L.gelu_mlp(p["mlp"], _norm(cfg, p["ln2"], x))
    else:
        x = x + L.swiglu(p["mlp"], _norm(cfg, p["ln2"], x))
    return x, cache, adm


def _block_prefill(p, cfg: ModelConfig, bt: str, x, positions, **kw):
    if bt in ("attn", "attn_moe", "local_attn", "attn_cross"):
        return _attn_block_prefill(p, cfg, bt, x, positions, **kw)
    zero = jnp.zeros((), jnp.float32)
    if bt == "rglru":
        y, state = RG.rglru_block(p["rec"], cfg, _norm(cfg, p["ln1"], x))
        x = x + y
        x = x + L.swiglu(p["mlp"], _norm(cfg, p["ln2"], x))
        return x, state, zero
    if bt == "mlstm":
        x, state = XL.mlstm_auto(p["cell"], cfg, x)
        return x, state, zero
    if bt == "slstm":
        x, state = XL.slstm_block(p["cell"], cfg, x)
        return x, state, zero
    raise ValueError(bt)


def prefill(params: Params, cfg: ModelConfig, tokens: Optional[jax.Array] = None,
            *, positions: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            enc_embeds: Optional[jax.Array] = None,
            use_wgkv: Optional[bool] = None, budget: Optional[int] = None,
            max_len: Optional[int] = None, moe_groups: int = 1,
            block_chunk: Optional[int] = None, q_chunk: Optional[int] = None,
            opts: DecodeOptions = DecodeOptions(), scan_unroll: bool = False,
            ) -> Tuple[PrefillOut, CacheTree]:
    dt = jnp.dtype(cfg.dtype)
    if use_wgkv is None:
        use_wgkv = cfg.wgkv.enabled
    x = L.embed(params["embed"], tokens, dt) if embeds is None else embeds.astype(dt)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if budget is None:
        budget = cfg.wgkv.global_budget(max_len or s)
    if max_len is None:
        max_len = s + 64
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, enc_embeds.astype(dt))
        x = x + L.sinusoidal_positions(s, cfg.d_model)[None].astype(dt)

    pf = functools.partial(
        _block_prefill, cfg=cfg, use_wgkv=use_wgkv, budget=budget,
        max_len=max_len, block_chunk=block_chunk, q_chunk=q_chunk,
        enc_out=enc_out, moe_groups=moe_groups, opts=opts)

    caches: CacheTree = {"t": jnp.full((b,), s, jnp.int32)}
    adm_sum, adm_n = jnp.zeros(()), 0
    stem_caches = []
    for i, bt in enumerate(cfg.stem_pattern):
        x, c, adm = pf(params["stem"][i], bt=bt, x=x, positions=positions)
        stem_caches.append(c)
        adm_sum, adm_n = adm_sum + adm, adm_n + 1
    if stem_caches:
        caches["stem"] = tuple(stem_caches)

    x = constrain_tokens(x)

    def body(carry, bp):
        xc, asum = carry
        xc = constrain_tokens(xc)
        bl_caches = {}
        for i, bt in enumerate(cfg.block_pattern):
            xc, c, adm = pf(bp[f"b{i}"], bt=bt, x=xc, positions=positions)
            bl_caches[f"b{i}"] = c
            asum = asum + adm
        return (constrain_tokens(xc), asum), bl_caches

    (x, adm_sum), blk_caches = jax.lax.scan(body, (x, adm_sum),
                                            params["blocks"], unroll=scan_unroll)
    adm_n += cfg.n_repeats * max(cfg.attn_blocks_per_pattern, 1)
    caches["blocks"] = blk_caches
    if opts.evict_hard_budget is not None:
        caches["obs"] = _init_obs_tree(cfg, b, opts)
    hidden = _norm(cfg, params["ln_f"], x)
    logits = L.unembed(params["embed"], hidden[:, -1])
    return PrefillOut(logits, hidden, adm_sum / max(adm_n, 1)), caches


def _init_obs_tree(cfg: ModelConfig, b: int, opts: DecodeOptions):
    one = lambda: EV.init_obs(b, cfg.n_heads, cfg.head_dim, opts.w_obs,
                              jnp.dtype(cfg.dtype))
    n_attn = cfg.attn_blocks_per_pattern
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_repeats, n_attn) + x.shape),
        one())
    return stacked


# ==========================================================================
# decode
# ==========================================================================
def _quest_mask(cfg: ModelConfig, cache: DualCache, q: jax.Array,
                pages: int) -> jax.Array:
    """Read-time Selection over the *global* cache (local + self always
    visible). Returns [B, Hkv, C + W + 1] bool. Scores the cache's
    incrementally-maintained page metadata (no O(C) rebuild per step)."""
    c = cache.budget
    assert c % SEL.PAGE_SIZE == 0, "global budget must be page-aligned for Quest"
    gvalid = jnp.arange(c)[None, None] < cache.gcnt[..., None]
    p_pages = c // SEL.PAGE_SIZE
    meta = SEL.PageMeta(cache.pkmin, cache.pkmax,
                        SEL.page_valid_from_count(cache.gcnt, p_pages))
    pmask = SEL.select_pages(q, meta, pages)
    gmask = SEL.token_mask_from_pages(pmask) & gvalid
    b, h = gvalid.shape[:2]
    rest = jnp.ones((b, h, cache.w_local), bool)  # local ring always visible
    return jnp.concatenate([gmask, rest], axis=-1)  # jaxlint: allow-concat(joins along the kv-position axis - batch axis untouched)


_ATTN_BLOCKS = ("attn", "attn_moe", "local_attn", "attn_cross")


def _attn_block_decode(p, cfg: ModelConfig, bt: str, x_t, cache, *,
                       opts: DecodeOptions, obs=None, obs_at=(),
                       layer=None, active=None, moe_groups: int):
    """One attention block at one position. With ``layer`` set, ``cache``
    (and ``obs``, indexed by ``obs_at``) are stacks written in place at
    that layer; ``active`` [B] masks rows at every write."""
    with jax.named_scope("proj"):
        xin = _norm(cfg, p["ln1"], x_t[:, None])[:, 0]
    self_cache = cache["self"] if bt == "attn_cross" else cache
    window = cfg.sliding_window if bt == "local_attn" else None
    trig = jnp.zeros((), jnp.float32)
    adm = None
    selp = None
    if isinstance(self_cache, DualCache):
        sel_fn = None
        if opts.quest_pages is not None:
            sel_fn = lambda cache, q: _quest_mask(cfg, cache, q, opts.quest_pages)
        t = self_cache.t if layer is None else self_cache.t[layer]
        h, new_cache, g_new, sel_pages = A.attn_decode_wgkv(
            p["attn"], cfg, xin, self_cache, layer=layer, active=active,
            write_kernel=opts.write_kernel, token_select_fn=sel_fn,
            select_pages_k=parse_selection_policy(opts.selection_policy),
            gate_override=_static_gates(cfg, opts, t))
        adm = (g_new >= cfg.wgkv.tau).mean(axis=-1)  # per-row [B]
        if sel_pages is not None:
            selp = sel_pages.astype(jnp.float32).mean(axis=-1)  # per-row [B]
        if opts.evict_hard_budget is not None and obs is not None:
            q_obs = A._heads((xin[:, None] @ p["attn"]["w_q"].astype(xin.dtype)),
                             cfg.n_heads, cfg.head_dim)[:, :, 0]
            obs = EV.push_query(obs, q_obs, active=active, at=obs_at)
            view = new_cache if layer is None else layer_of(new_cache, layer)
            evicted, trg = EV.maybe_evict(
                view, layer_of(obs, obs_at), hard_budget=opts.evict_hard_budget,
                evict_frac=opts.evict_frac, active=active)
            # a compaction rewrites the layer, but only triggered rows
            # take new values (maybe_evict keeps the others)
            new_cache = evicted if layer is None else jax.tree.map(
                lambda st, v: st.at[layer].set(v), new_cache, evicted)
            trig = trg.astype(jnp.float32).mean(axis=-1)  # per-row [B]
    else:
        h, new_cache = A.attn_decode_dense(p["attn"], cfg, xin, self_cache,
                                           window=window, layer=layer,
                                           active=active)
    x_t = x_t + h
    if bt == "attn_cross":
        cross = cache["cross"]
        x_t = x_t + A.attn_cross(p["xattn"], cfg,
                                 _norm(cfg, p["ln_x"], x_t[:, None]),
                                 cross if layer is None
                                 else layer_of(cross, layer))[:, 0]
        new_cache = {"self": new_cache, "cross": cross}
    with jax.named_scope("mlp"):
        if bt == "attn_moe":
            y, _ = MoE.moe_ffn(p["moe"], cfg,
                               _norm(cfg, p["ln2"], x_t[:, None]),
                               groups=moe_groups)
            x_t = x_t + y[:, 0]
        elif bt == "attn_cross" or cfg.arch_type == "audio":
            x_t = x_t + L.gelu_mlp(p["mlp"],
                                   _norm(cfg, p["ln2"], x_t[:, None]))[:, 0]
        else:
            x_t = x_t + L.swiglu(p["mlp"],
                                 _norm(cfg, p["ln2"], x_t[:, None]))[:, 0]
    return x_t, new_cache, obs, trig, adm, selp


def _block_decode(p, cfg: ModelConfig, bt: str, x_t, cache, *, opts, obs,
                  moe_groups, obs_at=(), layer=None, active=None):
    if bt in _ATTN_BLOCKS:
        return _attn_block_decode(p, cfg, bt, x_t, cache, opts=opts, obs=obs,
                                  obs_at=obs_at, layer=layer, active=active,
                                  moe_groups=moe_groups)
    zero = jnp.zeros((), jnp.float32)
    # recurrent states are small ([B, ...] a layer): step the layer's
    # state, keep inactive rows' old values, write the layer back
    old = cache if layer is None else layer_of(cache, layer)
    if bt == "rglru":
        y, state = RG.rglru_step(p["rec"], cfg,
                                 _norm(cfg, p["ln1"], x_t[:, None])[:, 0], old)
        x_t = x_t + y
        x_t = x_t + L.swiglu(p["mlp"], _norm(cfg, p["ln2"], x_t[:, None]))[:, 0]
    elif bt == "mlstm":
        x_t, state = XL.mlstm_step(p["cell"], cfg, x_t, old)
    elif bt == "slstm":
        x_t, state = XL.slstm_step(p["cell"], cfg, x_t, old)
    else:
        raise ValueError(bt)
    if active is not None:
        state = jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            state, old)
    if layer is not None:
        state = jax.tree.map(lambda st, v: st.at[layer].set(v), cache, state)
    return x_t, state, obs, zero, None, None


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                caches: CacheTree, *, moe_groups: int = 1,
                opts: DecodeOptions = DecodeOptions(),
                scan_unroll: bool = False,
                active: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, CacheTree, Dict[str, jax.Array]]:
    """token: [B] int32 -> (logits [B, V], new caches, stats).

    ``active`` [B] bool masks rows at every cache write: an inactive row
    writes back what the slots it touches held and keeps its counters, so
    its state comes out bit-identical (None: every row advances). The
    layer scan carries the stacked ``[n_repeats, B, ...]`` caches and
    writes each layer's touched slots into them in place."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], token[:, None], dt)[:, 0]  # [B, D]
    b = x.shape[0]
    t = caches["t"]
    if cfg.is_encdec:
        # sinusoid at per-batch position t
        dmax = cfg.d_model
        inv = jnp.exp(-jnp.log(10000.0) * jnp.arange(dmax // 2) / max(dmax // 2 - 1, 1))
        ang = t[:, None].astype(jnp.float32) * inv[None]
        x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dt)  # jaxlint: allow-concat(feature-axis sinusoid halves - batch axis untouched)

    new_caches: CacheTree = {
        "t": t + 1 if active is None else jnp.where(active, t + 1, t)}
    trig_sum = jnp.zeros((b,), jnp.float32)  # per-row eviction triggers
    adm_sum = jnp.zeros((b,), jnp.float32)  # per-row: batch rows may be dead
    adm_n = jnp.zeros((), jnp.float32)
    sel_sum = jnp.zeros((b,), jnp.float32)  # per-row selected pages (layer sum)
    bd = functools.partial(_block_decode, cfg=cfg, opts=opts,
                           moe_groups=moe_groups, active=active)
    stem_new = []
    for i, bt in enumerate(cfg.stem_pattern):
        x, c, _, trg, adm, selp = bd(params["stem"][i], bt=bt, x_t=x,
                                     cache=caches["stem"][i], obs=None)
        stem_new.append(c)
        trig_sum = trig_sum + trg
        if adm is not None:
            adm_sum, adm_n = adm_sum + adm, adm_n + 1.0
        if selp is not None:
            sel_sum = sel_sum + selp
    if stem_new:
        new_caches["stem"] = tuple(stem_new)

    x = constrain_tokens(x)

    def body(carry, xs):
        xc, trig, asum, an, ssum, blocks, obs = carry
        bp, r = xs
        xc = constrain_tokens(xc)
        blocks = dict(blocks)
        ai = 0
        for i, bt in enumerate(cfg.block_pattern):
            at_obs = obs is not None and bt in _ATTN_BLOCKS
            xc, blocks[f"b{i}"], obs_o, trg, adm, selp = bd(
                bp[f"b{i}"], bt=bt, x_t=xc, cache=blocks[f"b{i}"],
                obs=obs if at_obs else None, obs_at=(r, ai), layer=r)
            if at_obs:
                obs = obs_o
                ai += 1
            trig = trig + trg
            if adm is not None:
                asum, an = asum + adm, an + 1.0
            if selp is not None:
                ssum = ssum + selp
        return (xc, trig, asum, an, ssum, blocks, obs), None

    with jax.named_scope("layer_scan"):
        (x, trig_sum, adm_sum, adm_n, sel_sum, blocks, obs), _ = jax.lax.scan(
            body, (x, trig_sum, adm_sum, adm_n, sel_sum, caches["blocks"],
                   caches.get("obs")),
            (params["blocks"], jnp.arange(cfg.n_repeats, dtype=jnp.int32)),
            unroll=scan_unroll)
    new_caches["blocks"] = blocks
    if obs is not None:
        new_caches["obs"] = obs
    with jax.named_scope("lm_head"):
        hidden = _norm(cfg, params["ln_f"], x[:, None])[:, 0]
        logits = L.unembed(params["embed"], hidden)
    return logits, new_caches, {
        "evict_triggers": trig_sum.mean(),
        # per-row [B] so serving backends can re-sync the paged mirror for
        # (and average admission over) live slots only
        "evict_trigger_rows": trig_sum,
        "mean_admission": adm_sum / jnp.maximum(adm_n, 1.0),
        # per-row pages gathered this step under selection_policy (mean
        # over kv heads, summed over attention layers; zeros when off)
        "selected_pages_rows": sel_sum}


def prefill_extend(params: Params, cfg: ModelConfig, tokens: jax.Array,
                   caches: CacheTree, *, moe_groups: int = 1,
                   opts: DecodeOptions = DecodeOptions(),
                   scan_unroll: bool = False
                   ) -> Tuple[jax.Array, CacheTree, Dict[str, jax.Array]]:
    """Teacher-forced multi-token cache extension (chunked prefill).

    Feeds ``tokens`` [B, S] one position at a time through
    :func:`decode_step` under a scan, so a prompt can be processed in
    bounded chunks interleaved with other requests' decode steps. The
    resulting cache state matches a one-shot prefill over the
    concatenated sequence (lazy promotion admits exactly the same tokens
    the write-gate bias admits at prefill time); a single device call per
    chunk keeps it schedulable. Returns (logits of the LAST fed position
    [B, V], caches, stats)."""
    def body(carry, tok):
        logits, new_caches, st = decode_step(
            params, cfg, tok, carry, moe_groups=moe_groups, opts=opts,
            scan_unroll=scan_unroll)
        return new_caches, (logits, st["evict_triggers"], st["mean_admission"])
    caches, (logits, trig, adm) = jax.lax.scan(body, caches, tokens.T)
    return logits[-1], caches, {"evict_triggers": trig.sum(),
                                "mean_admission": adm.mean()}


def prefill_extend_ragged(params: Params, cfg: ModelConfig,
                          tokens: jax.Array, lengths: jax.Array,
                          caches: CacheTree, *, moe_groups: int = 1,
                          opts: DecodeOptions = DecodeOptions(),
                          scan_unroll: bool = False
                          ) -> Tuple[jax.Array, CacheTree,
                                     Dict[str, jax.Array]]:
    """Ragged multi-row chunked prefill: advance B tasks in ONE scan.

    ``tokens`` [B, S] holds each row's next prompt chunk left-aligned;
    ``lengths`` [B] says how many of those S positions are real. Every
    position runs through :func:`decode_step` exactly like the batch-1
    extend, with the row mask of that position: every cache write (KV,
    ring pointer, gate/eviction state) at positions >= ``lengths[i]``
    writes back what the slot held, so a short row's final cache state is
    bit-identical to running the sequential scan over its real tokens
    only, and a length-0 row is pure padding. Only the slots a position
    touches are written, in place; nothing selects over the whole tree.
    Returns

      * ``last_logits`` [B, V]: each row's logits at its LAST real
        position (zeros for length-0 rows — the caller keeps its prior
        logits for those),
      * the advanced caches,
      * per-row stats ``{"evict_trigger_rows": [B], "adm_sum_rows":
        [B]}`` (sums over that row's real positions only), so serving
        backends can account admission/eviction per request.

    Rows are independent per position, which is what the fused serving
    megabatch tick (serving/engine.py ``step_batch``) builds on: a
    FIRST-CHUNK row is just a freshly-spliced EMPTY cache row (per-row
    ``t`` starts its scan at position 0 — no separate batch-1 open
    path), and a live DECODE row rides along as a length-1 row whose
    single position computes exactly the batch-1 ``decode_step`` —
    so opens, mid-prefill extends, and decode steps share this one
    compiled call.
    """
    b, s = tokens.shape
    active_mat = (jnp.arange(s, dtype=jnp.int32)[:, None]
                  < lengths[None, :].astype(jnp.int32))       # [S, B]
    logits_s = jax.eval_shape(
        lambda c: decode_step(params, cfg, tokens[:, 0], c,
                              moe_groups=moe_groups, opts=opts,
                              scan_unroll=scan_unroll)[0], caches)

    def body(carry, xs):  # jaxlint: masked-scan-body
        old, last_logits = carry
        tok, active = xs                                      # [B], [B] bool
        logits, new, st = decode_step(params, cfg, tok, old,
                                      moe_groups=moe_groups, opts=opts,
                                      scan_unroll=scan_unroll, active=active)
        with jax.named_scope("row_keep"):
            last_logits = jnp.where(active[:, None], logits, last_logits)
            trig = jnp.where(active, st["evict_trigger_rows"], 0.0)
            adm = jnp.where(active, st["mean_admission"], 0.0)
            selp = jnp.where(active, st["selected_pages_rows"], 0.0)
        return (new, last_logits), (trig, adm, selp)

    init = (caches, jnp.zeros(logits_s.shape, logits_s.dtype))
    with jax.named_scope("position_scan"):
        (caches, last_logits), (trig, adm, selp) = jax.lax.scan(
            body, init, (tokens.T, active_mat))
    return last_logits, caches, {"evict_trigger_rows": trig.sum(axis=0),
                                 "adm_sum_rows": adm.sum(axis=0),
                                 "selected_pages_rows": selp.sum(axis=0)}
