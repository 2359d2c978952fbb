"""Dense GQA decoder served with the write-gated dual cache (WG-KV).

Everything the benchmark needs about one model family, computed from the
configuration file alone and importing nothing of the program except
where it builds the program's own config (``program_config``):

* ``program_config``: the file's sizes as the program's ``ModelConfig``;
* ``make_weights``: seeded weights in the program's parameter layout, made
  on the device in one jitted call, in the type they are served in;
* ``reference_logits``: a plain float32 ``jax.numpy`` forward of one
  sequence with the serving path's admission semantics (below);
* ``param_counts`` / ``step_work``: the work a fused step needs, counted
  from shapes.

Admission semantics the reference follows (per layer and KV head, as the
served dual cache keeps them): a token at position ``j`` is visible to the
query at ``t >= j`` while it is in the local ring (``t - j < W``); after
that only if its write gate ``g_j >= tau`` and fewer than ``C`` earlier
tokens of that head were admitted (the global cache holds the first ``C``
admitted tokens in position order; later ones overflow and are dropped).
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# name of the fused serving step's jitted program, as the device trace
# shows it (serving/sharded.py ``_make_fused_step``: ``jax.jit(fn)``)
FUSED_PROGRAM = "jit_fn"


def dims(c: Dict) -> Dict:
    d, hq = c["hidden_size"], c["num_attention_heads"]
    return dict(
        d=d, layers=c["num_hidden_layers"], hq=hq,
        hkv=c["num_key_value_heads"], hd=c.get("head_dim") or d // hq,
        ff=c["intermediate_size"], vocab=c["vocab_size"],
        qk_norm=bool(c["qk_norm"]), theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]),
        w=c["wgkv"]["w_local"], tau=c["wgkv"]["tau"],
        gate_hidden=c["wgkv"]["gate_hidden"],
        gate_b2=c["wgkv"]["gate_b2"],
        frac=c["wgkv"]["global_budget_frac"],
        # the program's global cache holds this many tokens per layer and
        # KV head: WGKVConfig.global_budget of the engine's capacity
        capacity=max(16, min(int(c["engine"]["capacity"]
                                 * c["wgkv"]["global_budget_frac"]),
                             c["engine"]["capacity"])),
        dtype=jnp.dtype(c["torch_dtype"]))


def program_config(c: Dict):
    """The configuration file as the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig, WGKVConfig
    m = dims(c)
    return ModelConfig(
        name=c["name"], arch_type="dense", d_model=m["d"], n_heads=m["hq"],
        n_kv_heads=m["hkv"], head_dim=m["hd"], d_ff=m["ff"],
        vocab_size=m["vocab"], block_pattern=("attn",),
        n_repeats=m["layers"], qk_norm=m["qk_norm"], rope_theta=m["theta"],
        tie_embeddings=bool(c["tie_word_embeddings"]), source=c["source"],
        wgkv=WGKVConfig(enabled=True, w_local=m["w"], tau=m["tau"],
                        gate_hidden=m["gate_hidden"],
                        global_budget_frac=m["frac"]),
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"])


def jax_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, wider than 32 bits too."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------
def _init(key, m):
    d, hq, hkv, hd, ff = m["d"], m["hq"], m["hkv"], m["hd"], m["ff"]
    gh, dt = m["gate_hidden"], m["dtype"]

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def scale_vec(k, n):       # norm scales near 1, so that each one acts
        return (1.0 + 0.1 * jax.random.normal(k, (n,))).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 14)
        attn = {"w_q": normal(ks[0], (d, hq * hd), d ** -0.5),
                "w_k": normal(ks[1], (d, hkv * hd), d ** -0.5),
                "w_v": normal(ks[2], (d, hkv * hd), d ** -0.5),
                "w_o": normal(ks[3], (hq * hd, d), (hq * hd) ** -0.5),
                "gate": {"w1": normal(ks[4], (hkv, 2 * hd, gh),
                                      (2 * hd) ** -0.5),
                         "b1": normal(ks[5], (hkv, gh), 0.1),
                         "w2": normal(ks[6], (hkv, gh, 1), gh ** -0.5),
                         "b2": jnp.full((hkv, 1), m["gate_b2"], dt)}}
        if m["qk_norm"]:
            attn["q_norm"] = scale_vec(ks[7], hd)
            attn["k_norm"] = scale_vec(ks[8], hd)
        return {"ln1": {"scale": scale_vec(ks[9], d)}, "attn": attn,
                "ln2": {"scale": scale_vec(ks[10], d)},
                "mlp": {"w_gate": normal(ks[11], (d, ff), d ** -0.5),
                        "w_up": normal(ks[12], (d, ff), d ** -0.5),
                        "w_down": normal(ks[13], (ff, d), ff ** -0.5)}}

    ke, kb, kf = jax.random.split(key, 3)
    return {"embed": {"tok": normal(ke, (m["vocab"], d), 0.02)},
            # one layer at a time: no float32 copy of every layer at once
            "blocks": {"b0": jax.lax.map(
                layer, jax.random.split(kb, m["layers"]))},
            "ln_f": {"scale": scale_vec(kf, d)}}


def make_weights(c: Dict, seed: int):
    """Seeded weights in the program's layout (tied embedding, one stacked
    ``b0`` block per layer), made on the default device in one jitted
    call. The gate's output bias is the one constant the file states."""
    m = dims(c)
    return jax.jit(functools.partial(_init, m=m))(jax_key(seed))


def param_counts(c: Dict) -> Dict[str, int]:
    """Parameters per kind: ``embed`` (the tied table, also the LM head)
    and ``body`` (everything else the fused step reads once per step)."""
    m = dims(c)
    d, hq, hkv, hd, ff, gh = (m["d"], m["hq"], m["hkv"], m["hd"], m["ff"],
                              m["gate_hidden"])
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    gate = hkv * (2 * hd * gh + gh + gh + 1)
    norms = 2 * d + (2 * hd if m["qk_norm"] else 0)
    layer = attn + gate + norms + 3 * d * ff
    return {"embed": m["vocab"] * d, "body": m["layers"] * layer + d}


# --------------------------------------------------------------------------
# plain float32 reference
# --------------------------------------------------------------------------
def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def _rope(x, pos, theta):
    """Rotate pairs (x[2i], x[2i+1]) by pos * theta**(-2i/hd), as served."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


F8_MAX = 448.0      # largest finite float8_e4m3fn


def _f8(a, axis):
    """Round to float8 (e4m3) with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(a), axis, keepdims=True) / F8_MAX + 1e-30
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _qdot(x, w, quant: bool):
    """``x @ w`` in float32; with ``quant`` both sides are first rounded to
    float8 e4m3 with a scale per row of ``x`` and per column of ``w``: the
    step below the served bfloat16, used as the control."""
    if quant:
        x, w = _f8(x, -1), _f8(w, -2)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _layer(m, quant, x, p, pos):
    f32 = lambda a: a.astype(jnp.float32)
    s, hq, hkv, hd = x.shape[0], m["hq"], m["hkv"], m["hd"]
    eps, hi = m["eps"], jax.lax.Precision.HIGHEST
    h = _rms(x, f32(p["ln1"]["scale"]), eps)
    a = p["attn"]
    q = _qdot(h, f32(a["w_q"]), quant).reshape(s, hq, hd)
    k_pre = _qdot(h, f32(a["w_k"]), quant).reshape(s, hkv, hd)
    v = _qdot(h, f32(a["w_v"]), quant).reshape(s, hkv, hd)
    if m["qk_norm"]:
        q = _rms(q, f32(a["q_norm"]), 1e-6)
        k_pre = _rms(k_pre, f32(a["k_norm"]), 1e-6)
    q, k = _rope(q, pos, m["theta"]), _rope(k_pre, pos, m["theta"])
    # write gate per KV head: g = sigmoid(w2 . gelu(w1 . [n(k_pre); n(k)]
    # + b1) + b2), n = RMS normalisation without weight
    g = p["attn"]["gate"]
    feats = jnp.concatenate([_rms(k_pre, None, 1e-6), _rms(k, None, 1e-6)],
                            -1).transpose(1, 0, 2)               # [H, S, 2hd]
    hid = jax.nn.gelu(jnp.einsum("hsf,hfm->hsm", feats, f32(g["w1"]),
                                 precision=hi) + f32(g["b1"])[:, None])
    if quant:
        hid = jnp.stack([_qdot(hid[i], f32(g["w2"][i]), True)
                         for i in range(hkv)])
    else:
        hid = jnp.einsum("hsm,hmo->hso", hid, f32(g["w2"]), precision=hi)
    y0 = hid[..., 0]                                 # gate logit, no bias
    gate = jax.nn.sigmoid(y0 + f32(g["b2"]))                     # [H, S]
    admitted = gate >= m["tau"]
    earlier = jnp.cumsum(admitted, -1) - admitted                # exclusive
    kept = admitted & (earlier < m["capacity"])                  # [H, S]
    t, j = pos[:, None], pos[None, :]
    vis = (j <= t) & ((t - j < m["w"])[None] | kept[:, None, :])  # [H, S, S]
    grp = hq // hkv
    qg = q.reshape(s, hkv, grp, hd)
    sc = jnp.einsum("shgd,jhd->hgsj", qg, k, precision=hi) * hd ** -0.5
    sc = jnp.where(vis[:, None], sc, -1e30)
    o = jnp.einsum("hgsj,jhd->shgd", jax.nn.softmax(sc, -1), v,
                   precision=hi).reshape(s, hq * hd)
    x = x + _qdot(o, f32(a["w_o"]), quant)
    h = _rms(x, f32(p["ln2"]["scale"]), eps)
    mlp = p["mlp"]
    up = jax.nn.silu(_qdot(h, f32(mlp["w_gate"]), quant)) \
        * _qdot(h, f32(mlp["w_up"]), quant)
    return x + _qdot(up, f32(mlp["w_down"]), quant), y0


def _scan(params, tokens, m, quant):
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    return jax.lax.scan(lambda x, p: _layer(m, quant, x, p, pos), x,
                        params["blocks"]["b0"])


# positions per block of the LM head: the vocabulary-wide logits of one
# block live on the device at a time (200,064 x 64 x 4 B = 51 MB)
HEAD_BLOCK = 64


def _hidden(params, tokens, at, m, quant):
    x, _ = _scan(params, tokens, m, quant)
    return _rms(x[at], params["ln_f"]["scale"].astype(jnp.float32), m["eps"])


def _head(params, hid, quant, reduce_fn, extra):
    """``reduce_fn(logits_block, extra_block)`` over blocks of positions."""
    tok_t = params["embed"]["tok"].T

    def blk(args):
        h, e = args
        return reduce_fn(_qdot(h, tok_t.astype(jnp.float32), quant), e)

    a, d = hid.shape
    out = jax.lax.map(blk, (hid.reshape(a // HEAD_BLOCK, HEAD_BLOCK, d),
                            extra.reshape(a // HEAD_BLOCK, HEAD_BLOCK)))
    return jax.tree.map(lambda x: x.reshape(a), out)


@functools.partial(jax.jit, static_argnames=("m_items", "quant"))
def _forward(params, tokens, at, *, m_items, quant):
    hid = _hidden(params, tokens, at, dict(m_items), quant)
    return _qdot(hid, params["embed"]["tok"].astype(jnp.float32).T, quant)


@functools.partial(jax.jit, static_argnames=("m_items",))
def _gaps(params, tokens, at, pick, *, m_items):
    """Reference best logit minus its logit of ``pick`` at each position."""
    hid = _hidden(params, tokens, at, dict(m_items), False)
    return _head(params, hid, False, lambda lg, p: lg.max(-1) - jnp.take_along_axis(
        lg, p[:, None], -1)[:, 0], pick)


@functools.partial(jax.jit, static_argnames=("m_items",))
def _control_pick(params, tokens, at, *, m_items):
    """The token the float8 control puts first at each position."""
    hid = _hidden(params, tokens, at, dict(m_items), True)
    return _head(params, hid, True, lambda lg, _: jnp.argmax(lg, -1).astype(
        jnp.int32), at)


@functools.partial(jax.jit, static_argnames=("m_items",))
def _gate_logits(params, tokens, *, m_items):
    return _scan(params, tokens, dict(m_items), False)[1]


def _items(m):
    return tuple(sorted((k, v) for k, v in m.items() if k != "dtype"))


def gate_bias_for(c: Dict, params, tokens: Sequence[int],
                  share: float) -> Dict[str, float]:
    """The one gate output bias under which ``share`` of the gates of
    ``tokens`` (all layers, KV heads and positions, float32 reference)
    reach tau, and the share the configured bias gives."""
    m = dims(c)
    y0 = np.asarray(_gate_logits(params, jnp.asarray(tokens, jnp.int32),
                                 m_items=_items(m))).ravel()
    logit_tau = float(np.log(m["tau"] / (1.0 - m["tau"])))
    return {"gate_b2": logit_tau - float(np.quantile(y0, 1.0 - share)),
            "share_at_configured": float(np.mean(y0 + m["gate_b2"]
                                                 >= logit_tau))}


def _padded(tokens, at, pad_to, at_pad):
    assert len(tokens) <= pad_to and len(at) <= at_pad, (len(tokens),
                                                         len(at))
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(tokens)] = tokens
    at_p = np.zeros((at_pad,), np.int32)
    at_p[:len(at)] = at
    return jnp.asarray(toks), jnp.asarray(at_p)


def reference_gaps(c: Dict, params, tokens: Sequence[int],
                   at: Sequence[int], served: Sequence[int], *, pad_to: int,
                   at_pad: int, control: bool = False):
    """At each position of ``at``: the float32 reference's best logit
    minus its logit of the token ``served`` there; with ``control`` also
    minus its logit of the token the float8 control puts first. Computed on
    the device (the vocabulary-wide logits never leave it); padding as in
    :func:`reference_logits`."""
    n, items = len(at), _items(dims(c))
    toks, at_p = _padded(tokens, at, pad_to, at_pad)
    sv = np.zeros((at_pad,), np.int32)
    sv[:n] = served
    g = np.asarray(_gaps(params, toks, at_p, jnp.asarray(sv),
                         m_items=items))[:n]
    if not control:
        return g, None
    pick = _control_pick(params, toks, at_p, m_items=items)
    return g, np.asarray(_gaps(params, toks, at_p, pick, m_items=items))[:n]


def reference_logits(c: Dict, params, tokens: Sequence[int],
                     at: Sequence[int], *, pad_to: int, at_pad: int,
                     quant: bool = False) -> np.ndarray:
    """Float32 logits [len(at), vocab] of ``tokens`` at positions ``at``.

    ``tokens`` is padded with zeros to ``pad_to`` (padding only follows the
    real positions, so causality keeps it out of every real one) and
    ``at`` to ``at_pad``, so that one compiled program serves every
    request of a cell. ``quant`` computes it as the float8 control."""
    toks, at_p = _padded(tokens, at, pad_to, at_pad)
    out = _forward(params, toks, at_p, m_items=_items(dims(c)), quant=quant)
    return np.asarray(out)[:len(at)]


# --------------------------------------------------------------------------
# needed work of one fused step
# --------------------------------------------------------------------------
def step_work(c: Dict, rows: Sequence[Dict]) -> Dict[str, float]:
    """FLOPs and HBM bytes one fused-step execution needs, from shapes.

    ``rows``: one dict per row that did real work, with ``tokens`` (real
    positions processed), ``kv_before`` / ``kv_after`` (KV tokens held
    before and after the step, summed over layers and KV heads, as the
    program counts them) and ``logits`` (positions whose logits are used:
    1 for a decode row or a prompt's last chunk, else 0).

    Needed work, whatever implements it: every weight read once; the LM
    head read and multiplied only where logits are used; the KV held
    before the step read once and the new KV written once; FLOPs of
    2 x body parameters per real token plus attention over the tokens
    held at each position (taken to grow evenly through a chunk).
    Padding rows count for nothing."""
    m = dims(c)
    pc = param_counts(c)
    item = m["dtype"].itemsize
    grp = m["hq"] // m["hkv"]
    kv_token_bytes = 2 * m["hd"] * item          # K and V of one head-token
    new_kv_per_token = m["layers"] * m["hkv"] * kv_token_bytes
    toks = sum(r["tokens"] for r in rows)
    logits = sum(r["logits"] for r in rows)
    attn = 0.0
    for r in rows:
        n, k0, k1 = r["tokens"], r["kv_before"], r["kv_after"]
        held = n * k0 + (k1 - k0) * (n + 1) / 2.0     # sum over positions
        attn += 4.0 * m["hd"] * grp * held
    flops = 2.0 * pc["body"] * toks + attn + 2.0 * pc["embed"] * logits
    byts = (pc["body"] * item
            + (pc["embed"] * item if logits else 0.0)
            + toks * m["d"] * item                       # embedding rows
            + sum(r["kv_before"] for r in rows) * kv_token_bytes
            + toks * new_kv_per_token)
    return {"flops": flops, "bytes": byts}


__all__ = ["dims", "program_config", "make_weights", "param_counts",
           "reference_logits", "step_work", "jax_key",
           "gate_bias_for", "reference_gaps"]
