"""Shared model building blocks (pure-JAX, functional params-as-pytrees)."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, jax.Array]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------
def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    if scale is None:
        scale = fan_in ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key: jax.Array, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def init_rmsnorm(dim: int, dtype) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(dim: int, dtype) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_nowt(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# rotary embeddings (standard + Qwen2-VL M-RoPE)
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """[head_dim // 2] inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., T, hd]; positions: broadcastable to [..., T] (int). Rotates
    pairs (x[2i], x[2i+1]). Returns same dtype as x."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., T, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (hd // 2, 2))
    x1, x2 = x32[..., 0], x32[..., 1]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """Qwen2-VL splits the hd/2 frequency slots into (t, h, w) sections;
    for hd=128 the reference uses (16, 24, 24). We generalize by ratio."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return t, h, w


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float) -> jax.Array:
    """Multimodal RoPE. x: [..., T, hd]; positions3: [3, ..., T] (t, h, w
    position ids — equal for text tokens, spatial for vision tokens)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # [hd/2]
    st, sh, sw = mrope_sections(hd)
    sec = jnp.concatenate([
        jnp.zeros((st,), jnp.int32),
        jnp.ones((sh,), jnp.int32),
        jnp.full((sw,), 2, jnp.int32),
    ])  # [hd/2] -> which position stream drives each freq slot
    # gather per-slot positions: [..., T, hd/2]
    pos = jnp.moveaxis(positions3, 0, -1)  # [..., T, 3]
    slot_pos = jnp.take_along_axis(
        pos.astype(jnp.float32),
        jnp.broadcast_to(sec[None], pos.shape[:-1] + (hd // 2,)).astype(jnp.int32),
        axis=-1,
    )
    ang = slot_pos * inv  # [..., T, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (hd // 2, 2))
    x1, x2 = x32[..., 0], x32[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    """Whisper-style absolute sinusoidal embeddings [seq, dim]."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.log(10000.0) * jnp.arange(dim // 2, dtype=jnp.float32)
                  / max(dim // 2 - 1, 1))
    ang = pos * inv[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# FFN (SwiGLU, llama-family) and whisper-style GELU MLP
# --------------------------------------------------------------------------
def init_swiglu(key: jax.Array, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
    return h @ p["w_down"].astype(dt)


def init_gelu_mlp(key: jax.Array, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, (d_model, d_ff), dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(k2, (d_ff, d_model), dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jax.nn.gelu(x @ p["w_in"].astype(dt) + p["b_in"].astype(dt))
    return h @ p["w_out"].astype(dt) + p["b_out"].astype(dt)


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------
def init_embedding(key: jax.Array, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    p = {"tok": embed_init(key, (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        key2 = jax.random.fold_in(key, 1)
        p["unembed"] = embed_init(key2, (cfg.d_model, cfg.vocab_size), dt)
    return p


def embed(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return p["tok"].astype(dtype)[tokens]


def unembed(p: Params, x: jax.Array) -> jax.Array:
    w = p.get("unembed")
    if w is None:
        w = p["tok"].T
    return x @ w.astype(x.dtype)
