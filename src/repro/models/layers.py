"""Shared layer primitives: norms, embeddings, RoPE, init helpers."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_apply(kind: str, p: dict, x: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    """RMSNorm at `eps` (the configuration's `norm_eps`), or LayerNorm."""
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"], eps)
    return layer_norm(x, p["scale"], p["bias"])


def norm_init(kind: str, d: int, dtype=jnp.float32) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# --- RoPE --------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, head_dim), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                     # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]               # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- init helpers ------------------------------------------------------------

def dense_init(key: jax.Array, shape, fan_in: Optional[int] = None,
               dtype=jnp.float32) -> jax.Array:
    fan = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / math.sqrt(fan)).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)
