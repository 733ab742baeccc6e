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


# --- YaRN (rope_scaling type "yarn", DeepSeek-V2) ---------------------------
#
# YaRN re-tunes the rotary frequencies of every position, not only past the
# original context: pairs rotating fewer than `beta_slow` times over the
# original context are divided by `factor`, pairs rotating more than
# `beta_fast` times keep theirs, and the pairs between blend linearly.

def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float) -> tuple:
    """(low, high) pair indices of the blend, clamped to [0, dim - 1]."""
    def at(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies (dim/2,): the plain ones below the
    correction range, divided by `factor` above it, blended between."""
    extra = rope_freqs(dim, theta)
    low, high = yarn_correction_range(dim, theta, original_max, beta_fast,
                                      beta_slow)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope_pairs(x: jax.Array, positions: jax.Array,
                     inv_freq: jax.Array, mscale: float = 1.0) -> jax.Array:
    """Rotary embedding in DeepSeek-V2's layout: x (..., S, H, dim) holds
    its pairs interleaved, (x0, x1), (x2, x3), ...; they are gathered to
    the two halves and rotated as rotate-half RoPE at `inv_freq` (dim/2,),
    cos and sin scaled by `mscale`.  positions: broadcastable to (..., S)."""
    dim = x.shape[-1]
    x = x.astype(jnp.float32)
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (dim // 2, 2)), -1, -2)
    x1, x2 = x[..., 0, :], x[..., 1, :]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * mscale)[..., None, :]
    sin = (jnp.sin(angles) * mscale)[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --- init helpers ------------------------------------------------------------

def dense_init(key: jax.Array, shape, fan_in: Optional[int] = None,
               dtype=jnp.float32) -> jax.Array:
    fan = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / math.sqrt(fan)).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)
