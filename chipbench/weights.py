"""Weights of a configuration, made on the device from the seed.

One jitted call makes every leaf, in the dtype the configuration serves in,
laid out as the serving engine takes them: a stacked `layers` tree of
`num_hidden_layers` rows.  The plain reference (reference.py) reads the same
arrays; neither side makes weights of its own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.traffic import seed_words


def dtype_of(cfg: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]


def key_of(seed: int, salt: int) -> jax.Array:
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, salt)


def make_weights(cfg: dict, seed: int) -> dict:
    """{'embed', 'layers', 'ln_final', 'lm_head'} and, for DSG, 'r' (the
    ternary projection); see the configuration's `assumed` list."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    dt = dtype_of(cfg)
    dsg = cfg["dsg"]

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape) * std).astype(dt)

        def scale(shape):
            return {"scale": (1.0 + 0.05 * jax.random.normal(next(ks), shape)
                              ).astype(dt)}

        w = {
            "embed": normal((v, d), 1.0),
            "layers": {
                "ln_attn": scale((L, d)),
                "attn": {"wq": normal((L, d, H, hd), d ** -0.5),
                         "wk": normal((L, d, kv, hd), d ** -0.5),
                         "wv": normal((L, d, kv, hd), d ** -0.5),
                         "wo": normal((L, H, hd, d), (H * hd) ** -0.5)},
                "ln_ffn": scale((L, d)),
                "ffn": {"w_gate": normal((L, d, f), d ** -0.5),
                        "w_up": normal((L, d, f), d ** -0.5),
                        "w_down": normal((L, f, d), f ** -0.5)},
            },
            "ln_final": scale((d,)),
            "lm_head": normal((d, v), d ** -0.5),
        }
        if dsg["enabled"]:
            k = dsg["proj_dim"]
            u = jax.random.uniform(next(ks), (k, d))
            sign = jnp.where(jax.random.uniform(next(ks), (k, d)) < 0.5,
                             1.0, -1.0)
            r = jnp.where(u < 1.0 / 3.0, sign * math.sqrt(3.0), 0.0)
            w["r"] = (r / math.sqrt(k)).astype(dt)
        return w

    return jax.jit(make)(key_of(seed, 0))


def program_params(w: dict) -> dict:
    """The engine's params tree: every leaf but the DSG projection."""
    return {k: a for k, a in w.items() if k != "r"}
