"""Weights of a configuration, made on the device from the seed.

The configuration's model module (`bench.model`) makes every leaf in one
jitted call from `key_of(seed, ...)`, in the dtype the configuration serves
in, laid out as the serving engine takes them.  The plain reference
(reference.py) reads the same arrays; neither side makes weights of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import bench
from chipbench.traffic import seed_words


def dtype_of(cfg: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]


def key_of(seed: int, salt: int) -> jax.Array:
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, salt)


def make_weights(cfg: dict, seed: int) -> dict:
    """The weights tree of the configuration's model, from the seed."""
    return bench.model(cfg).make_weights(cfg, seed)
