"""Persistent XLA compilation cache for the entry points.

Called from `main()` of the launchers and from `chip_smoke.py`, never at
library import: a library that set a process-wide cache would override
the caller's choice.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fallback cache directory: a fixed path inside the checkout.  The path
#: is part of what a later run must find again, so it never comes from a
#: temp name, a pid or the time.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the directory as is (JAX
    reads the variable itself, and nothing here overrides it); otherwise
    the cache lives at `DEFAULT_DIR`.  Every program is cached, not only
    those over JAX's default one-second compile time, so a second run
    compiles nothing it has compiled before."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
