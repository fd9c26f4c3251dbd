"""Persistent XLA compilation cache at a fixed place.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  :func:`enable_compile_cache` is the one place
the program configures it: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it by itself and nothing is set here; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  Call it before the
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
