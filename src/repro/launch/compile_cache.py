"""Persistent XLA compilation cache for the serving entry points.

A cold run at published widths spends minutes compiling; the cache
lets the next process on the same machine load those programs instead.
The cache key includes its directory, so the default path is fixed:
``<checkout>/.jax_cache`` (ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
