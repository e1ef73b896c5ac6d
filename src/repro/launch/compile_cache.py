"""JAX's persistent compilation cache for the entry points.

A directory named by ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads the
variable itself). Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of the cache key and a directory
that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
#: the fused kernels compile in about a second; JAX's default floor of one
#: second would leave most of them uncached
MIN_COMPILE_SECS = 0.1


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
