"""JAX's persistent compilation cache, placed from outside or at a fixed path.

A cold process compiles every program it runs, which on a chip can take
most of a short run. Entry points call :func:`enable_compile_cache` once,
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache's home when ``JAX_COMPILATION_CACHE_DIR`` is not set. A fixed
#: path: the directory is part of the cache key, so one that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.jax_cache`` at
    the root of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
