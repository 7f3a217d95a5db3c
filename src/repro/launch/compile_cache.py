"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the cache directory, so a directory that moves
between runs never hits. Call :func:`use_compile_cache` before the
first compile of a process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is src/repro/launch/...)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable itself,
    so nothing is set); where it is unset, at ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
