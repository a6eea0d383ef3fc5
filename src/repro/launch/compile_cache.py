"""JAX's persistent compilation cache, kept at one place.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the path is part of what a later run must find again.  The
directory is git-ignored and only JAX reads it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX at the cache directory; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
