"""Where JAX keeps its persistent compilation cache.

The cache key includes the cache path, so the path must not move from
one run to the next. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX
reads it by itself and nothing is set here. Otherwise the cache lives
at the fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and
    return that directory. Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
