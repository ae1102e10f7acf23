"""JAX's persistent compilation cache for the command-line entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory. Where it is not, the cache goes to the fixed
path ``<repo>/.jax_cache``: the directory is part of the cache key, so a
path that moved between runs would never hit. Library code and tests
never call this; the scripts call it first thing.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; return its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]
