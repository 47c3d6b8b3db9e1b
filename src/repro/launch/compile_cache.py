"""Persistent XLA compilation cache for the entry points.

Entry points (chip_smoke.py, launch/serve.py, benchmarks/run.py) call
`enable_compile_cache()` once at start-up; nothing calls it at import.
When JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and that
directory is the only cache path. Otherwise the cache lives at a fixed
directory inside the checkout (`.jax_cache/`, git-ignored): the path is
part of the cache key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: in-checkout cache used when JAX_COMPILATION_CACHE_DIR is not set
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process
    (no minimum compile time, so the serving path's small executables
    are kept too). Returns the cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
