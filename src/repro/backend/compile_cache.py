"""JAX's persistent compilation cache at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``)
call :func:`enable_compile_cache` once before they compile; tests do not.
The directory is part of the cache key, so it never depends on a temp name,
a PID or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets no other directory.  Otherwise the cache is ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
