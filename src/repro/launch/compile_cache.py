"""JAX's persistent compilation cache, at one fixed place.

Entry points call :func:`enable_compile_cache` before their first compile.
``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX reads it
itself; otherwise the cache lives in ``.jax_cache`` at the root of the
checkout.  The directory is part of what a cached entry is found by, so it
is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
