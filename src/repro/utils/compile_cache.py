"""JAX's persistent compilation cache, at a place that does not move.

Every entry point (``chip_smoke.py``, ``examples/``, ``benchmarks/``) calls
:func:`enable_compile_cache` once before it compiles anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because the path is part of what the cache is found by, so a
directory named after a pid, a time or a temporary name never hits again.
Tests do not call this; they compile on the CPU without a cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at a fixed directory; returns it."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
