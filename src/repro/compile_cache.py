"""Where JAX keeps its persistent compilation cache.

Every entry point (chip_smoke.py, ``repro.launch.train``, the examples and
the test suite) calls :func:`enable_compile_cache` once before it compiles.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
this leaves it alone. Otherwise the cache goes to :data:`CACHE_DIR`, one
fixed directory at the root of the checkout: the path is part of what JAX
keys a cache entry on, so a directory that moved between runs would never
be hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
