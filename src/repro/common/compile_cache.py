"""JAX's persistent compilation cache, placed for the program's entry points.

The cache key holds the cache's own path, so a directory that moves never
hits: the cache lives at one fixed place.  Call :func:`enable_compile_cache`
from an entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``) before anything compiles — never at import time and
never from tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/common/...
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
