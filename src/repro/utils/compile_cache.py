"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` from ``main`` (never at import,
so importing a module never changes JAX's configuration). A cold run on the
chip spends much of its time compiling, and the cache lets a later process
reuse what an earlier one compiled. The cache's key includes its directory,
so the directory must not move between runs: no temp name, pid or time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo root>/.jax_cache: src/repro/utils/compile_cache.py is parents[3] deep
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``<repo root>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
