"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` from ``main()`` (never at
import). If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at the
root of this checkout — a fixed path derived from this file, because the
directory is part of what a cache entry is found by: a temp name, pid or
timestamp would never hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
