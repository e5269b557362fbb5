"""Persistent compilation cache for the entry points.

Called from ``main()`` of each entry point, never at import.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed. Otherwise the cache goes to ``<checkout>/.jax_cache``:
    a fixed path, since the path is part of what a later run must find.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
