"""JAX's persistent compilation cache for the repo's entry points.

Call :func:`enable_compile_cache` once at start-up of a script (never on
import): a second run then reads its compiled programs from disk
instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here. Otherwise the cache is the fixed
    directory ``<checkout>/.jax_cache`` (listed in ``.gitignore``), so
    every run in one checkout finds what earlier runs wrote.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # a Pallas kernel compiles in well under JAX's default one-second
    # floor for caching an entry
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
