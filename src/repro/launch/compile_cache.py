"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`use_compile_cache` under their ``__main__`` check;
library code and tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Returns the directory compiled programs are cached in.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, because the path is part of what a later run looks up.
    It keeps every program, not only those that took JAX's default second
    to compile: a decode step compiles in under a second on a v5e host, and
    each later run would pay that again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
