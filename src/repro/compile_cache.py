"""JAX's persistent compilation cache for the repository's entry points.

Each entry point (``chip_smoke.py``, ``benchmarks.sweep``,
``benchmarks.run``, ``repro.launch.train``, ``repro.launch.serve``) calls
:func:`enable_compile_cache` first thing in its ``main()``, so a second
run of the same programs loads them instead of compiling them.  Nothing
calls it on import: tests and library users run without a cache unless
they ask for one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

#: the checkout this package runs from (``src/repro/compile_cache.py``)
_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing else is set.  Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``: the directory is part of each entry's key,
    so it must not move between runs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
