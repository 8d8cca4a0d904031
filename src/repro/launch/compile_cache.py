"""JAX's persistent compilation cache for this checkout's entry points.

``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve`` and
``benchmarks.run`` call :func:`enable_compile_cache` once at start-up, so
their processes share compiled programs.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it; otherwise the cache lives at a fixed ``.jax_cache/``
in the checkout (git ignores it).  The path is part of the cache key, so
it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
