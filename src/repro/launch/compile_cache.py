"""JAX's persistent compilation cache for this repo's entry points.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
their first compile; importing ``repro`` never turns the cache on, so
tests stay free of it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache lives there alone.  Otherwise it lives at the fixed path
``<checkout>/.jax_cache``: the cache key includes the path, so the path
never carries a temp name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: the small staging plans recompile on each cold
    # start too, and only a cache hit makes a warm start warm.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
