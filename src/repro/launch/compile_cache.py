"""JAX's persistent compilation cache, placed for the entry points.

Only entry points call :func:`enable_compile_cache` (``escg_run``,
``escg_serve``, ``chip_smoke.py``, the benchmark mains); importing
``repro`` leaves JAX's cache settings alone, so tests stay cache-free.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again. Every program is cached, however small or quick to
    compile, since the Pallas kernels compile in well under JAX's default
    one-second threshold. ``JAX_ENABLE_COMPILATION_CACHE=false`` still
    turns the cache off."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT_ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
