"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts) call
:func:`enable_compile_cache` before their first compile, so a rerun in
the same checkout reuses compiled kernels instead of compiling them
again. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and no directory is set here. Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, never a temporary, per-process
or time-stamped one, since a cache that moves is never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/compile_cache.py -> the checkout root holding src/.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return the directory it uses.

    The fitmask kernels compile in well under a second, below JAX's
    default one-second floor for writing an entry, so the floor is
    lowered to zero: without that none of them would be cached."""
    import jax
    path = os.environ.get(CACHE_ENV, "")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
