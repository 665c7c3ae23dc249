"""JAX's persistent compilation cache for the command-line entry points.

A cold mining run on a TPU compiles one step program per (metric, k,
geometry, bucket width) and each can take tens of seconds, so
`repro.launch.mine`, `benchmarks/run.py` and `chip_smoke.py` call
`enable_compile_cache()` first.  Tests do not: they compile uncached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout (listed in .gitignore): the cache is only
# found again by a later process that looks in the same place.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Cache every compiled program on disk; returns the cache directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this leaves the directory alone; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Both thresholds drop to 0 so that short
    compiles are cached as well as slow ones.
    """
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
