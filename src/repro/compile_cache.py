"""JAX's persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call ``enable_compile_cache()`` once at start; nothing calls it on import,
so tests and library users keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself; no other directory is set).  Otherwise the cache
    lives at the fixed ``<repo>/.jax_cache``, never a temporary name, a
    pid or the time, so a later run finds what an earlier one wrote.
    Every jit is cached, however quick its compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
