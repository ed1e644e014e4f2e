"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and ``python -m
repro.launch.train`` call :func:`enable_compile_cache` before their
first compile, so a later run of the same checkout loads every
executable an earlier run built instead of compiling it again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo root>/.jax_cache: a fixed path inside the checkout (listed in
# .gitignore), so every run of this checkout finds the same cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX has read it already and it stands; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  The size and compile-time thresholds are
    dropped so that every executable is cached: the data plane compiles
    many small, fast-compiling variants per plan."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
