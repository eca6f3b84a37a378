"""JAX's persistent compilation cache, set up in one place.

JAX writes a program to the persistent cache only when compiling it took
at least ``jax_persistent_cache_min_compile_time_secs`` (1 s by default),
so the cache holds the larger programs (jitted steps, the scans under the
eager step's ops), not the eager step's small op-by-op compiles.  Entry
points call :func:`setup_compile_cache` before they compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root, three levels up
# from the package directory.  A fixed path, so every run finds the entries
# earlier runs wrote.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is configured here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
