"""JAX's persistent compilation cache for the command-line entry points.

A chip run compiles every kernel and program it launches; the cache lets
the next process that runs the same code skip those compiles.  The entry
points (``chip_smoke.py``, ``benchmarks/*``, ``repro.launch.serve``)
call :func:`enable_compile_cache` once, before they compile anything.
Importing the library turns nothing on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, so one source checkout always hits its own cache: the directory is
# part of the cache's key, and a path that moves (a temporary name, a pid,
# a timestamp) never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and no other directory is set here; otherwise the cache lives in
    ``.jax_cache/`` at the root of the source checkout.  Every compile is
    cached, however short: a Pallas kernel compiles in about a second,
    under JAX's default one-second threshold.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
