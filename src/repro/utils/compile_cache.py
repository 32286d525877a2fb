"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``repro.launch.serve``, ``repro.launch.remote_serve``,
``benchmarks/run.py``, ``chip_smoke.py``) call ``enable_compile_cache``
from ``main``; nothing calls it on import, and tests never do.  A full-width
model compiles for tens of seconds per program, so a second run that finds
its executables on disk starts serving far sooner.

The cache key includes the directory path, so the path must not move between
runs: it is ``<repo>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set,
in which case JAX already reads that variable and no other directory is set.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
