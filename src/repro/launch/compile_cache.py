"""JAX's persistent compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, the examples, the benchmarks) call
:func:`enable_compile_cache` once before their first compile.  Importing
``repro`` never touches the cache: tests and library users keep whatever
JAX configuration they already have.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed path inside the checkout: a cache directory that moves between
# runs never hits, so the path carries no temp name, PID or timestamp.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.  Every compile is cached, however short: the
    fused detector kernels compile in well under JAX's default one-second
    floor, and together they still dominate a cold start.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
