"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call ``enable_compile_cache`` once before their first compile; library
modules never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets nothing.  Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout, found from this file's own path: the path is
part of what a cache entry is found by, so it never moves between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Returns the directory the cache is written to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
