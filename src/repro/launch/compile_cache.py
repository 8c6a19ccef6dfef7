"""JAX persistent compilation cache at a fixed location.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call ``enable_compile_cache()`` before their first compile; importing this
module changes nothing.  The cache directory is part of what a later run
must find again, so it never moves: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), otherwise
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
