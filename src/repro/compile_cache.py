"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``:
the path is part of the cache key, so it must not move between runs.
Every entry point (``launch/train.py``, ``launch/vc_serve.py``,
``chip_smoke.py``, ``benchmarks/run.py``) calls ``enable()`` before its
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
