"""Persistent XLA compilation cache for the entry points.

Compiling full-width models and their Pallas kernels is a large part of a
cold start.  The entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) call :func:`enable_compile_cache` before their first
compile so that a later process finds what an earlier one compiled:

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and this helper sets nothing;
  * otherwise the cache goes to ``<repo>/.jax_cache``.  The path is fixed
    (never built from a temporary name, a process id or the time) because
    it is part of the cache's key: a directory that moves never hits.

Importing this module changes nothing; only the call does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
