"""JAX's persistent compilation cache, placed from outside.

``setup_compile_cache()`` is called by the runnable entry points
(``chip_smoke.py``, ``benchmarks/run.py``, the examples, ``python -m
repro.obs``), never on import of the library.  The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the
fixed ``<repo>/.jax_cache``: the path is part of the cache key, so a
directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
