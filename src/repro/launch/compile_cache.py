"""Persistent JAX compilation cache for every entry script.

``setup()`` is the one call the entry points make (``chip_smoke.py``,
``benchmarks/run.py``, the examples' ``main()``) before their first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing here names another directory. Otherwise the cache lives at a
fixed path inside the checkout, ``.jax_cache/`` (gitignored): the path is
part of the cache key, so it is never derived from a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
