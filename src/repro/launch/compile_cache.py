"""Where JAX keeps its persistent compilation cache.

The cache's path is part of its key, so it must not move between runs: a
directory built from a temporary name, a pid or the time never hits.  Entry
points call :func:`use_compile_cache` at the start of ``main`` — never at
import time, so importing the library changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/compile_cache.py
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and its
    setting is left alone.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
