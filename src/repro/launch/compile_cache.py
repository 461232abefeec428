"""Persistent JAX compilation cache at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing is set here. Otherwise ``enable_compile_cache`` points JAX at
``<checkout>/.jax_cache``. The path is fixed on purpose: it is part of what
a later run must find again, so a temporary or per-process name would
never hit.

  from repro.launch.compile_cache import enable_compile_cache
  enable_compile_cache()      # before the first compile
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (left to JAX), else
    ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
