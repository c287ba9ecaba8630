"""Persistent XLA compilation cache for the command-line entry points.

A cold DC-SVM run compiles dozens of programs (one per level shape, solver
variant and serving bucket); the persistent cache lets the next process
load them instead.  Call ``enable_compile_cache()`` first thing in a
``main``, never at library import and never from the test suite.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# every cache key — a temp- or pid-named directory would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
