"""Persistent XLA compilation cache for the program's entry points.

Every process that compiles for the chip (``chip_smoke.py``, the
benchmark workers, the training launcher) calls
:func:`enable_compile_cache` once, before its first compile. Tests
never call it, and nothing calls it at import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads its cache
from there and this sets no other directory. Otherwise the cache goes
to ``<checkout>/.jax_cache`` (git-ignored). The path is fixed because it
is part of the cache key: a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
