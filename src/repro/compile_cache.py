"""Persistent XLA compilation cache for the entry points.

The entry points (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` once at start-up;
importing ``repro`` never does. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is changed here. Otherwise the cache lives
at the fixed, git-ignored path ``<checkout>/.jax_cache``: the directory is
part of what makes an entry findable again, so it is never built from a
temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
