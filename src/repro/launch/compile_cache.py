"""Persistent XLA compilation cache for the program's entry points.

A cold process compiles every program from scratch; on the chip that is
most of a short run.  :func:`enable_compile_cache` keeps compiled
programs on disk so that the next process with the same programs loads
them instead.  Entry points call it (``chip_smoke.py``,
``repro.launch.serve``); importing this module sets nothing, and tests do
not call it.
"""
from __future__ import annotations

import os

import jax

# one fixed directory in the checkout: the cache's key includes nothing
# that moves between runs, so a path that moved would never hit
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to :data:`CACHE_DIR`
    (``<checkout>/.jax_cache``, ignored by git)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
