"""Persistent compilation cache placement shared by every entry point.

A cold run on the chip compiles every program; the persistent cache lets
the next process (and the next run of the same checkout) skip that.  The
cache's path is part of its key, so it never moves: the directory named by
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), else
``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first JAX computation of a process.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    return path
