"""Placement of JAX's persistent compilation cache.

Entry points call :func:`setup_compile_cache` once at start-up, before the
first compile; importing this module changes nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache goes to a fixed
directory inside the checkout, ``<repo>/.jax_cache``: the path is part of
the cache key, so it must not carry a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["setup_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
