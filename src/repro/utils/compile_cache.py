"""JAX's persistent compilation cache, at one fixed place per checkout.

The chip benchmark (`bench/run.py`) calls `enable_compile_cache()` before
its first compile.  Importing `repro` never turns the cache on, so tests
and library users keep JAX's defaults.
"""
from __future__ import annotations

import os

import jax

# <repo root>/.jax_cache: a fixed path, because the directory is part of
# what makes an entry found again by the next run.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets no other directory; otherwise the cache goes to DEFAULT_DIR."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
