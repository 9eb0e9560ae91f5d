"""Placement of JAX's persistent compilation cache.

Entry points that drive a chip call `configure_compile_cache()` before their
first compile, so a second process with the same programs loads them from
disk instead of compiling them again.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache goes to the fixed path
`<checkout>/.jax_cache`: a directory named from a temporary name, a pid or
the time would never be found again by the next process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "CHECKOUT", "configure_compile_cache"]

#: the environment variable JAX reads as `jax_compilation_cache_dir`
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (this file is <checkout>/src/repro/launch/...)
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory: the environment's `JAX_COMPILATION_CACHE_DIR` when set,
    else `<checkout>/.jax_cache`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
