"""JAX's persistent compilation cache, placeable from outside.

Entry points that run on the chip call :func:`enable_compile_cache` before
their first compile, so a second run of the same checkout reads compiled
programs back instead of compiling them again.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when the environment names none: fixed inside the
#: checkout (listed in .gitignore), so every run of this checkout finds the
#: entries the last one wrote
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX
    reads it and this function sets no other directory.  Otherwise the
    cache goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
