"""JAX persistent compilation cache for the entry points."""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed cache path inside the checkout (listed in .gitignore); the path is
#: part of the cache key, so it must not move between runs
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Call once, at the start of an entry point,
    before anything compiles.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
