"""JAX's persistent compilation cache, for the entry points.

Each entry point's ``main()`` calls :func:`use_compile_cache` before it
compiles anything; importing a module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout holding ``src/repro``; the default cache lives in it at a
#: fixed path, because the directory is part of every cache key
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
