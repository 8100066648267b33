"""JAX's persistent compilation cache, placed from outside.

Entry points that compile for the chip call ``enable_compile_cache``
once, before their first compile.  Tests never call it, and importing
this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache: a fixed path, because the cache directory is part of
# what a later run must find again (listed in .gitignore)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
