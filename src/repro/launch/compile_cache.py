"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is cached under a key that includes the cache's path, so
the directory must not move between runs: it comes from
``JAX_COMPILATION_CACHE_DIR`` when that is set, and is otherwise the fixed,
gitignored ``.jax_cache/`` at the root of the checkout. Call
:func:`enable_compile_cache` from an entry point's ``main`` before the first
compile; nothing calls it at import.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads the cache
    directory from it, and no other directory is set here.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
