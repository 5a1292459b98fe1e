"""JAX's persistent compilation cache, placed from outside.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
sets no other directory.  Unset: the cache goes to ``.jax_cache`` at the
root of the checkout — a fixed path, because the path is part of what a
later process must find again (never a temp name, a pid or the time).
"""
from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
