"""JAX persistent compilation cache at a fixed place.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
otherwise ``<checkout>/.jax_cache``.  Entry points call
``enable_compile_cache()`` before they compile anything.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
