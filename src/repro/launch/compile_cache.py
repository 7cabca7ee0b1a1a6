"""JAX's persistent compilation cache, kept at one fixed place.

The cache directory is part of what a later process must find again, so
it never moves: ``$JAX_COMPILATION_CACHE_DIR`` where that is set (JAX
reads the variable itself), else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
