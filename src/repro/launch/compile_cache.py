"""JAX persistent compilation cache location for the entry points.

Call :func:`use_compile_cache` once, before the first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
nothing is changed. Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path inside the checkout, because the path is part of what a later
process must find again (no tempdir, pid or time in it).
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
