"""Persistent XLA compilation cache at a directory that does not move.

JAX finds a cached executable only in the directory it was written to, so
the directory must be the same from one run to the next.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing.  Otherwise the cache lives in ``<checkout>/.jax_cache``,
which ``.gitignore`` lists.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory (``JAX_COMPILATION_CACHE_DIR`` when set)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
