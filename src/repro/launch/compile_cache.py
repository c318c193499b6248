"""Persistent XLA compilation cache, placed from outside.

A process that compiles the full model spends most of a cold start in
the compiler; the persistent cache lets the next process of the same
checkout load those programs instead. Call :func:`enable_compile_cache`
from an entry point before the first compile — never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, inside the checkout and ignored by git: the cache directory is
# part of what makes a later process find an entry, so it must not
# depend on a temp name, a pid or the time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing. Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
