"""JAX's persistent compilation cache for the entry points.

The cache key includes the cache directory, so a directory that moves
between runs never hits: the cache lives where ``JAX_COMPILATION_CACHE_DIR``
says when that is set, and otherwise at one fixed path inside the checkout
(``<repo>/.jax_cache``, listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
