"""JAX's persistent compilation cache for the launch CLIs.

A full-width train step takes tens of seconds to compile; the cache lets a
later process on the same machine load it instead. The cache key includes
the directory, so the default is one fixed path inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this leaves it alone. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
