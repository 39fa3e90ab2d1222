"""JAX's persistent compilation cache, placed from outside the program.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at a fixed path inside the
checkout, `<repo>/.jax_cache`: the directory is part of the cache key, so a
path that moved between runs (a temp name, a pid, a time) would never hit.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "setup_compile_cache"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
