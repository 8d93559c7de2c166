"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Call ``enable()`` before the first compile. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it. Otherwise the
cache goes to ``<repo>/.jax_cache``: a fixed path, because the directory is
part of what a later process must find again (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; return the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
