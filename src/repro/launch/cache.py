"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`enable_compile_cache` before it compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (git-ignored).  A fixed path: the directory is
#: part of the cache key, so a name built from a PID or a time never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and nothing
    is changed here; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
