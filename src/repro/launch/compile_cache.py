"""Where JAX keeps compiled programs between processes."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, because the cache directory is part of
# what a cached entry is found by — a path that moves never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to DEFAULT_DIR."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
