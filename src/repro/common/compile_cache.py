"""Where JAX's persistent compilation cache lives, for every entry point.

The cache directory is part of each entry's key, so it must not move from
run to run: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on
import and nothing is set here; otherwise the cache goes to one fixed,
git-ignored directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/common/``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
