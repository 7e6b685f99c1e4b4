"""JAX's persistent compilation cache for the entry points.

Call ``enable()`` before the first compile.  When ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already keeps its cache there and nothing else is configured.
Otherwise the cache goes to one fixed directory inside the checkout
(``.jax_cache``, git-ignored): a fixed path, because a directory that moves
between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
