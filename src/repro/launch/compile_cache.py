"""JAX persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself
and nothing here overrides it.  Otherwise the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored), so a
second run of the same program on the same machine reuses the first
run's executables.  The path never carries a pid, a time or a temporary
name: it is part of the cache key, and a path that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
