"""JAX's persistent compilation cache at one fixed place per checkout.

Call ``enable_compile_cache()`` before the first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here changes it. Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the path is part of the cache key, so a directory
that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
