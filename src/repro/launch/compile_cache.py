"""JAX's persistent compilation cache for the entry points.

A cold start on the chip compiles every program (the full-width decode
step alone takes tens of seconds); with the cache on, a second process
with the same programs loads them instead.  The directory is part of the
cache's key, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that itself, and no other directory
is set here), else ``<checkout>/.jax_cache``, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
