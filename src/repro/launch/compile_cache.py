"""JAX's persistent compilation cache for the entry points.

Each entry point (``repro.launch.serve``, ``repro.launch.train``,
``chip_smoke.py``) calls :func:`enable_compile_cache` at the start of its
``main``; importing this module changes nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
directory is set.  Otherwise the cache lives at one fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored): the path is part of the cache
key, so it never comes from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
