"""JAX's persistent compilation cache for the entry points.

Every ``main()`` (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, the ``benchmarks/`` mains) calls
``enable_compile_cache()`` first; nothing turns the cache on at import,
so tests never use it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache key includes it, so a moving directory never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Cache every compiled program on disk and return the directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself); otherwise the cache is ``<repo>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
