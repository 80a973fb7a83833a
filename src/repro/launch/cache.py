"""Persistent compilation cache for the command-line entry points.

Compiling the train step dominates a cold run on the chip. Entry points call
:func:`use_compile_cache` first thing, so their processes share compiled
programs: in the directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads the
variable itself), else in the fixed ``<repo>/.jax_cache``. The path is part of
a cache entry's key, so it never depends on a temporary name, pid or time.
Nothing sets the cache on import: the library and its tests compile uncached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
