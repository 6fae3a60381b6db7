"""Persistent compilation cache placement for the entry points.

Called from ``main()`` of ``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks.run``, never at import, so tests and library callers keep
JAX's default (no persistent cache unless the environment asks for one).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/runtime/compile_cache.py -> the repository root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (git-ignored): a fixed path, because the path is
    part of what a later process must find again."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
