"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
``enable_compilation_cache`` before their first compile, so a second run
of the same program loads its executables instead of compiling them.
Importing ``repro`` does not call it: the tests stay cache-free.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed place, so every run of every entry point finds the same entries
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
