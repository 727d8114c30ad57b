"""JAX's persistent compilation cache, kept where the next process finds it.

Every entry point (``train_gnn``, ``serve_gnn``, ``chip_smoke.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing here overrides it; otherwise the cache lives at one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored).  The
path never depends on a temporary name, a process id or the clock, so a
second process compiling the same program reads what the first one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
