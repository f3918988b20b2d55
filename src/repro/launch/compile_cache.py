"""Persistent XLA compilation cache for the entry points that compile the
full model (``chip_smoke.py``, ``benchmarks/serve_bench.py``,
``repro.launch.serve``).

A directory named by ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and
is left alone.  Otherwise the cache lives at a fixed ``.jax_cache/`` in the
repository root: the path is part of the cache key, so a temporary, per-pid
or per-run directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
