"""Persistent XLA compilation cache for the launchers and chip_smoke.py.

A full-width step takes minutes to compile; the cache lets a second run
of the same program skip that. The directory is part of the cache key, so
it must not move between runs: JAX's own ``JAX_COMPILATION_CACHE_DIR``
wins when set, otherwise the fixed ``.jax_cache/`` at the repo root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call from a program's entry point, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env  # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
