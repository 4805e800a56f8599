"""Persistent XLA compilation cache for the chip entry points.

A run finds what an earlier run compiled only if both use the same
directory, so it never moves: either the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself, and
nothing here overrides it) or the fixed ``.jax_cache/`` at the
repository root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
