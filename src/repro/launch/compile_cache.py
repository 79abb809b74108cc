"""JAX's persistent compilation cache, placed from outside the program.

A cache only hits when its directory is the same from run to run (the
path is part of the key), so it lives at one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says, when that is set, and otherwise at
``<repo>/.jax_cache`` (git-ignored). Importing this module changes
nothing; the entry points call :func:`use_compile_cache` before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory. A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting
    and is left alone; otherwise the cache goes to the fixed
    :data:`REPO_CACHE_DIR`. Takes effect for compiles after the call."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
