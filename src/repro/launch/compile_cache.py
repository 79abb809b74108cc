"""JAX's persistent compilation cache, placed from outside the program.

A cache only hits when its directory is the same from run to run, so it
lives at one fixed place: where ``JAX_COMPILATION_CACHE_DIR`` says, when
that is set, and otherwise at ``<repo>/.jax_cache`` (git-ignored).
Importing this module changes nothing; the entry points call
:func:`use_compile_cache` before their first compile.

The key includes the program's metadata: an executable loaded from the
cache carries the ``op_name`` scopes of the program that compiled it,
and a device profile names its ops by them, so a program that differs
only in its ``jax.named_scope`` paths must not load another's. Source
paths enter the metadata relative to the repository root, so a checkout
keeps its hits wherever it lies.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory. A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting
    and is left alone; otherwise the cache goes to the fixed
    :data:`REPO_CACHE_DIR`. Either way the key holds the metadata, with
    source paths relative to :data:`REPO_ROOT`. Takes effect for
    compiles after the call."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
