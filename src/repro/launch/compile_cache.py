"""JAX's persistent compilation cache, placed at a fixed directory.

The cache key includes the directory, so the directory must not move
between runs: a path built from a temporary name, a pid or the time would
never hit.  Entry points call :func:`enable_compile_cache` once, before
they compile anything; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout this package runs from (src/repro/launch/..)
REPO_ROOT = Path(__file__).resolve().parents[3]
#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no directory of its own; otherwise the cache goes to
    ``<repo>/.jax_cache``.  The key includes the program's metadata (its
    name stacks and source lines): JAX leaves it out by default, and then
    a program that differs only in its named scopes loads the entry an
    older one wrote, whose operations a profiler trace names by the older
    scopes."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
