"""Where jax's persistent compilation cache lives.

The only place in the repo that sets `jax_compilation_cache_dir`. Entry
points that compile (chip_smoke.py, bench.py's child mode, the tools/
scripts) call `enable_compile_cache()` first thing.

- `JAX_COMPILATION_CACHE_DIR` set: nothing is done here — jax reads the
  variable itself, and whoever set it owns the placement.
- unset: the cache goes to `<checkout>/.jax_cache` (git-ignored). The
  path is part of jax's cache key, so it is one fixed directory, never
  a temporary name, pid or timestamp.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_OPTION", "REPO_CACHE_DIR", "enable_compile_cache"]

CACHE_OPTION = "jax_compilation_cache_dir"

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Arm the persistent compile cache; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update(CACHE_OPTION, REPO_CACHE_DIR)
    return REPO_CACHE_DIR
