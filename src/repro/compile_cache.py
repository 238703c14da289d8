"""JAX's persistent compilation cache, placed once per process.

Every process that compiles model steps (a fabric endpoint building a
``jit/`` container, an endpoint running federated training steps,
``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile, so a cold endpoint reads back what an earlier process on the
same checkout already compiled (ROADMAP 1.5).

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR``, when set: JAX reads it itself and this
  module sets no other path;
- otherwise ``<checkout>/.jax_cache``, one fixed path. The directory is
  part of the cache's key, so it is never built from a temp name, a pid
  or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point this process's JAX at the persistent cache; returns its
    directory. Idempotent."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
