"""JAX's persistent compilation cache, kept where a later run finds it.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  ``enable_compile_cache`` therefore uses
``$JAX_COMPILATION_CACHE_DIR`` when it is set, and otherwise one fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored) — never
a temp name, pid or time.  Entry points call it from their ``main``; it is
never called while a module is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``.
    Every compile is cached, however quick, so a second run of the same
    program skips compilation."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
