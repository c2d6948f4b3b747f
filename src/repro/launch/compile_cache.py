"""JAX's persistent compilation cache, placed at one fixed directory.

Entry points call :func:`use_compile_cache` first thing; library modules
never do.  The directory is part of every cache key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
