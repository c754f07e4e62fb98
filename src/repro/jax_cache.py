"""JAX's persistent compilation cache, kept where a later run finds it.

The cache directory is part of what a later process looks up, so it must
not move between runs: ``JAX_COMPILATION_CACHE_DIR`` where the environment
sets it (JAX reads that variable itself), otherwise one fixed directory
inside the checkout, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; call before the first compile.
    Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
