"""JAX's persistent compilation cache, kept at one fixed place."""

from __future__ import annotations

import os
import pathlib

import jax

# The checkout root, found from this file (src/repro/launch/...): the
# cache directory is part of each entry's key, so it never moves.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep this process's compiled programs for later runs.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
    nothing is changed. Otherwise the cache goes to ``.jax_cache/`` at
    the checkout root. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
