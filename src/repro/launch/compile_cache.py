"""Where JAX keeps its persistent compilation cache for this checkout."""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# A fixed directory at the root of the checkout (listed in .gitignore). The
# path is part of what a later run looks up, so it must not move between
# runs: never a temp name, a pid or a time.
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
