"""JAX's persistent compilation cache, switched on by the entry points.

Library code never calls this at import: a test or an embedding program
keeps whatever cache setting it chose.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

#: the checkout root (``src/repro/launch/`` is three levels below it)
_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing else is set here.  Otherwise the cache goes to ``.jax_cache/``
    at the checkout root: a fixed path, because the cache directory is part
    of what a later process must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
