"""Where JAX keeps its persistent compilation cache.

The cache directory is part of what a later run must find again, so the
default is a fixed directory inside the checkout: never a temporary
name, a process id or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    the cache stays there; no other directory is set.  Otherwise the cache
    goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
