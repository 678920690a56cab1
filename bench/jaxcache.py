"""JAX's persistent compilation cache, at a fixed directory inside the
checkout (``.jax_cache``, listed in ``.gitignore``), so that only the
first run of a cell in a checkout compiles.

The directory is given to the program through the variable its
``launch/compile_cache.enable_compile_cache`` reads, and set before JAX
starts, in place of any the environment carries.  Its size is not
bounded: a bound turns on eviction, whose file lock times out under the
reference's side-by-side compiles, and entries larger than the bound
(a VGG step program is) are never written.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"


def use_checkout_cache() -> str:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)
