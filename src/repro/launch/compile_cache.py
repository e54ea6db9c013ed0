"""JAX persistent compilation cache for the program's entry points.

Call :func:`enable_compile_cache` from an entry point (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``) before the first compile;
importing ``repro`` never turns the cache on. The directory is part of the
cache's key, so it never moves: ``JAX_COMPILATION_CACHE_DIR`` where that is
set (JAX reads the variable itself), else ``.jax_cache`` at the checkout
root, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache(
        environ: Mapping[str, str] = os.environ) -> pathlib.Path:
    """Turn the cache on and return its directory."""
    env = environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return REPO_CACHE_DIR
