"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed on the program and found again only at the same
path, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (JAX reads that variable itself, and nothing here overrides it),
otherwise ``.jax_cache`` at the root of this checkout, which git ignores.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
