"""JAX's persistent compilation cache, at a fixed place.

Entry points call ``enable_compile_cache()`` once, before their first
compile; importing this module changes nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this function
sets no other directory.  Otherwise the cache lives in ``.jax_cache`` at the
root of the checkout (listed in ``.gitignore``): a fixed path, never one
that depends on a temp name, a pid or the time, so a later run of the same
checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
