"""JAX's persistent compilation cache, kept where the next run finds it.

Call `enable()` at the start of a program, before its first compile; never
at import, so that importing a module changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the root of the checkout: src/repro/launch/ -> three levels up
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to
    `<checkout>/.jax_cache`: a fixed path, because a directory that moves
    from run to run never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
