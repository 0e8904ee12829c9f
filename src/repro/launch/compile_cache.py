"""JAX's persistent compilation cache, kept at one stable place.

Call :func:`enable` from a program's ``main()`` — never at import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set here.  Otherwise the cache lives in ``.jax_cache/`` at the root of
the checkout (git-ignored): a fixed path, because the path is part of the
cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE_DIR", "enable"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
