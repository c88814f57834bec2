"""JAX persistent compilation cache placement for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, owns the cache: JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives in a fixed
``.jax_cache/`` at the root of the checkout.  The path is part of the cache
key, so it never derives from a temporary name, a pid or the time — a run
finds the programs an earlier run of the same checkout compiled.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns that path."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
