"""JAX's persistent compilation cache for this checkout's entry points.

Call :func:`use_compile_cache` at the start of an entry point's ``main``
(never at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is changed.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the path is part of what
the cache is keyed on, so a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
