"""JAX's persistent compilation cache, kept at one fixed path.

The cache key includes the directory, so a path that changes from run
to run (a temp name, a pid, the time) would never hit.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (src/repro/launch/ -> three levels up)
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the cache on before anything compiles; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing else is set here.  Otherwise the cache goes to
    ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
