"""Where JAX keeps its persistent compilation cache.

A full-width train step takes tens of seconds to minutes to compile. The
cache key includes the cache's path, so it is kept at a path that does not
move: the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, and
otherwise the fixed directory ``.jax_cache`` at the root of the checkout
(listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
