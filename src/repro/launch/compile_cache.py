"""Where JAX keeps its persistent compilation cache.

A full-width train step takes tens of seconds to minutes to compile. The
cache key includes the cache's path, so it is kept at a path that does not
move: the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, and
otherwise the fixed directory ``.jax_cache`` at the root of the checkout
(listed in ``.gitignore``).

The key also covers each instruction's metadata, the source-level names
(``op_name``) a profile reads. JAX leaves it out by default, so that a
program changed only in its names (a ``jax.named_scope`` added) would load
the executable compiled before the change, and its trace would carry the
old names.
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
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
