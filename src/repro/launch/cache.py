"""JAX's persistent compilation cache at a fixed, outside-placeable path.

A run that compiles the same kernels as an earlier one on the same machine
reads them back instead of compiling again — but only if the cache
directory is the same: the path is part of the cache key, so it is never
built from a temp name, a PID or the time.
"""
from __future__ import annotations

import os

import jax

#: the in-repo default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in
    code (JAX reads the variable itself); otherwise the cache lives at
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
