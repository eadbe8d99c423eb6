"""JAX's persistent compilation cache, at a fixed place.

Entry points call :func:`enable_compile_cache` once, at start-up; importing
this module sets nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this sets nothing.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the path is part of what
makes a cached program found again (``.gitignore`` lists it).
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory it uses."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
