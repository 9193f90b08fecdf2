"""Where JAX keeps compiled programs between runs.

Call :func:`use_compile_cache` before the first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
path is set here; otherwise the cache is ``<checkout>/.jax_cache``, a
fixed path, so a later run of the same checkout finds it again.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
