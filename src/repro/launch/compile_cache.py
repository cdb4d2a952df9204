"""Persistent XLA compilation cache at a place that can be set from outside.

Entry points call :func:`use_compile_cache` before anything compiles.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache lives in ``.jax_cache/`` at the checkout
root: a fixed path, because the directory is part of what a later run must
find again.
"""

from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache", "CHECKOUT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Leaves JAX's configuration alone when
    ``JAX_COMPILATION_CACHE_DIR`` is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
