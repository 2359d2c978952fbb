"""JAX's persistent compilation cache for the command-line entry points.

Call :func:`enable_compile_cache` from a ``main()``, never at import:
tests and library users keep JAX's own settings.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    else is set.
  * otherwise: ``<repo>/.jax_cache``, a fixed path (listed in
    ``.gitignore``). The path is part of the cache key, so a directory
    built from a temp name, a pid or the time would never hit.
  * ``JAX_ENABLE_COMPILATION_CACHE=false`` turns the cache off (tests that
    run an entry point in a subprocess set it).
"""
from __future__ import annotations

import os
from typing import Optional

import jax

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache; returns its directory, or
    None when the cache is turned off."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
