"""Persistent XLA compile cache placement, for entry points only.

Call ``configure_compile_cache()`` from a ``main()`` (never at import).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
leaves it alone.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a cache hit needs, and a
git-ignored one, because compiled programs are never committed.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
