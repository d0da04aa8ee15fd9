"""JAX's persistent compilation cache for the repo's entry points.

Call :func:`use_compile_cache` from a program's ``main()`` before the
first compilation; importing this module changes nothing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``, a path
fixed by this file's place in the checkout: the directory is part of
each entry's key, so a path that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; returns it."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
