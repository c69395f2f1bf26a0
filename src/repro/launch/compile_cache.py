"""JAX's persistent compilation cache, placed from outside or at the
checkout.

Call :func:`enable_compile_cache` at the top of a program's ``main``,
before anything compiles — never at import, so importing the package
(or running the tests) leaves JAX's cache settings alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fallback cache directory: fixed, at the root of the checkout that
#: holds this file (the path is part of what a cache hit needs)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    that directory is used as is; otherwise the cache goes to
    ``<checkout>/.jax_cache``. The minimum compile time to cache is
    lowered to zero so the host trainer's many small stage programs are
    cached too, not only the programs that take over a second.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
