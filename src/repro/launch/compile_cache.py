"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` at the start of ``main()``
(never at import, and tests never turn it on).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and nothing is set here.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout: the path is part of the cache key, so a
temporary or per-process directory would never be hit again.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
