"""JAX's persistent compilation cache for the repo's entry points.

A fresh process on a TPU host compiles every served program and kernel
again; with the cache on, a second run reads them back.  The entry points
(``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``) call
:func:`enable_compile_cache` before they compile anything.  Importing the
library sets nothing.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache — a fixed path: the cache key includes it, so a
# directory that moves between runs never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
