"""JAX's persistent compilation cache for this repo's entry points.

A cold process compiles every serving executor, kernel and train step
from scratch; with the cache on, a later process in the same place
reads them back. Entry points (``chip_smoke.py``,
``launch/serve_bnn.py``, the benchmark writers through
``benchmarks/_util.py``) call :func:`enable_compile_cache` before their
first compile. Library modules never do: importing one leaves jax's
configuration alone.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed directory at the root of the checkout: the cache's key
# includes nothing about the directory, but a path that moved between
# runs would never be found again.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and
    is left as it is. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]
