"""Where this program keeps compiled executables between processes.

The cache directory is part of what a later process must find again, so
there is exactly one rule, applied by every entry point on the chip path
(``chip_smoke.py``, ``bench.py``, the serving replicas, the examples and
benchmarks):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing here
  sets a directory in code, so whoever placed the cache from outside
  (the chip tool, an operator) wins;
* unset — ``<checkout>/.jax_cache``, a fixed, git-ignored path next to
  the package: never a temp dir, a pid or a time, which would never hit.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect.  Call it
    before the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
