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

Either way an operation's METADATA is part of the cache key: its
``op_name`` (with the named scopes) and the ONE source line that emits it,
as a path relative to the checkout.  JAX leaves metadata out by default,
and a cached executable then reports to a profiler the names and lines of
whatever program first compiled to the same operations: on a machine with
a warm cache a trace showed no scope in the three serving programs whose
operations PR 24 had not otherwise changed (PERF.md).  A trace has to name
the code that runs.  JAX's key takes the locations whole or not at all
(``strip-debuginfo``), so what can be kept out of them is: the callers'
frames (``jax_traceback_in_locations_limit`` 1: an edit to a driver, a
server or a test moves no key) and the checkout's own path
(``jax_hlo_source_file_canonicalization_regex``: a cache shared between
checkouts still hits).  The price that stays: a program compiles again
after an edit that moves the lines that EMIT its operations
(``models/transformer.py``, ``ops/``, ``optim.py``, ``serving/cache.py``,
the compiled bodies in ``serving/engine.py``).  A limit of 0 would key on
``op_name`` alone, and leave every trace without a ``source`` line — the
line is what tells a whole-pool copy's cause from its scope's other
operations (PERF.md section 5).  (Turning
``jax_include_full_tracebacks_in_locations`` off instead drops the scopes:
XLA then reads only the primitive's name as ``op_name``; seen on the chip.)
"""

from __future__ import annotations

import os
import re

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect.  Call it
    before the first compilation."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(CHECKOUT + os.sep))
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
