"""Persistent XLA compile-cache placement (one owner for all entry points).

``ops/blake2b.py`` compiles one program per (declared rows,
power-of-two block count) bucket; a persistent cache turns a second
start into cache hits for every program that compiles above the floor
set below.  The directory is part of jax's cache key, so it
must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` in the environment places the cache from
  outside.  jax reads the variable itself; this module then sets nothing
  in code, so the operator's placement is never overridden.
* otherwise the cache lives in ONE fixed, git-ignored directory at the
  root of the checkout — the same path from any pid or working
  directory.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — three levels up from this file
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Make sure jax keeps a persistent compile cache; return its path.

    Call before the first device program compiles (jax latches the
    cache at first use).  Every entry point that can reach a device
    program goes through here: the sidecar, ``chip_smoke.py``,
    ``__graft_entry__.py``, the examples and ``tests/conftest.py``.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # half of jax's default 1 s floor.  The served Pallas BLAKE2b
    # programs still compile under it on the chip: a second start
    # builds them again and reads nothing back (PERF.md §6, PR 29)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return DEFAULT_CACHE_DIR
