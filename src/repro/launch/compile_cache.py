"""JAX persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples,
``serve_tm.main``) call :func:`use_compile_cache` once before their first
compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it into
  ``jax.config.jax_compilation_cache_dir`` and the cache lives there — no
  other directory is ever set.
* Unset: the cache lives at :data:`DEFAULT_DIR`, one fixed directory in
  the checkout (git-ignored).  The directory is part of no cache key, but
  a path built from a temporary name, PID or time would never be found
  again by the next run.

Every compiled program is cached, however quick its compile: the default
one-second floor would skip the small Pallas kernels.
"""
from __future__ import annotations

import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
