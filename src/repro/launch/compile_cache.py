"""The one place JAX's persistent compilation cache is configured.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``
and the shard worker) call ``setup_compile_cache()`` before their first
compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and no other
  directory is set here — the cache lives where the environment says.
* unset: the cache goes to ``.jax_cache/`` at the root of this checkout (a
  fixed, gitignored path: the directory is part of a cache entry's identity,
  so a temp-, pid- or time-derived path would never hit).

Every compile is cached, however short: a cold run of the served path is a
few hundred small programs, and what a warm run saves is their sum.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
