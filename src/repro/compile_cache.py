"""Persistent XLA compilation cache placement for entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call ``enable()`` before their first compile; the library never does, so
importing ``repro`` changes no global JAX state beyond x64.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else
  is set and that directory is returned.
* unset: the cache goes to the fixed ``<checkout>/.jax_cache``.  The
  path is part of what a later run must match to hit the cache, so it
  never carries a temporary name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
