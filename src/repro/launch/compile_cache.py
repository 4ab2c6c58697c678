"""Persistent XLA compilation cache for the entry points.

Every entry point (``chip_smoke.py`` and the ``python -m repro.launch.*``
mains) calls :func:`enable_compile_cache` once before it compiles anything.
Library import never does, so tests and embedders keep JAX's defaults.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other path is
  set in code.
* otherwise: ``.jax_cache/`` at the root of the checkout (git-ignored).  The
  path is fixed because it is part of the cache key: a directory that moves
  between runs never hits.

Executables are keyed with their metadata (op names, source lines): JAX's
default key strips it, so an executable loaded from the cache would carry
the named scopes of whatever source stored it, and a program that differs
only in its scopes would load another's.  ``CompiledModel.run_hlo`` reads
those scopes, and refuses a cache keyed without them.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache, keyed with the programs'
    metadata; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
