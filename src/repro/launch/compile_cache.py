"""Persistent XLA compilation cache for the program's entry points.

``enable()`` is called by the entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``launch/train.py``, ``launch/serve.py``) before
their first compile, never at import time:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
  other directory is set here;
* otherwise the cache goes to ``.jax_cache/`` at the root of this checkout
  (git-ignored). The path is fixed: it is part of what a later run must
  find again, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
