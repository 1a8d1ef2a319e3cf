"""Where JAX's persistent compilation cache lives for this repo's entry
points (``chip_smoke.py``, ``python -m repro.experiments``).

Called explicitly by those entry points, never at import: tests and
library callers keep JAX's own default.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: fixed, because the path is part of the cache key, so
# a directory that moves from run to run never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself, and nothing here overrides it); otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
