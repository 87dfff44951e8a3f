"""JAX persistent compilation cache location.

A full-width model compiles for minutes; the persistent cache lets the next
process on the same machine load those programs instead.  The cache key
includes the directory, so it lives at one fixed path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: used when ``JAX_COMPILATION_CACHE_DIR`` is unset (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    changes nothing; otherwise the cache goes to ``DEFAULT_CACHE_DIR``.
    Call before the first compile."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
