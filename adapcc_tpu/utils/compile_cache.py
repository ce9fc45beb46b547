"""JAX's persistent compilation cache, placed from outside or at one fixed
path.

The path is part of the cache key's lookup: a directory that moves between
runs never hits.  So the cache lives where ``JAX_COMPILATION_CACHE_DIR``
says — JAX reads that variable itself, nothing is set in code — and
otherwise at ``<checkout>/.jax_cache`` (git-ignored), never under a
temporary name, a pid or a timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout cache directory (this file is
#: ``<checkout>/adapcc_tpu/utils/compile_cache.py``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile;
    returns the directory in force."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
