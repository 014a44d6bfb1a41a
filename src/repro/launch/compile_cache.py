"""JAX's persistent compilation cache, placed from outside the program.

Entry points call ``enable_compile_cache()`` from their ``main()``; nothing
calls it at import.  The cache's path is part of its key, so it never comes
from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed in-checkout cache directory (listed in .gitignore)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives in ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
