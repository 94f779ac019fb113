"""Where the program keeps what it caches across runs.

Both caches live at fixed paths inside the checkout, resolved from this
file, never from the current directory: JAX's persistent compilation cache
(its directory is part of what makes a later run hit) and the measured
``block_m`` autotune cache (``repro.kernels.common``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: root of the checkout (``src/repro/utils/cache.py`` -> three levels up)
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
#: the compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is not set
COMPILE_CACHE_DIR = CHECKOUT_ROOT / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    Call it once at start-up, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
