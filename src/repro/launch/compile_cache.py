"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that run on the chip (``chip_smoke.py``,
``python -m repro.launch.svd_serve``) call :func:`use_compile_cache`
before their first compile, so a second run of the same checkout loads
the executables the first one compiled instead of compiling again.
"""

from __future__ import annotations

import os
import pathlib

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path (never a temporary name, a process id or the time),
    because the directory is part of what a later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
