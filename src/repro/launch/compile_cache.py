"""JAX's persistent compilation cache for the entry points.

Called from the ``main`` of every entry point (``chip_smoke.py``,
``launch/quantize``, ``benchmarks.run``), never at import: importing a
module must not change process-wide jax configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo>/.jax_cache`` (listed in .gitignore). The directory is what a
#: later run must find again, so it is fixed: never a temporary name, a pid
#: or a time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: jax reads it
    itself and no other path is set here. Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`. Every program is cached whatever its compile
    time, so a warm run compiles nothing that a cold run already did.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
