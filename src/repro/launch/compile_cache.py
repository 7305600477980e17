"""JAX's persistent compilation cache, switched on by entry points.

A compile of the paper-scale superstep takes tens of seconds, and a
fresh process pays it again.  The persistent cache keeps compiled
executables on disk, keyed by the program and by the cache path, so a
second run of the same entry point in the same checkout reads them back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: Path) -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already
    and this sets nothing.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, because a path that changes from run to run (a
    temporary name, a pid, the time) never hits.  Call it from an entry
    point's ``main`` before the first compile, never at import.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
