"""JAX's persistent compilation cache, as the entry points turn it on.

Only entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/gnn_streaming.py``) call :func:`enable_compile_cache`; importing
any ``repro`` module sets no cache, so the tests never write to one.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_NAME = ".jax_cache"


def enable_compile_cache(repo_root) -> str:
    """Point JAX's persistent compilation cache at its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here. Otherwise the cache lives at the fixed
    ``<repo_root>/.jax_cache`` (never a temp, pid or time-based path), so a
    later run finds what an earlier one compiled. Returns the directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(repo_root).resolve() / CACHE_DIR_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
