"""JAX's persistent compilation cache, switched on by the entry points.

A cold chip run compiles every step program (32-layer prefill and decode, the
kernels); the persistent cache lets a later process on the same machine read
them back.  The cache directory is part of each entry's key, so it is a fixed
path, never one built from a temp name, a pid or the time.

Call :func:`enable_compile_cache` from an entry point before its first
compile.  Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; return the directory it uses.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set here.  Otherwise the cache goes to
    ``.jax_cache/`` at the repository root.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
