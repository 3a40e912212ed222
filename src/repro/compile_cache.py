"""Where JAX keeps its persistent compilation cache.

Call :func:`enable_compile_cache` from a program's ``main`` — never at
import — before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's fixed cache directory (listed in .gitignore); a path
#: that moves between runs never hits, since it is part of the key
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    it itself and no other is set here.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
