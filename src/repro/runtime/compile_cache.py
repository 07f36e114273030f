"""JAX's persistent compilation cache, placed from outside or at a fixed path.

A paper-width ZO step takes tens of seconds to compile.  Every entry point
that compiles it (``launch/train.py``, ``launch/serve_pde.py``,
``chip_smoke.py``) calls ``enable_compile_cache`` first, so a later process
reuses the compiled programs.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory: JAX reads the
variable itself and this module sets nothing.  Otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored).  The path is fixed, never derived
from a temp name, a pid or the time: the directory is part of what a cache
hit needs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
