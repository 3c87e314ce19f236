"""Where JAX keeps its persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache goes to ``<repo root>/.jax_cache``:
a fixed path (the path is part of the cache key, so a directory derived
from temp files, pids or times would never hit), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout this module lives in (src/repro/launch/..)
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
