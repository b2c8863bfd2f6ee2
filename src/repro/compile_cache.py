"""JAX's persistent compilation cache, switched on by the entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed in-checkout cache directory (listed in .gitignore)
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives at the fixed path ``<repo>/.jax_cache``, so a later process in
    the same checkout finds what an earlier one compiled. Called by entry
    points (``chip_smoke.py``, ``benchmarks/run.py``), never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
