"""Where JAX keeps its persistent compilation cache.

The entry points call :func:`use_compile_cache` first thing in ``main``.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache goes to a fixed directory inside the
checkout, so a second run finds the programs the first one compiled; a
directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in .gitignore).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Point the persistent compilation cache at its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
