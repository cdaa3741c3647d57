"""JAX's persistent compilation cache, kept at one fixed place.

JAX keys a cached executable on, among other things, the cache directory,
so a directory that moves between runs never hits.  The entry points
(`chip_smoke.py`, `examples/nekbone_solve.py`, `benchmarks/`) call
:func:`enable` once before they compile anything.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "ENV", "enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at ``CACHE_DIR``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
    itself and this sets nothing.  Returns the directory in use.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
