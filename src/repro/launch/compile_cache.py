"""JAX's persistent compilation cache for this repo's entry points.

Called by ``chip_smoke.py`` and the benchmark ``main()`` functions, never
at library import: importing ``repro`` leaves JAX's configuration alone.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout-local cache directory (git-ignored).  A fixed path: the
#: directory is part of each entry's key, so a moving path never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; return the directory in use.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
