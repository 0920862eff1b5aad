"""Where JAX keeps its persistent compilation cache.

Called once at the top of every entry point (the `repro.launch` drivers
and `chip_smoke.py`).  The cache directory is part of what a later run
must find, so it never depends on a temp dir, a pid or the time:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    is set here — whoever placed the cache owns it;
  * unset: one fixed path inside the checkout, ``<repo>/.jax_cache``
    (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
