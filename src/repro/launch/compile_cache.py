"""Where the entry points keep JAX's persistent compilation cache.

Every entry point (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``) calls ``place_compile_cache()`` before it compiles
anything.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout: a fixed path, because the path is part of the
cache key, so a directory that moves from run to run never hits.

The key also holds each Pallas kernel's Mosaic module, which keeps the
source files of the Python frames that traced it.  Source files are
therefore named relative to the checkout, so that a program compiled in
one checkout is found again from another.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"


def place_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{REPO_ROOT}{os.sep}"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
