"""JAX's persistent compilation cache for this repository's entry points.

Entry points (`chip_smoke.py`, `repro.launch.serve`) call
`use_persistent_cache()` once, before they compile anything; nothing
calls it at import. The cache directory is part of what a cached entry is
found under, so it must not move between runs: it is either the one the
environment names or one fixed directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed cache directory inside the checkout (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX already reads it and nothing is
    set here. Otherwise the cache goes to `DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
