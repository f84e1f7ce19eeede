"""Device setup for the launchers: the mesh over the devices present and the
persistent compile cache.

Defined as functions (never module-level state) so importing this module
touches neither jax device state nor jax config.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.core import compat

# <checkout>/artifacts/jax_cache: a fixed path, because the cache directory
# is part of what a persisted entry is found again by
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / "artifacts" / \
    "jax_cache"


def make_local_mesh():
    """Whatever devices exist, as (data, model) with model=1 — what every
    launcher runs on."""
    n = len(jax.devices())
    return compat.make_mesh((n, 1), ("data", "model"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; call before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses
    it and nothing changes; otherwise the cache goes to the checkout's
    ``artifacts/jax_cache``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
