"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run`` call
:func:`enable` before they compile anything; importing ``repro`` never
does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
no other directory is set here.  Otherwise the cache lives at a fixed
``<checkout>/.jax_cache``, which git ignores, so a run reads and writes
nothing outside its checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
