"""Persistent XLA compilation cache placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``repro.launch.*``)
call ``enable()`` once at start; nothing calls it at package import, so test
runs stay cache-free.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (src/repro/launch/ -> three levels up)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself; nothing else is set). Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: its path is part of what a later run must
    find, so it is never a temporary, per-process or per-run name.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
