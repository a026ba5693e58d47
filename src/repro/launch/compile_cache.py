"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

A cold run on the chip spends much of its time compiling; JAX's
persistent cache lets a later process with the same programs skip that.
The cache key includes the directory, so the directory never moves: it
is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself), and otherwise ``.jax_cache/`` at the root of the
checkout.  Call ``enable_compile_cache()`` from an entry point's
``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
