"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable_compile_cache` at start-up; importing
this module turns nothing on, so library users and the tests compile without
a persistent cache.

The directory comes from ``JAX_COMPILATION_CACHE_DIR`` when that is set.
Otherwise it is the fixed ``.jax_cache`` directory at the root of the
checkout (listed in ``.gitignore``): the cache key includes nothing that moves
between runs, so a fixed path is what lets a later run find its entries.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its directory."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CHECKOUT_CACHE))
