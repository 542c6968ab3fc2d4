"""JAX's persistent compilation cache for the entry points.

A 36-layer serving step takes tens of seconds to compile, and each run
of an entry point starts from a fresh process.  `JAX_COMPILATION_CACHE_DIR`,
when set, names the cache directory and JAX reads it itself; otherwise
the entry points (`launch.serve`, `chip_smoke.py`, `benchmarks/run.py`)
keep the cache at one fixed path inside the checkout, `.jax_cache/`
(git-ignored), so a later run finds what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; -> the directory in use.  Call it
    before the first compilation of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
