"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``, ``benchmarks/fleet_scale.py``) call
:func:`enable_compile_cache` before their first compile.  Nothing calls it
at import or in the tests: an entry compiled for the TPU warns when a CPU
process reads it back.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (git-ignored)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it lives in.

    A ``JAX_COMPILATION_CACHE_DIR`` from the environment is JAX's own
    setting and is left alone.  Otherwise the cache goes to
    :data:`CACHE_DIR`, a fixed path, so that the next process finds what
    this one compiled.  Must run before the process's first compile: JAX
    decides once whether a cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
