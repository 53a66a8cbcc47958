"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``examples/*.py`` call
:func:`enable_compile_cache` before their first compile; importing
``repro`` never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
keeps the cache in that directory on its own and no other is set here.
Otherwise the cache lives at the fixed :data:`CHECKOUT_CACHE_DIR`, so a
later run of the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Persist every compiled program; returns the cache directory.

    The minimum compile time is lowered to zero: a cold serving start
    pays for many sub-second compiles (padding, bucketing and reduction
    steps around the kernels), and each of those is worth keeping.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
