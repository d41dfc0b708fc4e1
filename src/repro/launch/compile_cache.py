"""JAX's persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``kbench/run.py``) call
:func:`enable_compile_cache` before their first compile; importing the
library and running the tests never do. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here. Otherwise the cache
goes to one fixed directory inside the checkout: the path is part of the
cache key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os

#: The in-checkout cache directory (listed in ``.gitignore``).
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
