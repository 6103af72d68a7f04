"""JAX's persistent compile cache, placed from outside the library.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve`` and the ``benchmarks/`` scripts) calls
:func:`enable_compile_cache` at the top of ``main``.  Importing the library
never turns the cache on, so tests stay silent.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing here changes.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of what
    a later run looks up, so a later run in the same checkout compiles less.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
