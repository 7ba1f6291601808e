"""Where the entry points keep JAX's persistent compilation cache.

The launchers, ``benchmarks/run.py`` and ``chip_smoke.py`` call
``enable()`` at start-up.  Importing ``repro`` does not, so library users
and the test suite compile exactly as JAX's own settings say.
"""

from __future__ import annotations

import os

import jax

# src/repro/utils/compile_cache.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing is set here.  Otherwise the cache lives in
    ``.jax_cache`` at the root of the checkout.  The path is fixed, never a
    temporary name, because a cache that moves is never hit again."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
