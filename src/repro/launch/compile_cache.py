"""Where JAX keeps its persistent compilation cache for the entry points.

A compiled program is found again only under the same cache path, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it
itself), otherwise ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Call from an entry point's ``main`` before the first compile, never at
    import time.  Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
