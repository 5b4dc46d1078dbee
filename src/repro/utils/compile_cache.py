"""JAX's persistent compilation cache, at one place per checkout.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache there
and nothing here overrides it. Otherwise the cache goes to ``.jax_cache``
at the root of the checkout: a fixed path, so that a later run finds what
an earlier one compiled (a directory named after a pid, a temp dir or the
time would never be found again). Entry points call :func:`enable_compile_cache` once, before
their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
