"""JAX's persistent compilation cache, at a path that can be set from outside.

Call :func:`enable_compile_cache` from an entry point's ``main()``, never at
import. ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and is
left alone. Otherwise the cache lives at ``.jax_cache/`` in the root of the
checkout: a fixed path, because the path is part of what a later process
must find again (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
