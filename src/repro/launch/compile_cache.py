"""JAX's persistent compilation cache for the entry points.

A paper-scale fit takes minutes to compile, so the entry points
(``launch/serve_gp.py::main``, ``chip_smoke.py``) keep compiled programs
on disk.  Importing the library turns nothing on; an entry point calls
:func:`enable_compile_cache` once, before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# a fixed path: the cache key includes nothing that moves, so a later run
# in the same checkout finds what an earlier one wrote
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)
