"""Where JAX keeps its persistent compilation cache for this checkout.

A cold call on a fresh machine compiles every step program; the
persistent cache lets a later process (or a later call on a machine
that keeps the directory) load them instead.  JAX finds an entry again
only under the same directory, so the location is fixed: never a temp
name, a process id or a timestamp.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — listed in .gitignore.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and
    return the directory.  Call before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and nothing is set here.  Otherwise the cache
    goes to ``.jax_cache`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
