"""Where the drivers keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache, fixed by this file's place in the checkout and not
# by the working directory, so every later run of the same checkout finds
# the executables an earlier one compiled
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``CHECKOUT_CACHE_DIR``.
    Entry points call this at start-up; library code and tests do not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
