"""Where JAX keeps its persistent compilation cache for this program.

A run on a chip starts from no compiled code unless an earlier process left
its programs in a cache that this one finds again, and JAX keys the cache on
its directory.  So the directory is either the one the deployment names in
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or a fixed path inside
the checkout, the same from any working directory.  Entry points call
:func:`use_compile_cache` when they start; importing this module changes
nothing.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is the cache and
    nothing is set in code; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
