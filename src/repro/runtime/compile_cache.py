"""JAX's persistent compile cache for the repo's entry points.

``chip_smoke.py``, the ``examples/`` scripts and ``benchmarks/run.py`` call
``enable_compile_cache()`` before their first compile.  Nothing calls it on
package import, so the tests run without a cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored).  The path is fixed: it is part of the cache key, so a path
that moved between runs would never hit.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                        os.pardir, os.pardir))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the directory the cache uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
