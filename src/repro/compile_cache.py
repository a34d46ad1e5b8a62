"""JAX's persistent compilation cache for the repo's entry points.

A cold call of the sort compiles several Pallas kernels and one whole
program per signature; the persistent cache lets a later process on the
same machine skip that.  The cache key includes the directory, so it
must not move between runs: the directory is ``JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads it itself, and nothing else is set here),
else the fixed ``.jax_cache`` directory at the root of the checkout.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cache_dir() -> str:
    """The directory the cache lives in (see the module docstring)."""
    return os.environ.get(_ENV) or DEFAULT_DIR


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call once at the start of an entry point, before the first compile.
    """
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return cache_dir()
