"""Where the persistent XLA compilation cache lives: one rule, one helper.

The directory is part of what a deployment decides, so it is placed from
outside: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this code sets no directory. Where it is not, the cache is
``<checkout>/.cache/jax`` - one fixed directory shared by the tests, the
fleet workers, the scripts, ``chip_smoke.py`` and ``chipbench``, never
derived from a temporary name, a pid or the time (the path is part of the
cache key's lookup: a directory that moves never hits).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".cache", "jax")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use. Call
    before the first compile; safe to call again."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
