"""Where this checkout keeps JAX's persistent compilation cache.

The cache's path is part of its key, so it must not move between runs: it is
`JAX_COMPILATION_CACHE_DIR` where the environment sets that, and otherwise
`.jax_cache` beside the `ray_tpu` package (git-ignored). Never a temp name, a pid
or a time. Every process that compiles calls `enable_compile_cache()`; the
processes it starts inherit the choice through the environment.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point this process, and every child it starts, at the cache. Call it before
    jax is imported: the environment variable is what jax reads at import."""
    path = compile_cache_dir()
    os.environ[_ENV] = path
    return path
