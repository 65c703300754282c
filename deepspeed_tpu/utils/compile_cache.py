"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``initialize``, the inference engines,
``chip_smoke.py``): if ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set in code; otherwise the cache goes to
``<checkout>/.jax_cache``. The path is part of the cache key, so it is fixed
and derived from the package's own location — never a temp name, pid or
time, which would never hit.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compile cache; return the directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
