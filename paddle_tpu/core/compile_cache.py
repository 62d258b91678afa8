"""Where XLA's persistent compilation cache lives — decided in one place.

Every entry point that compiles on the chip (chip_smoke.py, bench.py, the
tools/ that run there) and tests/conftest.py call
:func:`enable_compile_cache` once, before the first compile.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is
  touched, so the cache can be placed from outside the program.
- not set: ``<checkout>/.jax_cache`` (gitignored).  A fixed path — the
  directory is part of what a warm run must find again, so no temp
  names, pids or timestamps.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

# this file lives at <checkout>/paddle_tpu/core/compile_cache.py
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
