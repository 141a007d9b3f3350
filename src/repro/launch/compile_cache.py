"""JAX's persistent compilation cache for the entry points.

Every ``main()`` (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable` before it
compiles anything, so a second process on the same machine reads the
programs the first one wrote instead of compiling them again.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  nothing is set in code.
* otherwise: :data:`DEFAULT_DIR`, one fixed directory inside the checkout
  (git-ignored).  The path is part of what the cache is keyed on, so it is
  never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

#: the checkout's own cache directory (``<repo>/.jax_compile_cache``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_compile_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
