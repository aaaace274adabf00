"""Where JAX keeps compiled programs between processes.

Every entry point that compiles (``chip_smoke.py``, ``serving/httpd.py``
``main``, ``benchmarks/run.py``, ``tests/conftest.py``) calls
``enable_compile_cache()`` once, before its first compile.
"""
from __future__ import annotations

import os

# One fixed directory inside the checkout (git-ignored): never a
# temporary name, a pid or a time.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and this sets nothing — whoever placed the variable owns
    the location; otherwise the cache lives at ``CACHE_DIR``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
