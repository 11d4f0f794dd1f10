"""Process-level device set-up shared by every JAX entry point."""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the one cache
    directory and return it.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside wins: JAX reads it
    itself, so nothing is set in code.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``, resolved from this file — the same path
    from any working directory, so entries written by one run are found
    by the next.  Every entry point that runs JAX (``cli.main``,
    ``bench.py``, the ``scripts/``, ``chip_smoke.py``, the test harness)
    calls this before its first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
