"""Process-level device set-up shared by every JAX entry point."""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


# JAX's defaults keep an executable only if its compile took a second,
# so a fresh process compiled the sub-second ones again, for ever.  -1
# is JAX's own "no size limit, and let nothing override it".
_KEEP_EVERY_PROGRAM = (
    ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ("jax_persistent_cache_min_entry_size_bytes", -1),
)


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the one cache
    directory, make it keep every executable, and return the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside wins: JAX reads it
    itself, so no directory is set in code.  Otherwise the cache lives
    at ``<checkout>/.jax_cache``, resolved from this file — the same
    path from any working directory, so entries written by one run are
    found by the next.  Either way both of JAX's write thresholds are
    taken away, so a second process compiles nothing it has met; a
    threshold set from outside (``JAX_PERSISTENT_CACHE_MIN_*``) wins as
    the directory does.  Every entry point that runs JAX (``cli.main``,
    ``bench.py``, the ``scripts/``, ``chip_smoke.py``, the test harness)
    calls this before its first compile.
    """
    for option, value in _KEEP_EVERY_PROGRAM:
        if option.upper() not in os.environ:
            jax.config.update(option, value)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
