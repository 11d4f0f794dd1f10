"""``unit``: how the single-chip engine builds a program once a process.

A unit is a whole program of ``engine/bodies.py``: a module-level
function that closes over nothing, wrapped ONCE, at import, in
``jax.jit``.  Its identity outlives every checker and its key is its own
argument list (shapes and dtypes of the traced arguments, static ones by
value), so JAX's own caches answer a second check of the same binding
with the executable the first one built.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from pulsar_tlaplus_tpu.obs import spans


def unit(
    scope: Optional[str] = None, static: tuple = (), donate: tuple = ()
):
    """Decorator: ``jax.jit(fn, static_argnames=static,
    donate_argnums=donate)``, the function's own name the program's.

    Everything the body reads is an argument: arrays are traced, the
    ``static`` ones are passed by keyword and hashed by value, so a
    value the body reads cannot be left out of the key.  The body runs
    under the stage scope ``scope`` (entered inside it) and counts its
    runs on the compile meter (``jit_body_traces``: a body runs only on
    a miss).  ``.body`` is the function as written.

    What is returned is JAX's own wrapper, and the one Python frame
    added between it and the body does both jobs: every frame between a
    dispatch site and a traced equation is on that equation's
    traceback, and a first check pays for each (PERF.md §6, PR 33)."""

    def deco(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            spans.compile_meter().count("body_traces")
            if scope is None:
                return fn(*args, **kwargs)
            with spans.stage(scope):
                return fn(*args, **kwargs)

        jitted = jax.jit(
            traced, static_argnames=static, donate_argnums=donate
        )
        jitted.body = fn
        return jitted

    return deco
