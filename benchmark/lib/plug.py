"""How the harness finds what belongs to one cell: by name, as a file.

``benchmark/<kind>/<name>.py`` for ``kind`` in ``drivers`` (a traffic
file's ``driver``), ``comparisons`` (a configuration's
``reference.comparison``), ``controls`` (its ``control.kind``) and
``layer_metrics`` (a per-layer metric of ``BENCHMARK.json``).  There is
no table of names anywhere: a later PR adds a file.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_PREFIX = "bench:"


def path_of(kind: str, name: str, ext: str = ".py") -> str:
    return os.path.join(BENCH_DIR, kind, name + ext)


def load_file(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py`` (names may hold ``-``
    and ``.``, so it is loaded by path)."""
    path = path_of(kind, name)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no {kind[:-1]} named {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_attr(path: str):
    """``"package.module:attr"``: an entry point of the program, named as
    data in a configuration file."""
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's own trace (no cost to speak of when
    no trace runs): the idle-gap attribution reads these."""
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
