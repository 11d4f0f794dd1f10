"""Share of the window's device self time under none of the program's
``ptt.`` stage scopes (``benchmark/lib/program_spans.py``) in a
temporal-property check: transfers, the eager slices of the sweep's
edge planes, what the host dispatches eagerly.  Prints the seconds of
every scope (the explorer's and the ``ptt.live_*`` and ``ptt.sweep_*``
ones)."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.unscoped_pct(ctx)
