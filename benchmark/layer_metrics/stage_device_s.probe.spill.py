"""Device self seconds of the window's operations under the program's
``ptt.probe`` stage scope (``benchmark/lib/program_spans.py``): the
probe of a table held to the budget's ceiling, emptied and refilled,
first inside the fused level kernel and then a flush a dispatch.  Summed
over the window's checks."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "probe")
