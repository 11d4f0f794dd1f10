"""Device self seconds of the window's operations under the program's
``ptt.probe`` stage scope (``benchmark/lib/program_spans.py``): the
level kernel's probe of a table that grows from 2^17 to 2^25 slots
inside the check, at 4,096 states a sub-batch."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "probe")
