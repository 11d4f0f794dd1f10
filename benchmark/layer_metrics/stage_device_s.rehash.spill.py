"""Device self seconds of the window's operations under the program's
``ptt.rehash`` stage scope (``benchmark/lib/program_spans.py``): the
doublings up to the budget's ceiling and, after every eviction, the
re-insertion of the survivors into a table of the SAME size.  Summed
over the window's checks."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "rehash")
