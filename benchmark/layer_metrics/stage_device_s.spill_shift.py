"""Device self seconds of the window's operations under the program's
``ptt.spill_shift`` stage scope (``benchmark/lib/program_spans.py``):
the slide of the row window and of the two log windows after an aged range was spilled.
Summed over the window's checks."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "spill_shift")
