"""Device self seconds of the window's operations under the program's
``ptt.rehash`` stage scope (``benchmark/lib/program_spans.py``): the
on-device rehash of every table doubling a check crosses."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "rehash")
