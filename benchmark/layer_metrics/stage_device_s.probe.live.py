"""Device self seconds of the window's operations under the program's
``ptt.probe`` stage scope (``benchmark/lib/program_spans.py``): the
explorer's probe inside a temporal-property check, beside the sweep's
sorts."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "probe")
