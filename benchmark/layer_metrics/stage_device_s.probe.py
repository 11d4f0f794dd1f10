"""Device self seconds of the window's operations under the program's
``ptt.probe`` stage scope (``benchmark/lib/program_spans.py``)."""

from benchmark.lib import program_spans


def read(ctx, params):
    return program_spans.stage_seconds(ctx, "probe")
