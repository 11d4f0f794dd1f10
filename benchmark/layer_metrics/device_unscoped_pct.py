"""Share of the window's device self time under none of the program's
``ptt.`` stage scopes (``benchmark/lib/program_spans.py``); prints the
seconds of every scope."""

from benchmark.lib import program_spans


def read(ctx, params):
    return program_spans.unscoped_pct(ctx)
