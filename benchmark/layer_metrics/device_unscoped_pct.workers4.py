"""Share of the window's device self time, mean of the device planes,
under none of the sharded engine's ``ptt.`` stage scopes
(``benchmark/lib/program_spans.py``): buffer fills, the eager
concatenates of store growth, transfers.  Prints the seconds of every
scope (``ptt.route`` among them)."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.unscoped_pct(ctx)
