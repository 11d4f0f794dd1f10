"""Device self seconds of the window's operations under the liveness
engine's ``ptt.sweep_prop`` stage scope
(``benchmark/lib/program_spans.py``): the doubling-shift passes that
carry a table entry's gid to the equal-key queries after it, at the
merged width."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    # a commit whose sweep has no scope traces no second under one
    return program_spans.stage_seconds(ctx, "sweep_prop") or None
