"""Device self seconds of the window's operations under the liveness
engine's ``ptt.sweep_join`` stage scope
(``benchmark/lib/program_spans.py``): per sweep chunk the merged sort of
the WHOLE key -> gid table with the chunk's successor keys, and the
sort back to query order."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    # a commit whose sweep has no scope traces no second under one
    return program_spans.stage_seconds(ctx, "sweep_join") or None
