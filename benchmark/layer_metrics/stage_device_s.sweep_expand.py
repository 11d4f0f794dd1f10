"""Device self seconds of the window's operations under the liveness
engine's ``ptt.sweep_expand`` stage scope
(``benchmark/lib/program_spans.py``): a sweep chunk's rows unpacked,
their successors generated, packed and keyed."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    # a commit whose sweep has no scope traces no second under one
    return program_spans.stage_seconds(ctx, "sweep_expand") or None
