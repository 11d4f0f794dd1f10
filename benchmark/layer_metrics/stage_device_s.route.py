"""Device self seconds of the window's operations under the sharded
engine's ``ptt.route`` stage scope, mean of the device planes
(``benchmark/lib/program_spans.py``): owner bucketing, both
``all_to_all`` exchanges and the flag gather, everything that exists
only because keys change shards."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "route")
