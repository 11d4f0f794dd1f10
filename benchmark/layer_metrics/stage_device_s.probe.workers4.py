"""Device self seconds of the window's operations under the program's
``ptt.probe`` stage scope, mean of the device planes
(``benchmark/lib/program_spans.py``): each shard's own table, probed at
a quarter of the mesh's lanes."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "probe")
