"""Device self seconds of the window's operations under the part scope
``part.gather`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the round's first read of the table: a
gather a key column at the probed slots, the empty-slot test and the key
comparison; level 6's one flush of 26,738,688 lanes against 2^27 slots."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "gather")
