"""Device self seconds of the window's operations under the part scope
``part.gather`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the round's first read of the table: a
gather a key column at the probed slots, the empty-slot test and the key
comparison; the level kernel's probe of a table that grows from 2^17 to 2^25
slots inside the check, at 4,096 states a sub-batch."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "gather")
