"""Device self seconds of the window's operations under the part scope
``part.reread`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the round's second read of the table,
after the writes: same-key losers resolve against the slot just written; the
level kernel's probe of a table that grows from 2^17 to 2^25 slots inside
the check, at 4,096 states a sub-batch."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "reread")
