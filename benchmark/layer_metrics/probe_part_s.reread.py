"""Device self seconds of the window's operations under the part scope
``part.reread`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the round's second read of the table,
after the writes: same-key losers resolve against the slot just written;
level 6's one flush of 26,738,688 lanes against 2^27 slots."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "reread")
