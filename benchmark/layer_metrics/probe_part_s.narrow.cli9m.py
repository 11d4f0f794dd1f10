"""Device self seconds of the window's operations under the part scope
``part.narrow`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the ladder between rounds: the order-
preserving compaction of the pending lanes into the next buffer, the slices
to its width and the scatter of a step's winner flags back to lane order;
the level kernel's probe of a table that grows from 2^17 to 2^25 slots
inside the check, at 4,096 states a sub-batch."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "narrow")
