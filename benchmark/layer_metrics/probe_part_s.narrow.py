"""Device self seconds of the window's operations under the part scope
``part.narrow`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the ladder between rounds: the order-
preserving compaction of the pending lanes into the next buffer, the slices
to its width and the scatter of a step's winner flags back to lane order;
level 6's one flush of 26,738,688 lanes against 2^27 slots."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "narrow")
