"""Device self seconds of the window's operations under the part scope
``part.claims_bid`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the bid for empty slots: the scatter-min
of lane ids into ``claims``, the read back at the probed slots and the
winners; level 6's one flush of 26,738,688 lanes against 2^27 slots."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "claims_bid")
