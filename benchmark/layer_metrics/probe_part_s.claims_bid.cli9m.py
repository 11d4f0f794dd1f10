"""Device self seconds of the window's operations under the part scope
``part.claims_bid`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the bid for empty slots: the scatter-min
of lane ids into ``claims``, the read back at the probed slots and the
winners; the level kernel's probe of a table that grows from 2^17 to 2^25
slots inside the check, at 4,096 states a sub-batch."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "claims_bid")
