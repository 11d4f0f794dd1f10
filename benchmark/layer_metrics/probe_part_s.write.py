"""Device self seconds of the window's operations under the part scope
``part.write`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the winners' keys written: one scatter a
key column into the table; level 6's one flush of 26,738,688 lanes against
2^27 slots."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "write")
