"""Device self seconds of the window's operations under the part scope
``part.claims_fill`` of the program's ``ptt.probe`` stage
(``benchmark/lib/probe_parts.py``): the refill of ``claims``:
``jnp.full((cap + 1,), _NO_LANE)``, a pass over a table-sized buffer every
round; level 6's one flush of 26,738,688 lanes against 2^27 slots."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.part_seconds(ctx, "probe", "claims_fill")
