"""Of the window's device self seconds under the program's ``ptt.probe`` stage,
the share under none of its part scopes (``benchmark/lib/probe_parts.py``):
the slot arithmetic, the pending count, the loop's carry, the flush's mask
and sums, and what the compiler inserts with no ``op_name``; the level
kernel's probe of a table that grows from 2^17 to 2^25 slots inside the
check, at 4,096 states a sub-batch. Prints the seconds by part and width,
and the longest such operations."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.unparted_pct(ctx, "probe")
