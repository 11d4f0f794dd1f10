"""Of the window's device self seconds under the program's ``ptt.probe`` stage,
the share under none of its part scopes (``benchmark/lib/probe_parts.py``):
the slot arithmetic, the pending count, the loop's carry, the flush's mask
and sums, and what the compiler inserts with no ``op_name``; level 6's one
flush of 26,738,688 lanes against 2^27 slots. Prints the seconds by part and
width, and the longest such operations."""

from benchmark.lib import probe_parts


def read(ctx, params):
    return probe_parts.unparted_pct(ctx, "probe")
