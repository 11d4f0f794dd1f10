"""The least bytes the single-chip engine's table growth has to move,
reckoned from the program's growth counters and the configuration's
widths.

The program counts and the benchmark reckons: a doubling of
``engine/device_bfs.py``'s fingerprint table (``_grow_visited``) reads
every slot of the OLD table once and writes the new one, of twice the
slots, once (its empty fill; the keys' scatter into it is not counted
again), ``K`` key columns of 32-bit words each.  ``grow_rehash_slots``
of a check's ``result`` stats is the sum of the old tables' slots over
the check's doublings, so a check's rehashes move at least

    grow_rehash_slots * K * 4 * (1 + 2)

bytes.  ``K`` is the configuration's ``shapes.key_columns``.  The
rehash is a probe of the new table, bound by gather latency as
``ptt.probe`` is and not by bandwidth: the share of the peak says how
far.
"""

from __future__ import annotations

WORD_BYTES = 4


def rehash_bytes(check: dict, key_columns: int):
    """Least bytes one check's table doublings moved, from its
    ``result`` stats; None where the program has no growth counters (an
    older commit)."""
    slots = check.get("grow_rehash_slots")
    if slots is None:
        return None
    return slots * key_columns * WORD_BYTES * (1 + 2)


def window_bytes(ctx):
    """The sum over the window's checks that carry the counter; None
    where none does."""
    k = ctx["config"]["shapes"]["key_columns"]
    found = [rehash_bytes(a.get("stats", {}), k)
             for a in ctx["out"]["answers"]]
    found = [b for b in found if b is not None]
    return sum(found) if found else None


def share_pct(moved_bytes: float, device_s: float,
              hbm_bytes_per_s: float) -> float:
    """``moved_bytes`` over ``device_s`` device seconds, as a percentage
    of the memory's peak."""
    return 100.0 * moved_bytes / device_s / hbm_bytes_per_s
