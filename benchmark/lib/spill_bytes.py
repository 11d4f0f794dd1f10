"""What a check under a device-memory budget printed and counted, read
and reckoned for the tiered store's cell.

**The tiered line.**  ``cli check ... -hbm-budget B`` prints, after the
verdict, one line on standard output (``docs/memory.md`` has the
grammar; ``TIERED_LINE`` is the benchmark's own reading of it, and
imports nothing of the program):

    Tiered store: budget 134217728 B (table <= 4194304 slots, rows <=
    4194304, logs <= 4194304), hot tier peak 1966080 keys (20.8% of
    9445152), 7 evictions of 8215524 keys, 9012345 cold lookups (1203456
    already visited), 8201235 rows spilled, budget overridden: no.

**The least bytes an eviction moves.**  An eviction
(``store/sieve.py: extract_cold``) reads every slot of the table once,
``K`` key columns and the generation column of 32-bit words each, writes
the holed table and the cleared generations back, and writes the evicted
keys once.  ``spill_evict_slots`` of a check's ``result`` stats is the
table's slots summed over the check's evictions, ``spill_keys_evicted``
the keys that left, so a check's evictions move at least

    4 * (2 * spill_evict_slots * (K + 1) + spill_keys_evicted * K)

bytes.  The compaction and the three-operand sort between the read and
the write move more; the share of the peak says how far they are from a
plain pass.
"""

from __future__ import annotations

import re

WORD_BYTES = 4

TIERED_LINE = re.compile(
    r"^Tiered store: budget (?P<budget>\d+) B \(table <= (?P<table>\d+) "
    r"slots, rows <= (?P<rows>\d+), logs <= (?P<logs>\d+)\), hot tier peak "
    r"(?P<hot_peak>\d+) keys \((?P<hot_pct>\d+\.\d)% of (?P<states>\d+)\), "
    r"(?P<evictions>\d+) evictions of (?P<keys_evicted>\d+) keys, "
    r"(?P<lookups>\d+) cold lookups \((?P<hits>\d+) already visited\), "
    r"(?P<rows_spilled>\d+) rows spilled, budget overridden: "
    r"(?P<overridden>yes|no)\.$", re.M)


def parse_tiered_line(text: str):
    """The numbers of the one tiered line in a check's standard output
    (``overridden`` a bool, ``hot_pct`` a float, the rest ints); None
    where there is no such line, or more than one."""
    found = list(TIERED_LINE.finditer(text))
    if len(found) != 1:
        return None
    got = found[0].groupdict()
    out = {k: int(v) for k, v in got.items()
           if k not in ("hot_pct", "overridden")}
    out["hot_pct"] = float(got["hot_pct"])
    out["overridden"] = got["overridden"] == "yes"
    return out


def evict_bytes(check: dict, key_columns: int):
    """Least bytes one check's evictions moved, from its ``result``
    stats; None where the program has no such counter (an older commit)
    or the check evicted nothing."""
    slots = check.get("spill_evict_slots")
    if not slots:
        return None
    return WORD_BYTES * (
        2 * slots * (key_columns + 1)
        + check.get("spill_keys_evicted", 0) * key_columns)


def window_evict_bytes(ctx):
    """The sum over the window's checks that carry the counter; None
    where none does."""
    k = ctx["config"]["shapes"]["key_columns"]
    found = [evict_bytes(a.get("stats", {}), k)
             for a in ctx["out"]["answers"]]
    found = [b for b in found if b is not None]
    return sum(found) if found else None
