"""The bytes a shard sends through the sharded engine's key exchange,
reckoned from the program's counters and the configuration's widths.

The program counts and the benchmark reckons: a round of
``engine/sharded_device.py`` sends ``K`` key planes out through one
``all_to_all`` (``_route_keys``) and, at the flush that follows, gets one
flag plane back through another (``_flags_back``); every plane is
``route_capacity_lanes`` 32-bit lanes a shard, full or not, because an
exchange is compiled to its capacity.  So a shard sends

    (K + 1) * 4 * sum over capacities (capacity * rounds at it)

bytes in a check; ``route_rounds_by_capacity`` has more than one entry
only where a route overflow grew the capacity mid-run.  ``K`` is the
configuration's ``shapes.key_columns``.  Of every plane the block a
shard addresses to itself (one of ``chips``) never leaves the chip:
``crossing`` takes it out, for the share of the interconnect's peak.
"""

from __future__ import annotations

from benchmark.lib import tlafmt

LANE_BYTES = 4


def sent_bytes(check: dict, key_columns: int):
    """Bytes one shard sent through both exchanges in one check, from
    its ``result`` stats; None where the program has no route counters
    (an older commit, or a run on one shard)."""
    by_cap = check.get("route_rounds_by_capacity")
    if not by_cap:
        return None
    lanes = sum(int(cap) * n for cap, n in by_cap.items())
    return (key_columns + 1) * LANE_BYTES * lanes


def crossing(nbytes: float, chips: int) -> float:
    """The part of a shard's exchanged bytes that crosses the
    interconnect: all but the one block of ``chips`` it keeps."""
    return nbytes * (chips - 1) / chips


def per_check(ctx):
    """``[(bytes a shard sent, distinct states found)]`` of the window's
    checks that carry route counters and printed a count."""
    k = ctx["config"]["shapes"]["key_columns"]
    out = []
    for ans in ctx["out"]["answers"]:
        b = sent_bytes(ans.get("stats", {}), k)
        counts = tlafmt.parse_counts(ans.get("text", ""))
        if b is not None and counts:
            out.append((b, counts[0]))
    return out
