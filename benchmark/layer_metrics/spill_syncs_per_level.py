"""Host syncs a level that the cold tier adds: ``spill_syncs`` (one a
flush once keys have been evicted: the host waits for the sieve's count
before it can look the flush's survivors up) over the levels of the
check, as its progress lines number them.  Median over the window's
checks; None on a commit without the counter."""

import statistics


def read(ctx, params):
    vals = [
        a["stats"]["spill_syncs"] / len(a["level_sizes"])
        for a in ctx["out"]["answers"]
        if a.get("stats", {}).get("spill_syncs") is not None
        and a.get("level_sizes")]
    return statistics.median(vals) if vals else None
