"""Of the keys the hot table flagged new and the host looked up in the
cold runs (``spill_misses_resolved``), the share that had been visited
and evicted (``spill_miss_hits``): what the sieve sends over the link for
nothing is the rest.  Median over the window's checks; None on a commit
without the counters or a check that looked nothing up."""

from benchmark.lib import sweep_bytes


def read(ctx, params):
    return sweep_bytes.median_over_checks(
        ctx, lambda st: 100.0 * st["spill_miss_hits"]
        / st["spill_misses_resolved"]
        if st.get("spill_misses_resolved") else None)
