"""Lanes the edge sweep sorted (``sweep_sort_lanes``: two sorts of the
whole key -> gid table with the chunk's queries, every chunk) per
``<Next>_vars`` edge kept (``sweep_edges``): the price of sorting the
whole table a chunk.  Median over the window's checks; None on a commit
without the counters."""

from benchmark.lib import sweep_bytes


def read(ctx, params):
    return sweep_bytes.median_over_checks(
        ctx, lambda st: st["sweep_sort_lanes"] / st["sweep_edges"]
        if st.get("sweep_edges") and st.get("sweep_sort_lanes") else None)
