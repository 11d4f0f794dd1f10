"""Gigabytes of the edge planes a check fetched from the device
(``sweep_d2h_bytes``: the kept counts and, sliced to a group's longest
kept prefix, the lane and destination planes).  Median over the window's
checks; None on a commit without the counter."""

from benchmark.lib import sweep_bytes


def read(ctx, params):
    return sweep_bytes.median_over_checks(
        ctx, lambda st: st["sweep_d2h_bytes"] / 1e9
        if st.get("sweep_d2h_bytes") else None)
