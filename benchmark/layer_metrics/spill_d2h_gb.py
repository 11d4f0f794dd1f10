"""Gigabytes a check's tiered store fetched from the device
(``spill_d2h_padded_bytes``: the sieved keys and lanes of every flush,
the evicted runs, the aged rows and logs, each sliced on the device to a
bucketed length; ``spill_d2h_bytes`` is what the host kept of them, and
the printed ratio says what the buckets cost).  Median over the window's
checks; None on a commit without the counter."""

from benchmark.lib import program_spans, sweep_bytes


def read(ctx, params):
    ratios = [
        a["stats"]["spill_d2h_padded_bytes"] / a["stats"]["spill_d2h_bytes"]
        for a in ctx["out"]["answers"]
        if a.get("stats", {}).get("spill_d2h_bytes")]
    if ratios:
        program_spans.say(
            "spill fetches, bytes over the link a byte kept, by check: "
            + ", ".join(f"{r:.4f}" for r in ratios))
    return sweep_bytes.median_over_checks(
        ctx, lambda st: st["spill_d2h_padded_bytes"] / 1e9
        if st.get("spill_d2h_padded_bytes") else None)
