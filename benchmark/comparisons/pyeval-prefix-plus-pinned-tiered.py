"""Comparison ``pyeval-prefix-plus-pinned-tiered``.

Every check of ``pyeval-prefix-plus-pinned`` (called, not copied: exit
code, distinct states, diameter and every level size against the
reference, which keeps every state in a Python set and knows no budget)
and, from every check's standard output, that the check was held to the
device-memory budget the configuration states (``budget`` in the
configuration's file), exactly:

- the tiered line is there (``benchmark/lib/spill_bytes.py``), once;
- its budget and its three ceilings are the configuration's;
- ``budget overridden: no``;
- the hot tier's peak is at most the configuration's ``hot_keys_max``
  (the table ceiling's keys) and its count is the reference's;
- keys were evicted, cold lookups were made and rows were spilled: a
  check that kept the whole set on the device is another deployment.

A traced run's checks carry the engine's ``result`` stats, and there
``spill_degraded`` has to be false.
"""

from __future__ import annotations

from benchmark.lib import plug, spill_bytes
from benchmark.lib.reference import chk


def compare(config, traffic, answers, seed):
    base = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    checks = base.compare(config, traffic, answers, seed)
    prefix, stored = base.wanted_sizes(config, traffic)
    states, want = sum(prefix + stored), config["budget"]
    lines = [spill_bytes.parse_tiered_line(a["text"]) for a in answers]
    for m in spill_bytes.TIERED_LINE.finditer(
            "\n".join(a["text"] for a in answers)):
        print(f"[benchmark] {m.group(0)}", flush=True)

    def wrong(pred):
        # a check with no line is counted once, as missing
        return sum(1 for ln in lines if ln is not None and pred(ln))

    checks.append(chk("tiered_line_missing",
                      sum(1 for ln in lines if ln is None), 0))
    checks.append(chk(
        f"budget_or_ceilings_differ_from_{want['bytes']}",
        wrong(lambda ln: [ln["budget"], ln["table"], ln["rows"], ln["logs"]]
              != [want["bytes"], want["table_slots"], want["rows"],
                  want["logs"]]), 0))
    checks.append(chk("budget_overridden", wrong(lambda ln: ln["overridden"]),
                      0))
    checks.append(chk(
        f"hot_tier_peak_over_{want['hot_keys_max']}_of_{states}",
        wrong(lambda ln: ln["hot_peak"] > want["hot_keys_max"]
              or ln["states"] != states), 0))
    checks.append(chk("nothing_evicted",
                      wrong(lambda ln: ln["keys_evicted"] <= 0), 0))
    checks.append(chk("no_cold_lookup", wrong(lambda ln: ln["lookups"] <= 0),
                      0))
    checks.append(chk("no_row_spilled",
                      wrong(lambda ln: ln["rows_spilled"] <= 0), 0))
    checks.append(chk(
        "spill_degraded",
        sum(1 for a in answers if a.get("stats", {}).get("spill_degraded")),
        0))
    return checks
