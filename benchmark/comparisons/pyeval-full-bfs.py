"""Comparison ``pyeval-full-bfs``.

Every check of the window against the reference's own breadth-first
search of the same ``.cfg``: exit code 0, distinct states, diameter
and every level's size (from the progress lines the CLI prints for each
level), in every run.
"""

from __future__ import annotations

from benchmark.lib import tlafmt
from benchmark.lib.reference import bfs_levels, chk, fallback_or_recovery


def compare(config, traffic, answers, seed):
    c = tlafmt.constants_from_cfg(traffic["cfg_path"])
    sizes, seen = bfs_levels(c)
    checks = [chk("checks_compared", len(answers) > 0, True)]
    bad_rc = sum(1 for a in answers if a["rc"] != 0)
    counts = [tlafmt.parse_counts(a["text"]) for a in answers]
    checks.append(chk("wrong_exit_code", bad_rc, 0))
    checks.append(chk(
        f"distinct_states_differ_from_{len(seen)}",
        sum(1 for x in counts if x is None or x[0] != len(seen)), 0))
    checks.append(chk(
        f"diameter_differs_from_{len(sizes)}",
        sum(1 for x in counts if x is None or x[1] != len(sizes)), 0))
    checks.append(chk(
        f"level_sizes_differ_from_the_reference's_{len(sizes)}",
        sum(1 for a in answers if a.get("level_sizes") != sizes), 0))
    checks.append(chk("fallback_or_recovery", fallback_or_recovery(answers), 0))
    return checks
