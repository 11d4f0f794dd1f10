"""Comparison ``pyeval-prefix-plus-pinned``.

``pyeval-full-bfs`` for a binding whose whole search by the reference
does not fit beside the run: every check of the window against the
reference's own breadth-first search of the same ``.cfg`` for the first
``reference.prefix_levels`` levels, and against the native checker's
sizes stored in the configuration (``reference.pinned_level_sizes``,
level -> size, as ``compaction-scaled`` stores its level 6) for every
level after them.  Held exactly, in every run: exit code 0, the distinct
states (the prefix's and the stored sizes' sum), the diameter (the last
stored level) and every level's size, from the progress lines the CLI
prints for each level.
"""

from __future__ import annotations

from benchmark.lib import tlafmt
from benchmark.lib.reference import bfs_levels, chk, fallback_or_recovery


def wanted_sizes(config, traffic):
    """``(the reference's own prefix, the stored sizes after it)``."""
    ref = config["reference"]
    n = ref["prefix_levels"]
    pinned = {int(k): v for k, v in ref["pinned_level_sizes"].items()}
    if sorted(pinned) != list(range(n + 1, n + 1 + len(pinned))):
        raise ValueError(
            f"pinned_level_sizes has to number the levels from {n + 1} on "
            f"without a gap; it has {sorted(pinned)}")
    c = tlafmt.constants_from_cfg(traffic["cfg_path"])
    sizes, _seen = bfs_levels(c, max_levels=n)
    return sizes, [pinned[k] for k in sorted(pinned)]


def compare(config, traffic, answers, seed):
    prefix, stored = wanted_sizes(config, traffic)
    n, sizes = len(prefix), prefix + stored
    checks = [chk("checks_compared", len(answers) > 0, True)]
    # a search that ended before the prefix did leaves nothing to store
    checks.append(chk("prefix_levels_searched", n,
                      config["reference"]["prefix_levels"]))
    bad_rc = sum(1 for a in answers if a["rc"] != 0)
    counts = [tlafmt.parse_counts(a["text"]) for a in answers]
    got = [a.get("level_sizes") or [] for a in answers]
    checks.append(chk("wrong_exit_code", bad_rc, 0))
    checks.append(chk(
        f"distinct_states_differ_from_{sum(sizes)}",
        sum(1 for x in counts if x is None or x[0] != sum(sizes)), 0))
    checks.append(chk(
        f"diameter_differs_from_{len(sizes)}",
        sum(1 for x in counts if x is None or x[1] != len(sizes)), 0))
    checks.append(chk(
        f"level_sizes_differ_from_the_reference's_first_{n}",
        sum(1 for g in got if g[:n] != prefix), 0))
    checks.append(chk(
        f"level_sizes_differ_from_the_stored_{n + 1}_to_{len(sizes)}",
        sum(1 for g in got if g[n:] != stored), 0))
    checks.append(chk("fallback_or_recovery", fallback_or_recovery(answers), 0))
    return checks
