"""Comparison ``liveness-prefix-plus-pinned``.

A temporal-property check (``cli check -property P -fairness F``) held
to the plain reference's behaviour graph of the same ``.cfg``
(``benchmark/lib/live_reference.py``, on ``benchmark/ref/pyeval.py``'s
``successors``): EVERY check of the window has the exit code and the
verdict the configuration stores for ``P`` under ``F``, the reference's
state count, diameter and level sizes (from the CLI's per-level progress
lines on stderr and from the graph it prints), and the reference's
``<Next>_vars`` edges, goal states and dead ends LEVEL BY LEVEL, as the
CLI prints them after the verdict.  The first ``reference.prefix_levels``
levels are searched by the reference in every run; the rest are held to
``reference.pinned`` (level -> number, one table a column), stored from
ONE whole search of the reference, whose own analysis of that whole
graph is the stored verdict.  Exact: the limit on every number is 0.
"""

from __future__ import annotations

import re

from benchmark.lib import live_reference, tlafmt
from benchmark.lib.reference import chk, fallback_or_recovery

COLUMNS = ("size", "edges", "goal", "dead_ends")
VERDICT = re.compile(
    r"^Temporal property (\w+) \(fairness=(\w+)\): (satisfied|VIOLATED)",
    re.M)
# a check that swept no edge (no fairness assumed) prints n/a for them
GRAPH = re.compile(
    r"^Behaviour graph: (\d+) states in (\d+) levels, (\d+|n/a) "
    r"<Next>_vars edges, (\d+) goal states, (\d+|n/a) dead ends\.", re.M)
LEVEL = re.compile(
    r"^\s*graph level (\d+): (\d+) states, (\d+|n/a) edges, (\d+) goal, "
    r"(\d+|n/a) dead ends", re.M)


def _num(text):
    return None if text == "n/a" else int(text)


def parse_report(text: str):
    """What one check printed: ``{"property", "fairness", "holds",
    "states", "levels", "edges", "goal_states", "dead_ends", "profile"}``
    with ``profile`` the four per-level columns; a part the text lacks
    (a commit that prints no graph, a run cut short) is None."""
    out = dict.fromkeys(
        ("property", "fairness", "holds", "states", "levels", "edges",
         "goal_states", "dead_ends", "profile"))
    m = VERDICT.search(text)
    if m:
        out.update(property=m.group(1), fairness=m.group(2),
                   holds=m.group(3) == "satisfied")
    m = GRAPH.search(text)
    if m:
        out.update(zip(("states", "levels", "edges", "goal_states",
                        "dead_ends"), (_num(x) for x in m.groups())))
    rows = [tuple(_num(x) for x in m.groups()) for m in LEVEL.finditer(text)]
    if rows and [r[0] for r in rows] == list(range(1, len(rows) + 1)):
        out["profile"] = {c: [r[i + 1] for r in rows]
                          for i, c in enumerate(COLUMNS)}
    return out


def wanted_profile(config, traffic):
    """``(prefix, stored)``: the four per-level columns as the reference
    finds them for the first ``prefix_levels`` levels, and as the
    configuration stores them for the levels after."""
    ref = config["reference"]
    n = ref["prefix_levels"]
    c = tlafmt.constants_from_cfg(traffic["cfg_path"])
    found = live_reference.search(c, max_levels=n)
    prefix = live_reference.profile_of(found["levels"])
    stored = {}
    for col in COLUMNS:
        pinned = {int(k): v for k, v in ref["pinned"][col].items()}
        if sorted(pinned) != list(range(n + 1, n + 1 + len(pinned))):
            raise ValueError(
                f"pinned.{col} has to number the levels from {n + 1} on "
                f"without a gap; it has {sorted(pinned)}")
        stored[col] = [pinned[k] for k in sorted(pinned)]
    return prefix, stored


def compare(config, traffic, answers, seed):
    ref = config["reference"]
    prefix, stored = wanted_profile(config, traffic)
    n = len(prefix["size"])
    whole = {c: prefix[c] + stored[c] for c in COLUMNS}
    want = ref["pinned_verdict"]
    reports = [parse_report(a["text"]) for a in answers]
    checks = [chk("checks_compared", len(answers) > 0, True)]
    # a search that ended before the prefix did leaves nothing to store
    checks.append(chk("prefix_levels_searched", n, ref["prefix_levels"]))
    checks.append(chk(
        "wrong_exit_code",
        sum(1 for a in answers if a["rc"] != traffic["exit_code"]), 0))
    checks.append(chk(
        f"verdict_differs_from_{want['property']}_under_{want['fairness']}"
        f"_{'satisfied' if want['holds'] else 'VIOLATED'}",
        sum(1 for r in reports
            if (r["property"], r["fairness"], r["holds"])
            != (want["property"], want["fairness"], want["holds"])), 0))
    totals = {"states": sum(whole["size"]), "levels": len(whole["size"]),
              "edges": sum(whole["edges"]),
              "goal_states": sum(whole["goal"]),
              "dead_ends": sum(whole["dead_ends"])}
    for key, w in totals.items():
        checks.append(chk(
            f"{key}_differ_from_{w}",
            sum(1 for r in reports if r[key] != w), 0))
    # the level sizes twice: as the explorer's progress lines give them
    # (stderr) and as the printed graph does
    got = [a.get("level_sizes") or [] for a in answers]
    checks.append(chk(
        f"progress_level_sizes_differ_from_the_reference's_first_{n}",
        sum(1 for g in got if g[:n] != prefix["size"]), 0))
    checks.append(chk(
        f"progress_level_sizes_differ_from_the_stored_{n + 1}_to_"
        f"{totals['levels']}",
        sum(1 for g in got if g[n:] != stored["size"]), 0))
    profiles = [r["profile"] or dict.fromkeys(COLUMNS, []) for r in reports]
    for col in COLUMNS:
        checks.append(chk(
            f"level_{col}_differ_from_the_reference's_first_{n}",
            sum(1 for p in profiles if p[col][:n] != prefix[col]), 0))
        checks.append(chk(
            f"level_{col}_differ_from_the_stored_{n + 1}_to_"
            f"{totals['levels']}",
            sum(1 for p in profiles if p[col][n:] != stored[col]), 0))
    checks.append(chk("fallback_or_recovery", fallback_or_recovery(answers), 0))
    return checks
