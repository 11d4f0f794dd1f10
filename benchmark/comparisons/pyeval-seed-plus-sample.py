"""Comparison ``pyeval-seed-plus-sample``.

The wide run that cannot finish: the program's host seed against
the reference's own search of the same levels; every level that
closed in the window against the native checker's size stored in the
configuration; a sample, drawn from ``seed``, of the states the
device found in those levels, each walked back through the engine's
parent and lane logs and replayed lane by lane through the reference
(a real path of the right depth to a state the seed levels do not
hold, no two samples the same state); and no table failure, memory
recovery or kernel fallback.
"""

from __future__ import annotations

import random

from benchmark.lib.reference import bfs_levels, chk, replay_lanes
from benchmark.ref import pyeval as pe


def compare(config, traffic, answers, seed):
    refcfg = config["reference"]
    c = pe.Constants(**config["constants"])
    (a,) = answers
    n_seed = refcfg["seed_levels"]
    sizes, seen = bfs_levels(c, max_levels=n_seed)
    checks = [chk("seed_level_sizes", list(a["seed_level_sizes"]), sizes)]
    levels = list(a["level_sizes"])
    truth = {int(k): v for k, v in refcfg["ground_truth_levels"].items()}
    # A truncated run's last entry is a level cut short, unless the run
    # stopped on the level's boundary: the result does not say which, so
    # the last level counts as closed only at its stored size (a wrong
    # size then shows as a level that did not close)
    closed = levels
    if a["truncated"] and levels and truth.get(len(levels)) != levels[-1]:
        closed = levels[:-1]
    need = traffic["min_closed_level"]
    checks.append(chk(f"closed_levels_at_least_{need}", len(closed) >= need,
                      True))
    checks.append(chk("closed_seed_levels", closed[:n_seed], sizes))
    # every closed level, and every level the cell needs closed that the
    # run reports at all (so a wrong size is printed beside the right one)
    for lvl in range(n_seed + 1, max(len(closed), min(need, len(levels))) + 1):
        if lvl in truth:
            checks.append(chk(f"level_{lvl}_size", levels[lvl - 1],
                              truth[lvl]))
    checks.append(chk("stop_reason", a["stop_reason"], traffic["stop_reason"]))
    for k in ("fpset_failures", "hbm_recovered"):
        checks.append(chk(k, a["stats"].get(k), 0))
    checks.append(chk("fuse", a["stats"].get("fuse"), "level"))
    # the sample: gids of the closed levels past the seed
    lo, hi = sum(closed[:n_seed]), sum(closed)
    parent, lane = a["parent_log"], a["lane_log"]
    k = min(refcfg["sample"], max(hi - lo, 0))
    checks.append(chk("sample_drawn", k > 0 and len(parent) >= hi, True))
    if not checks[-1]["ok"]:
        return checks
    bounds = [sum(closed[:i]) for i in range(len(closed) + 1)]
    rng = random.Random(seed)
    gids = sorted(rng.sample(range(lo, hi), k))
    bad = {"chain": 0, "depth": 0, "not_a_path": 0, "in_seed_levels": 0}
    reached = set()
    for g in gids:
        level = next(i for i in range(1, len(bounds)) if g < bounds[i])
        chain, cur = [], g
        while cur >= 0 and len(chain) <= len(closed):
            chain.append(int(lane[cur]))
            cur = int(parent[cur])
        if cur != -1:  # the single initial state is root marker -1
            bad["chain"] += 1
            continue
        lanes = chain[-2::-1]  # drop the root's own lane, oldest first
        bad["depth"] += len(lanes) != level - 1
        s = replay_lanes(c, lanes)
        if s is None:
            bad["not_a_path"] += 1
            continue
        bad["in_seed_levels"] += s in seen
        reached.add(s)
    for name, v in bad.items():
        checks.append(chk(f"sample_{name}", v, 0))
    checks.append(chk(
        "sample_distinct_states",
        len(reached), k - bad["chain"] - bad["not_a_path"]))
    return checks
