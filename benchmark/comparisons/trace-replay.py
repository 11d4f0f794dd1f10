"""Comparison ``trace-replay``.

Every counterexample of the window replayed through the reference:
exit code 1, the named invariant, a path from an initial state whose
every step is a transition of the named action, only the last state
violating, and as short as the reference's own breadth-first search
finds (the shortest-counterexample guarantee).
"""

from __future__ import annotations

from benchmark.lib import tlafmt
from benchmark.lib.reference import chk, fallback_or_recovery
from benchmark.ref import pyeval as pe


def compare(config, traffic, answers, seed):
    c = tlafmt.constants_from_cfg(traffic["cfg_path"])
    name = traffic["invariant"]
    inv = pe.INVARIANTS[name]
    ref = pe.check(c, invariants=(name,))
    inits = set(pe.initial_states(c))
    checks = [chk("checks_compared", len(answers) > 0, True)]
    bad = {"exit_code": 0, "invariant": 0, "first_state": 0,
           "transition": 0, "early_violation": 0, "last_state": 0,
           "length": 0}
    for a in answers:
        bad["exit_code"] += a["rc"] != 1
        try:
            violated, states, actions = tlafmt.parse_trace(
                a["text"], c.compaction_times_limit)
        except (ValueError, KeyError, AttributeError):
            violated, states, actions = None, [], []
        bad["invariant"] += violated != name
        bad["length"] += len(states) != ref.diameter
        if not states:
            bad["first_state"] += 1
            continue
        bad["first_state"] += states[0] not in inits
        for s, act, t in zip(states, actions, states[1:]):
            nxt = [u for k, u in pe.successors(c, s)
                   if pe.ACTION_NAMES[k] == act]
            bad["transition"] += t not in nxt
            bad["early_violation"] += not inv(c, s)
        bad["last_state"] += bool(inv(c, states[-1]))
    for k, v in bad.items():
        want_len = f"_not_{ref.diameter}" if k == "length" else ""
        checks.append(chk(f"trace_wrong_{k}{want_len}", int(v), 0))
    checks.append(chk("fallback_or_recovery", fallback_or_recovery(answers), 0))
    return checks
