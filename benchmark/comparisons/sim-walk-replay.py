"""Comparison ``sim-walk-replay``.

A clean check in simulation mode gives no count to hold it to: what it
gives is behaviours.  So EVERY check of the window is held to what its
own output says it walked, and every behaviour it dumped is replayed
through the reference (``benchmark/ref/pyeval.py``, which imports
nothing of the program and knows nothing of its engine):

- exit code 0 (``wrong_exit_code``) and the sentence that the verdict
  is not exhaustive (``not_exhaustive_sentence_missing``);
- the simulated line is there, once (``simulated_line_missing``: the
  line ``cli.simulated_line`` prints after the verdict), and its
  walkers, depth, rounds and steps are the traffic's
  (``budget_differs``);
- the totals are exact (``totals_differ``): steps = M, states visited
  = M + walkers x rounds, completed walks = walkers x rounds, from the
  ``Simulation:`` line;
- as many behaviour files as the traffic asks for, and the line says
  the same number (``behaviours_dumped_differ``); each file is
  ``depth + 1`` states (``behaviour_wrong_length``), starts in an
  initial state (``behaviour_wrong_first_state``), each step is a
  transition of the action it names, the ``Terminating`` self-loop
  where the reference enables it among them
  (``behaviour_wrong_transition``), and every state satisfies the
  configuration's invariants by the reference
  (``behaviour_wrong_early_violation``; a control may hand the
  reference a further invariant to hold the states to, under
  ``hold_also`` in an answer);
- the line's replay mismatches are 0 (``replay_mismatches``): each
  replayed behaviour ended, on the device, in the state the timed scan
  itself carried for that walker;
- one digest over the window's checks (``digests_differ``): the walk
  stream is a function of (seed, walkers, depth) alone;
- a traced run's checks carry the engine's ``result`` stats, and there
  ``sim_violations`` and ``sim_dump_mismatches`` have to be 0.

What has no per-layer metric is printed: every simulated line and, from
a traced run, the ``sim_*`` counters of each check, the device seconds
under the step's scopes and the step's share of the memory roofline
(``benchmark/lib/sim_bytes.py``).
"""

from __future__ import annotations

import glob
import json
import re

from benchmark.lib import plug, program_spans, sim_bytes, tlafmt, xplane_fast
from benchmark.lib.reference import chk
from benchmark.ref import pyeval as pe

SIMULATED_LINE = re.compile(
    r"^Simulated: (?P<walkers>\d+) walkers of depth (?P<depth>\d+) in "
    r"segments of (?P<segment>\d+) steps, (?P<rounds>\d+) rounds, "
    r"(?P<steps>\d+) steps, (?P<dumped>\d+) behaviours dumped "
    r"\((?P<mismatches>\d+) replay mismatches\), final walker states "
    r"sha256 (?P<digest>[0-9a-f]{64})\.$", re.M)
TOTALS_LINE = re.compile(
    r"^Simulation: (\d+) walkers of depth (\d+) \((\d+) states visited, "
    r"(\d+) steps, (\d+) completed walks\)\.$", re.M)
NOT_EXHAUSTIVE = "simulation is NOT exhaustive"
COUNTERS = ("sim_steps", "sim_states", "sim_walks", "sim_stutter_steps",
            "sim_enabled_lanes", "sim_dup_ratio_est", "sim_step_chunks",
            "sim_peak_bytes", "sim_dump_s", "steady_steps_per_sec",
            "jit_host_s")


def parse_simulated_line(text: str):
    """The one simulated line of a check's output as a dict, or None
    where there is none, more than one, or one cut short."""
    found = list(SIMULATED_LINE.finditer(text))
    if len(found) != 1:
        return None
    g = found[0].groupdict()
    return {k: (v if k == "digest" else int(v)) for k, v in g.items()}


def argv_value(argv, flag):
    return argv[argv.index(flag) + 1]


def wanted(traffic):
    """``(walkers, depth, rounds, steps, behaviours)`` of the traffic's
    own command line."""
    argv = traffic["argv"]
    walkers = int(argv_value(argv, "-simulate"))
    depth = int(argv_value(argv, "-depth"))
    steps = int(argv_value(argv, "-sim-steps"))
    return (walkers, depth, steps // (walkers * depth), steps,
            int(argv_value(argv, "-sim-dump-num")))


def behaviour_faults(c, text, depth, invariants, inits=None):
    """How one dumped behaviour fails the reference: a dict of counts
    (all 0 for a behaviour that is one of ``Next``'s)."""
    bad = {"length": 0, "first_state": 0, "transition": 0,
           "early_violation": 0}
    try:
        _v, states, actions = tlafmt.parse_trace(
            text, c.compaction_times_limit)
    except (ValueError, KeyError, AttributeError):
        states, actions = [], []
    bad["length"] += len(states) != depth + 1
    if not states:
        bad["first_state"] += 1
        return bad
    if inits is None:
        inits = set(pe.initial_states(c))
    bad["first_state"] += states[0] not in inits
    for s, act, t in zip(states, actions, states[1:]):
        nxt = [u for k, u in pe.successors(c, s)
               if pe.ACTION_NAMES[k] == act]
        bad["transition"] += t not in nxt
    for s in states:
        bad["early_violation"] += not all(
            pe.INVARIANTS[n](c, s) for n in invariants)
    return bad


def dumped_files(answer):
    prefix = answer.get("dump_prefix")
    return sorted(glob.glob(prefix + "_*")) if prefix else []


def print_traced(config, answers):
    """A traced run's counters, the step's device seconds and its share
    of the memory roofline: printed, not judged."""
    traced = [a["stats"] for a in answers if a.get("stats")]
    for st in traced:
        print("[benchmark] simulated check: "
              + ", ".join(f"{k} {st.get(k)}" for k in COUNTERS), flush=True)
    if not traced:
        return
    ctx = {}
    xplane_fast.prime(ctx)
    sp = program_spans.load(ctx)
    if sp is None or not sp["scoped"]:
        return
    steps = sum(st.get("sim_steps", 0) for st in traced)
    secs = sim_bytes.step_seconds(sp["scope_s"])
    with open(plug.path_of("lib", "peaks", ".json"), encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    for kind, row in peaks.items():
        pct = sim_bytes.step_hbm_pct(config, steps, sp["scope_s"], row)
        if pct is not None:
            print(f"[benchmark] simulation step: {steps} walker-steps of "
                  f"the window's checks, least bytes "
                  f"{sim_bytes.least_bytes(config, steps)}, {secs:.4f} "
                  f"device s under ptt.{', ptt.'.join(sim_bytes.STEP_SCOPES)}"
                  f": {pct:.2f}% of the memory roofline of a {kind}",
                  flush=True)


def compare(config, traffic, answers, seed):
    c = tlafmt.constants_from_cfg(traffic["cfg_path"])
    walkers, depth, rounds, steps, k = wanted(traffic)
    invariants = tuple(config["assumed"]["invariants"])
    inits = set(pe.initial_states(c))
    checks = [chk("checks_compared", len(answers) > 0, True)]
    lines = [parse_simulated_line(a["text"]) for a in answers]
    for m in SIMULATED_LINE.finditer("\n".join(a["text"] for a in answers)):
        print(f"[benchmark] {m.group(0)}", flush=True)
    checks.append(chk(
        "wrong_exit_code", sum(1 for a in answers if a["rc"] != 0), 0))
    checks.append(chk(
        "not_exhaustive_sentence_missing",
        sum(1 for a in answers if NOT_EXHAUSTIVE not in a["text"]), 0))
    checks.append(chk("simulated_line_missing",
                      sum(1 for ln in lines if ln is None), 0))
    # a check with no line is counted once, as missing
    have = [ln for ln in lines if ln is not None]
    checks.append(chk(
        f"budget_differs_from_{walkers}x{depth}x{rounds}",
        sum(1 for ln in have
            if [ln["walkers"], ln["depth"], ln["rounds"], ln["steps"]]
            != [walkers, depth, rounds, steps]), 0))
    want_totals = [str(x) for x in (
        walkers, depth, steps + walkers * rounds, steps, walkers * rounds)]
    checks.append(chk(
        "totals_differ",
        sum(1 for a in answers
            if [list(m.groups()) for m in TOTALS_LINE.finditer(a["text"])]
            != [want_totals]), 0))
    bad = {"length": 0, "first_state": 0, "transition": 0,
           "early_violation": 0}
    wrong_count = 0
    for a, ln in zip(answers, lines):
        files = dumped_files(a)
        wrong_count += len(files) != k or (
            ln is not None and ln["dumped"] != k)
        hold = invariants + tuple(a.get("hold_also", ()))
        for path in files:
            with open(path, encoding="utf-8") as f:
                faults = behaviour_faults(c, f.read(), depth, hold, inits)
            for name, n in faults.items():
                bad[name] += n
    checks.append(chk(f"behaviours_dumped_differ_from_{k}", wrong_count, 0))
    for name, n in bad.items():
        want_len = f"_not_{depth + 1}" if name == "length" else ""
        checks.append(chk(f"behaviour_wrong_{name}{want_len}", int(n), 0))
    checks.append(chk(
        "replay_mismatches", sum(ln["mismatches"] for ln in have), 0))
    checks.append(chk(
        "digests_differ", max(len({ln["digest"] for ln in have}) - 1, 0), 0))
    traced = [a["stats"] for a in answers if a.get("stats")]
    for key in ("sim_violations", "sim_dump_mismatches"):
        checks.append(chk(
            f"stats_{key}", sum(1 for st in traced if st.get(key) != 0), 0))
    print_traced(config, answers)
    return checks
