"""What a liveness run says about itself (PR 36): the ``result`` event's
``stats`` (the explorer's own, the liveness phases, the sweep's and the
analysis's counters), the behaviour graph it reports level by level
against the benchmark's plain reference, the ``ptt.live_*`` and
``ptt.sweep_*`` scopes of its programs, the CLI's printed summary, and
the tiers the CLI starts the liveness explorer at."""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import live_reference, plug
from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.engine import liveness
from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from tests.helpers import SMALL_CONFIGS

BINDINGS = {
    "producer_on": SMALL_CONFIGS["producer_on"],
    "two_crashes": SMALL_CONFIGS["two_crashes"],
    # the spec's stub consumer never advances, so the goal is out of
    # reach: a fair behaviour ends in a not-goal dead end
    "consumer_on": dataclasses.replace(
        SMALL_CONFIGS["producer_on"], model_consumer=True
    ),
}
# (binding, fairness) -> the verdict
CASES = {
    ("producer_on", "wf_next"): True,
    ("two_crashes", "wf_next"): True,
    ("consumer_on", "wf_next"): False,
    ("producer_on", "none"): False,
}
SWEPT = [k for k in CASES if k[1] == "wf_next"]
COUNTERS = (
    "distinct_states", "sweep_chunks", "sweep_groups", "sweep_edges",
    "sweep_query_lanes", "sweep_sort_lanes", "sweep_prop_lanes",
    "sweep_d2h_bytes", "live_goal_states", "live_dead_ends",
    "analyse_peel_rounds", "fp_collision_prob", "jit_traces",
    "jit_host_s", "jit_backend_compiles", "jit_body_traces",
    # the explorer's own stats stay underneath
    "host_dispatch_s", "host_fetch_s", "grow_events", "fpset_table_cap",
    "dispatches_per_level",
)
_ran = {}


def ids(cases):
    return ["-".join(c) for c in cases]


def run_case(case, tmp_path_factory):
    """``(LivenessResult, the run's telemetry events)``, one run a case
    for the whole module."""
    if case not in _ran:
        tel = tmp_path_factory.mktemp("live") / "telemetry.jsonl"
        res = LivenessChecker(
            CompactionModel(BINDINGS[case[0]]), fairness=case[1],
            frontier_chunk=512, visited_cap=1 << 13, telemetry=str(tel),
        ).run()
        with open(tel, encoding="utf-8") as f:
            _ran[case] = (res, [json.loads(x) for x in f if x.strip()])
    return _ran[case]


def reference_of(binding):
    return live_reference.profile_of(
        live_reference.search(BINDINGS[binding])["levels"]
    )


@pytest.mark.parametrize("case", SWEPT, ids=ids(SWEPT))
def test_liveness_result_event_carries_stats(case, tmp_path_factory):
    """The run emits two ``result`` events (the explorer's, then its
    own); the LAST one, which a reader of the stream takes, has
    ``stats`` with every counter, and the liveness phases sum with
    ``host_unaccounted_s`` to the run's wall."""
    _res, events = run_case(case, tmp_path_factory)
    results = [e for e in events if e["event"] == "result"]
    assert len(results) == 2 and "holds" in results[-1]
    st = results[-1]["stats"]
    for k in COUNTERS:
        assert k in st, k
    phases = [f"host_{p}_s" for p in spans.LIVE_PHASES]
    for k in phases:
        assert st[k] >= 0.0, k
    total = sum(st[k] for k in phases) + st["host_unaccounted_s"]
    wall = results[-1]["wall_s"]
    assert abs(total - wall) <= 0.02 * wall + 2e-3
    assert st["host_explore_s"] >= results[0]["wall_s"] * 0.98
    assert st["sweep_query_lanes"] == (
        st["sweep_chunks"] * 16384 * CompactionModel(BINDINGS[case[0]]).A
    )
    assert st["sweep_d2h_bytes"] >= 8 * st["sweep_edges"]
    assert st["fp_collision_prob"] == 0.0
    # the schema v16 keys stay where they were
    assert results[-1]["work_sweep_sort_lanes"] == st["sweep_sort_lanes"]


@pytest.mark.parametrize("case", list(CASES), ids=ids(CASES))
def test_graph_counters_equal_the_references(case, tmp_path_factory):
    res, events = run_case(case, tmp_path_factory)
    ref = reference_of(case[0])
    assert res.holds is CASES[case]
    graph, by = res.graph, res.graph["by_level"]
    st = [e for e in events if e["event"] == "result"][-1]["stats"]
    assert graph["states"] == sum(ref["size"]) == res.distinct_states
    assert graph["levels"] == len(ref["size"])
    assert by["size"] == ref["size"] and by["goal"] == ref["goal"]
    assert graph["goal_states"] == st["live_goal_states"] == sum(ref["goal"])
    if case[1] == "none":
        # no fairness: the verdict needs no edge, and none is swept
        assert graph["edges"] is None and "edges" not in by
        assert st["sweep_edges"] is None and st["sweep_chunks"] == 0
        return
    assert by["edges"] == ref["edges"]
    assert by["dead_ends"] == ref["dead_ends"]
    assert graph["edges"] == st["sweep_edges"] == sum(ref["edges"])
    assert graph["dead_ends"] == st["live_dead_ends"] == sum(
        ref["dead_ends"]
    )


def test_the_references_verdicts_are_the_oracles():
    """``live_reference.verdict`` against ``pyeval.check_eventually``
    (the program's copy of the oracle), both fairness modes."""
    from pulsar_tlaplus_tpu.ref import pyeval as pe

    for name, c in BINDINGS.items():
        g = live_reference.search(c, keep_graph=True)
        for fairness in ("none", "wf_next"):
            holds, _why, lasso = live_reference.verdict(g, fairness)
            assert holds == pe.check_eventually(c, fairness)[0], name
            assert (lasso is None) == holds
    # a prefix says whether it was the whole graph
    part = live_reference.search(BINDINGS["producer_on"], max_levels=3)
    assert not part["complete"] and len(part["levels"]) == 3
    assert part["next_size"] == 56


PROGRAMS = {
    "ptt_live_table": {"ptt.live_table"},
    "ptt_live_goal": {"ptt.live_goal"},
    "ptt_sweep": {
        "ptt.sweep_expand", "ptt.sweep_join", "ptt.sweep_prop",
        "ptt.sweep_compact",
    },
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_liveness_program_carries_its_scopes(program):
    """As lowered: each scope is in the program's HLO metadata, every
    operation of the sweep lies under one of its four, and the jitted
    functions are named ``ptt_*`` (a scope is no part of JAX's cache
    key, a name is: obs/spans.py)."""
    ck = LivenessChecker(
        CompactionModel(BINDINGS["producer_on"]), fairness="wf_next",
        frontier_chunk=512, visited_cap=1 << 13,
    )
    cap = ck._table_cap(1654)
    rows = jax.ShapeDtypeStruct((2 * cap * ck.model.layout.W,), jnp.uint32)
    n = jax.ShapeDtypeStruct((), jnp.int32)
    col = jax.ShapeDtypeStruct((cap,), jnp.uint32)
    lowered = {
        "ptt_live_table": lambda: ck._table_jit(cap).lower(rows, n),
        "ptt_live_goal": lambda: ck._goal_jit(cap).lower(rows, n),
        "ptt_sweep": lambda: ck._sweep_jit(cap, 2).lower(
            rows, n, n, *([col] * (ck.K + 1))
        ),
    }[program]()
    txt = lowered.as_text(debug_info=True)
    assert set(re.findall(r"ptt\.[a-z_]+", txt)) == PROGRAMS[program]
    names = re.findall(rf'"(jit\({program}\)/[^"]*)"', txt)
    assert len(names) >= 5
    assert all("/ptt." in x for x in names)
    if program == "ptt_sweep":
        # as compiled: innermost wins (the chunk's stages nest in the
        # scan's scope), both sorts of the join carry theirs, and the
        # names under no scope are bare: the program's arguments and a
        # comparator's or reducer's own, which no device event carries
        hlo = lowered.compile().as_text()
        ops = re.findall(r'op_name="([^"]*)"', hlo)
        assert sum(
            x.endswith("/while/body/closed_call/ptt.sweep_join/sort")
            for x in ops
        ) >= 2
        assert not [x for x in ops if "ptt." not in x and "/" in x]
        innermost = {re.findall(r"ptt\.[a-z_]+", x)[-1]
                     for x in ops if "ptt." in x}
        assert innermost == PROGRAMS[program]


def test_phase_clock_reads_any_list_of_phases():
    clock = spans.PhaseClock("r")
    with clock.phase("explore"):
        with clock.phase("sweep_fetch"):
            pass
    got = clock.host_seconds(spans.LIVE_PHASES)
    assert set(got) == {f"host_{p}_s" for p in spans.LIVE_PHASES} | {
        "host_unaccounted_s"
    }
    assert got["host_explore_s"] > 0.0 and got["host_analyse_s"] == 0.0
    assert sum(got.values()) == pytest.approx(clock.elapsed(), abs=1e-3)
    # DeviceChecker's own list is the default, with its extras
    assert "host_dispatch_s" in clock.stats()
    assert "level_wall_max_s" in clock.stats()


@pytest.mark.parametrize("case", list(CASES), ids=ids(CASES))
def test_printed_summary_parses_back(case, tmp_path_factory, capsys):
    """What ``cli check -property`` prints after the verdict, read back
    by the benchmark's comparison: the same numbers."""
    res, _events = run_case(case, tmp_path_factory)
    args = type("A", (), {"fairness": case[1], "checkpoint": None})
    capsys.readouterr()
    rc = cli._report_liveness("Termination", args, res)
    text = capsys.readouterr().out
    assert rc == (0 if CASES[case] else 1)
    parse = plug.load_file(
        "comparisons", "liveness-prefix-plus-pinned"
    ).parse_report
    got = parse(text)
    g = res.graph
    assert (got["property"], got["fairness"], got["holds"]) == (
        "Termination", case[1], CASES[case]
    )
    for k in ("states", "levels", "edges", "goal_states", "dead_ends"):
        assert got[k] == g[k], k
    by = g["by_level"]
    assert got["profile"]["size"] == by["size"]
    assert got["profile"]["goal"] == by["goal"]
    none = [None] * len(by["size"])
    assert got["profile"]["edges"] == by.get("edges", none)
    assert got["profile"]["dead_ends"] == by.get("dead_ends", none)


def test_cli_banner_names_the_property_and_lists_no_invariant():
    args = type("A", (), {"liveness_property": "Termination",
                          "fairness": "wf_next"})
    said = cli._checking_what(args, ("TypeSafe",))
    assert "Termination" in said and "wf_next" in said
    assert "TypeSafe" not in said and "no invariant" in said
    args.liveness_property = None
    assert cli._checking_what(args, ("TypeSafe",)) == (
        "invariants: ['TypeSafe']"
    )


def test_cli_liveness_explorer_starts_at_cli_checks_tiers(monkeypatch):
    """``cli check -property`` hands its explorer the ``visited_cap``
    ``cli check`` starts at, so one binding grows through the same
    table sizes whichever question is asked; ``LivenessChecker``'s own
    default stays for its other callers."""
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    seen = {}

    class Stop(Exception):
        pass

    def live_run(self, resume=False):
        seen["live"] = self._checker
        raise Stop

    def check_run(self, seed=None, resume=False):
        seen["check"] = self
        raise Stop

    monkeypatch.setattr(liveness.LivenessChecker, "run", live_run)
    monkeypatch.setattr(DeviceChecker, "run", check_run)
    argv = ["check", "specs/compaction.tla", "-config",
            "specs/compaction.cfg"]
    with pytest.raises(Stop):
        cli.main(argv + ["-property", "Termination", "-fairness", "wf_next"])
    with pytest.raises(Stop):
        cli.main(argv)
    live, check = seen["live"], seen["check"]
    assert live.TCAP == check.TCAP == 2 * (1 << 16)
    assert live.LCAP == check.LCAP
    assert live.G == check.G == 4096
    assert live.progress
    own = LivenessChecker(
        CompactionModel(BINDINGS["producer_on"]), fairness="wf_next"
    )
    assert own._checker.TCAP == 2 * (1 << 14)


# ---- what the sweep's dispatches are made of (ISSUE 54) -----------------

SWEEP_SPLIT_KEYS = (
    "sweep_dispatch_calls", "sweep_dispatch_call_s",
    "sweep_dispatch_uploads", "sweep_dispatch_upload_s",
    "sweep_dispatch_jit_s", "sweep_dispatch_python_s",
    "sweep_dispatch_by_program", "sweep_calls_by_phase",
    "sweep_programs_by_phase",
)


@pytest.mark.parametrize("case", SWEPT, ids=ids(SWEPT))
def test_the_sweep_says_what_its_dispatches_are_made_of(
    case, tmp_path_factory
):
    """The liveness run's own clock splits ``sweep_dispatch``; the
    explorer's split of ITS ``dispatch`` rides underneath, as its other
    stats do."""
    from tests.test_spans import SPLIT_KEYS, assert_split_adds_up

    _res, events = run_case(case, tmp_path_factory)
    st = [e for e in events if e["event"] == "result"][-1]["stats"]
    for k in (*SWEEP_SPLIT_KEYS, *SPLIT_KEYS):
        assert k in st, k
    assert_split_adds_up(st, "sweep_dispatch")
    assert_split_adds_up(st)
    # a group a dispatch: one call of the sweep's program, two scalars
    by = st["sweep_dispatch_by_program"]
    assert list(by) == ["ptt_sweep"]
    assert by["ptt_sweep"][0] == st["sweep_groups"] > 0
    assert by["ptt_sweep"][2] == 2 * st["sweep_groups"]
    assert 0.0 < st["sweep_dispatch_jit_s"] <= st["sweep_dispatch_call_s"]
    # the two other programs of a liveness run, each under its phase
    others = st["sweep_programs_by_phase"]
    assert others["live_table"]["ptt_live_table"][:1] == [1]
    assert others["live_goal"]["ptt_live_goal"][:1] == [1]
    assert st["sweep_calls_by_phase"]["live_goal"][0] == 1
    # the explorer's programs are on ITS clock, not on this one
    assert "explore" not in others
    assert "ptt_level2" in st["dispatch_by_program"]


def test_an_unfair_run_sweeps_nothing_and_says_so(tmp_path_factory):
    _res, events = run_case(("producer_on", "none"), tmp_path_factory)
    st = [e for e in events if e["event"] == "result"][-1]["stats"]
    assert st["sweep_dispatch_calls"] == 0
    assert st["sweep_dispatch_by_program"] == {}
    assert st["sweep_dispatch_python_s"] == st["host_sweep_dispatch_s"] == 0.0
    assert list(st["sweep_programs_by_phase"]) == ["live_goal"]
