"""The temporal-property cell's own pieces, on the CPU: the tiny fixture
cell (``fixtures/BENCHMARK.termination.test.json``: the real cell's
driver, comparison, control and per-layer readers on the shipped
binding, 45,198 states and 56,133 ``<Next>_vars`` edges in 20 levels)
comes out correct as it stands and not correct with one level's edge
count altered (in the part the reference searches and in the part the
configuration stores), with the verdict altered, and with the exit code
altered; its control reaches the comparison and fails it; a program
without the sweep's counters (the parent commit's) makes the
counter-fed readers report nothing; and the sweep roofline's arithmetic
is held to made-up numbers."""

import os

import pytest

from benchmark import control, run
from benchmark.lib import live_reference, plug, program_spans, sweep_bytes

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.termination.test.json")
CELL = "cli-termination-wf"
ON_CPU = (
    "host_explore_s.live", "host_sweep_dispatch_s.live",
    "host_sweep_fetch_s.live", "host_sweep_account_s.live",
    "host_analyse_s.live", "host_unaccounted_s.live",
    "sweep_sort_lanes_per_edge", "sweep_d2h_gb", "jit_host_s.live",
    "compiles_in_window.live",
)
ON_CHIP_ONLY = (
    "stage_device_s.sweep_join", "stage_device_s.sweep_prop",
    "stage_device_s.sweep_expand", "stage_device_s.probe.live",
    "device_unscoped_pct.live", "sweep_hbm_pct", "peak_hbm_gb.live",
)
COUNTER_FED = ("sweep_sort_lanes_per_edge", "sweep_d2h_gb", "sweep_hbm_pct",
               "host_analyse_s.live")


def cell(trace):
    return run.run_cell(FIX, CELL, 2147483659, 4.0, trace,
                        require_tpu=False)


def wrong(r):
    return {c["name"] for c in r if not c["ok"]}


def test_sound_cell_is_correct_and_reads_its_counters():
    r = cell(trace=1)
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] >= 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ON_CPU:
        assert isinstance(m[name], (int, float)), name
    # one chunk of 16,384 states x 7 lanes against a 49,152-entry table,
    # sorted twice, three chunks; 56,133 edges kept
    assert m["sweep_sort_lanes_per_edge"] == pytest.approx(
        3 * 2 * (49152 + 16384 * 7) / 56133)
    assert m["sweep_d2h_gb"] >= 8 * 56133 / 1e9
    assert abs(m["host_unaccounted_s.live"]) < 0.05
    # the CPU's stand-in device plane carries no ptt. scope, the CPU has
    # no row in the peaks table and reports no peak memory
    for name in ON_CHIP_ONLY:
        assert name not in m, name


def altered_cell(monkeypatch, text=None, rc=None):
    """One untraced run of the fixture cell whose every check's report
    goes through ``text`` (a function of what the CLI printed) and whose
    exit code is ``rc``."""
    import builtins

    from pulsar_tlaplus_tpu import cli

    sound = cli._report_liveness

    def report(prop, args, lres):
        said = []
        with monkeypatch.context() as m:
            m.setattr(builtins, "print",
                      lambda *a, **k: said.append(" ".join(map(str, a))))
            code = sound(prop, args, lres)
        out = "\n".join(said)
        print(text(out) if text else out)
        return code if rc is None else rc

    monkeypatch.setattr(cli, "_report_liveness", report)
    return cell(trace=0)


@pytest.mark.parametrize("level", [5, 15])
def test_one_levels_edge_count_altered_is_not_correct(monkeypatch, level):
    """Level 5 is one the reference searches in the run, level 15 one
    whose numbers the configuration stores (the fixture's prefix is 12
    levels); the totals are left as they were."""
    r = altered_cell(monkeypatch, text=lambda out: out.replace(
        f"graph level {level}: 2187 states, 2916 edges",
        f"graph level {level}: 2187 states, 2917 edges")
        if level == 5 else out.replace(
        f"graph level {level}: 2916 states, 3645 edges",
        f"graph level {level}: 2916 states, 3644 edges"))
    assert r["correct"] is False, r
    assert r["failed"] == 0  # the check ran; its graph is what is wrong
    assert set(r["metrics"]) == {"verdict_s", "setup_s"}


def test_the_alteration_reaches_only_the_level_it_names():
    """The same alteration handed to the comparison directly: one check
    fails, the one of that level's part."""
    _man, _cell, config, traffic = run.load_cell(FIX, CELL)
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    mod = plug.load_file("comparisons", "liveness-prefix-plus-pinned")
    drv = plug.load_file("drivers", traffic["driver"]).Driver(
        config, traffic, run.ROOT, run.WORK_DIR, 0, 1)
    os.makedirs(run.WORK_DIR, exist_ok=True)
    drv.load()
    ans = drv.one()
    assert wrong(mod.compare(config, traffic, [ans], 1)) == set()
    bad = dict(ans, text=ans["text"].replace(
        "graph level 15: 2916 states, 3645 edges",
        "graph level 15: 2916 states, 3644 edges"))
    assert wrong(mod.compare(config, traffic, [bad], 1)) == {
        "level_edges_differ_from_the_stored_13_to_20"}
    bad = dict(ans, text=ans["text"].replace(
        "graph level 17: 3645 states, 2187 edges, 2187 goal, 0 dead",
        "graph level 17: 3645 states, 2187 edges, 2186 goal, 1 dead"))
    assert wrong(mod.compare(config, traffic, [bad], 1)) == {
        "level_goal_differ_from_the_stored_13_to_20",
        "level_dead_ends_differ_from_the_stored_13_to_20"}
    bad = dict(ans, level_sizes=[x + (i == 3) for i, x in
                                 enumerate(ans["level_sizes"])])
    assert wrong(mod.compare(config, traffic, [bad], 1)) == {
        "progress_level_sizes_differ_from_the_reference's_first_12"}


def test_the_verdict_altered_is_not_correct(monkeypatch):
    r = altered_cell(monkeypatch, text=lambda out: out.replace(
        "satisfied", "VIOLATED"))
    assert r["correct"] is False and r["failed"] == 0, r


def test_the_exit_code_altered_is_not_correct(monkeypatch):
    r = altered_cell(monkeypatch, rc=1)
    assert r["correct"] is False and r["failed"] == r["attempted"], r


def test_control_fairness_none_reaches_the_comparison_and_fails_it():
    rs = control.run_control(FIX, CELL, [1, 2147483659], 40.0, False)
    assert [r["correct"] for r in rs] == [False, False], rs
    names = {w["name"] for w in rs[0]["wrong"]}
    assert "control_crashed" not in names
    # what a check with no fairness assumed lacks, and nothing else: the
    # states, the levels and the goal states are the same graph's
    assert names == {
        "wrong_exit_code",
        "verdict_differs_from_Termination_under_wf_next_satisfied",
        "edges_differ_from_56133", "dead_ends_differ_from_0",
        "level_edges_differ_from_the_reference's_first_12",
        "level_edges_differ_from_the_stored_13_to_20",
        "level_dead_ends_differ_from_the_reference's_first_12",
        "level_dead_ends_differ_from_the_stored_13_to_20",
    }


def test_the_real_cells_stored_numbers_add_up():
    """``compaction-termination`` stores levels 14-23 of four columns;
    with the reference's own levels 1-13 (counted once, in the sandbox)
    they are the binding's totals; the guarantees start with
    ``compaction-published``'s six, and the one cut is the ladder's."""
    real = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-termination.json"))
    pub = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-published.json"))
    ref, b = real["reference"], real["bindings"]["specs/compaction_1m.cfg"]
    assert ref["prefix_levels"] == 13
    assert ref["pinned_verdict"] == {
        "property": "Termination", "fairness": "wf_next", "holds": True}
    for col in ("size", "edges", "goal", "dead_ends"):
        assert sorted(int(k) for k in ref["pinned"][col]) == list(
            range(14, 24)), col
    assert 416811 + sum(ref["pinned"]["size"].values()) == b["states"]
    assert 902978 + sum(ref["pinned"]["edges"].values()) == \
        b["next_vars_edges"] == 2392706
    assert sum(ref["pinned"]["goal"].values()) == b["goal_states"]
    assert sum(ref["pinned"]["dead_ends"].values()) == b["dead_ends"] == 0
    assert max(ref["pinned"]["size"].values()) == b["widest_level"]
    assert real["guarantees"][:6] == pub["guarantees"][:6]
    assert len(real["guarantees"]) == 9
    assert list(real["reduced"]) == ["MessageSentLimit"]
    assert "332.0 s" in real["reduced"]["MessageSentLimit"]
    man = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    argv = run.read_json(os.path.join(
        run.ROOT, "benchmark", "traffic", "repeat-termination.json"))["argv"]
    assert not {"-chunk", "-sweep-group", "-maxstates"} & set(argv)
    entry = next(c for c in man["configs"]
                 if c["name"] == "compaction-termination")
    assert entry["source"] == real["source"]
    assert entry["reduced"] == list(real["reduced"])

    def constants(name):
        with open(os.path.join(run.ROOT, "specs", name)) as f:
            text = f.read()
        return text[text.index("CONSTANTS"):]

    assert constants("compaction_1m.cfg") == constants(
        "compaction_29m.cfg").replace(
        "MessageSentLimit = 4", "MessageSentLimit = 3")


def test_every_metric_of_the_cell_has_a_reader_and_a_fixture_entry():
    man = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    mine = [m for m in man["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 18
    for m in mine:
        assert m["moves"] == "verdict_s"
        assert (os.path.exists(plug.path_of("layer_metrics", m["name"]))
                or os.path.exists(
                    plug.path_of("layer_metrics", m["name"], ".json"))), m
    assert run.read_json(FIX)["per_layer"] == mine
    assert CELL in next(m for m in man["end_to_end"]
                        if m["name"] == "verdict_s")["workloads"]


# ---- the counter-fed readers, on made-up contexts ----------------------

SHAPES = {"state_words": 2, "key_columns": 2, "successor_lanes": 16}
CHECK = {"distinct_states": 3862640, "sweep_edges": 6635530,
         "sweep_chunks": 236, "sweep_sort_lanes": 1948778496,
         "sweep_d2h_bytes": 250_000_000, "host_analyse_s": 3.5}
MOVED = 4 * (3862640 * 5 + 2 * 3862640 * 16 * 2 + 2 * 6635530)


def made_up_ctx(scope_s, checks=(CHECK,)):
    return {
        "out": {"answers": [{"stats": c} for c in checks],
                "stats": {"checks": list(checks)}},
        "config": {"shapes": SHAPES},
        "peaks": {"hbm_bytes_per_s": 819e9},
        program_spans.CACHE_KEY: {
            "device_planes": 1, "scoped": True, "scope_s": scope_s},
    }


SWEEP_S = {"sweep_expand": 0.5, "sweep_join": 8.0, "sweep_prop": 1.25,
           "sweep_compact": 0.25, "probe": 9.0}


def test_graph_bytes_are_rows_keys_table_and_kept_edges():
    assert sweep_bytes.graph_bytes(CHECK, SHAPES) == MOVED == 1119172880
    assert sweep_bytes.graph_bytes({}, SHAPES) is None  # a parent's result
    # a check whose edge list came from an earlier goal swept nothing
    assert sweep_bytes.graph_bytes(dict(CHECK, sweep_chunks=0), SHAPES) \
        is None
    assert sweep_bytes.window_bytes(made_up_ctx(SWEEP_S, (CHECK, CHECK))) \
        == 2 * MOVED
    assert sweep_bytes.window_bytes(made_up_ctx(SWEEP_S, ({},))) is None


def test_sweep_hbm_pct_arithmetic():
    read = plug.load_file("layer_metrics", "sweep_hbm_pct").read
    # the probe's seconds are no part of the sweep's
    want = 100.0 * MOVED / 10.0 / 819e9
    assert read(made_up_ctx(SWEEP_S), {}) == pytest.approx(want)
    assert 0.0 < want < 1.0  # sorts, not bytes, bound today's sweep
    assert sweep_bytes.share_pct(819e9, 1.0, 819e9) == 100.0
    # nothing to read: no sweep traced, or a run off the chip
    assert read(made_up_ctx({"probe": 9.0}), {}) is None
    assert read({**made_up_ctx(SWEEP_S), "peaks": {}}, {}) is None


def test_the_other_counter_fed_readers():
    ctx = made_up_ctx(SWEEP_S)
    assert run.read_layer_metric("sweep_sort_lanes_per_edge", ctx) == \
        pytest.approx(293.68845)
    assert run.read_layer_metric("sweep_d2h_gb", ctx) == 0.25
    assert run.read_layer_metric("host_analyse_s.live", ctx) == 3.5
    assert run.read_layer_metric("stage_device_s.sweep_join", ctx) == 8.0


@pytest.mark.parametrize("name", COUNTER_FED)
def test_a_program_without_sweep_counters_reports_nothing(name):
    """The parent commit's liveness ``result`` event has no ``stats``:
    the driver hands ``{}`` and each counter-fed reader returns None
    and does not raise."""
    ctx = made_up_ctx(SWEEP_S, ({}, {}))
    assert run.read_layer_metric(name, ctx) is None


def test_the_reference_counts_the_fixtures_binding():
    from benchmark.lib import tlafmt

    c = tlafmt.constants_from_cfg(
        os.path.join(run.ROOT, "specs", "compaction.cfg"))
    g = live_reference.search(c, keep_graph=True)
    prof = live_reference.profile_of(g["levels"])
    assert (sum(prof["size"]), sum(prof["edges"]), sum(prof["goal"]),
            sum(prof["dead_ends"])) == (45198, 56133, 3645, 0)
    assert live_reference.verdict(g, "wf_next")[:2] == (
        True, live_reference.SATISFIED_WF)
    assert live_reference.verdict(g, "none")[0] is False
