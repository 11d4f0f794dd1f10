"""The simulation cell, its own pieces, on the CPU: the simulated line's
parser on the line as the CLI prints it; the comparison
``sim-walk-replay`` on recorded answers (a sound one; one with the line
missing; one on another budget; one whose totals are off; one with no
"not exhaustive" sentence; one that reports replay mismatches; two
digests in one window; traced ones whose stats are off); the least bytes
and the roofline share of ``lib/sim_bytes.py``; the configuration's five
guarantees and the manifest's lists; the driver's refusal of a checkout
with no ``simulated_line``; the tiny fixture cell and its control
through the harness."""

import copy
import os

import pytest

from benchmark import run
from benchmark.lib import plug, program_spans, sim_bytes

CELL = "cli-simulate-scaled"
MANIFEST = os.path.join(run.ROOT, "BENCHMARK.json")
COMPARISON = plug.load_file("comparisons", "sim-walk-replay")
DRIVER = plug.load_file("drivers", "repeat-cli-simulate")
NINE = [
    "device_idle_pct.cli9m", "compiles_in_window.cli9m",
    "host_dispatch_s.cli9m", "host_fetch_wait_s.cli9m",
    "host_unaccounted_s.cli9m", "jit_host_s.cli9m", "jit_body_traces.cli9m",
    "peak_hbm_gb.cli9m", "device_unscoped_pct.cli9m"]


def loaded():
    _man, _cell, config, traffic = run.load_cell(MANIFEST, CELL)
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    return config, traffic


W, D, R, M, K = COMPARISON.wanted(loaded()[1])
DIGEST = "ab" * 32


def line(walkers=W, depth=D, rounds=R, steps=M, dumped=0, mismatches=0,
         digest=DIGEST):
    return (f"Simulated: {walkers} walkers of depth {depth} in segments of "
            f"25 steps, {rounds} rounds, {steps} steps, {dumped} behaviours "
            f"dumped ({mismatches} replay mismatches), final walker states "
            f"sha256 {digest}.")


def answer(text_line=None, states=M + W * R, walks=W * R, sentence=True,
           stats=None, rc=0):
    text = (f"Simulation: {W} walkers of depth {D} ({states} states "
            f"visited, {M} steps, {walks} completed walks).\n"
            "Finished in 9.9s (1 steps/sec, 1 walks/sec).\n")
    if sentence:
        text += ("No violation found within the simulation budget (stop "
                 "reason: step_budget); simulation is NOT exhaustive — "
                 "absence of violations is inconclusive.\n")
    text += (line() if text_line is None else text_line) + "\n"
    # no dump_prefix: a recorded answer has no files, so its count of
    # behaviours reads as differing; the tests below look past that name
    return {"rc": rc, "text": text, "stats": stats or {}}


def wrong_names(answers):
    config, traffic = loaded()
    return [c["name"] for c in COMPARISON.compare(config, traffic, answers, 7)
            if not c["ok"] and not c["name"].startswith("behaviours_dumped")]


# ---- the line and the comparison on recorded answers -----------------------

def test_the_parser_reads_the_line_as_the_cli_prints_it():
    from pulsar_tlaplus_tpu import cli

    st = {"sim_walkers": W, "sim_depth": D, "sim_segment_len": 25,
          "sim_rounds": R, "sim_steps": M, "sim_dump_behaviours": K,
          "sim_dump_mismatches": 0, "sim_keys_digest": DIGEST}
    assert COMPARISON.parse_simulated_line(cli.simulated_line(st)) == {
        "walkers": W, "depth": D, "segment": 25, "rounds": R, "steps": M,
        "dumped": K, "mismatches": 0, "digest": DIGEST}
    assert COMPARISON.parse_simulated_line("no line") is None
    two = cli.simulated_line(st) + "\n" + cli.simulated_line(st)
    assert COMPARISON.parse_simulated_line(two) is None


def test_the_traffic_is_the_issues():
    config, traffic = loaded()
    assert (W, D, K) == (262144, 100, 16)
    assert 4 <= R <= 16 and M == R * 26214400
    assert (traffic["rounds"], traffic["sim_steps"]) == (R, M)
    assert traffic["argv"] == [
        "check", "specs/compaction.tla", "-config", traffic["cfg"],
        "-simulate", "262144", "-depth", "100", "-sim-steps", str(M),
        "-sim-dump-num", "16"]
    assert traffic["driver"] == "repeat-cli-simulate"
    assert (traffic["expect"], traffic["exit_code"]) == ("clean", 0)


@pytest.mark.parametrize("case,want", [
    (dict(), []),
    (dict(text_line=""), ["simulated_line_missing"]),
    (dict(text_line=line() + "\n" + line()), ["simulated_line_missing"]),
    (dict(text_line=line(rounds=R + 1, steps=M + W * D)),
     [f"budget_differs_from_{W}x{D}x{R}"]),
    (dict(text_line=line(walkers=W // 2)),
     [f"budget_differs_from_{W}x{D}x{R}"]),
    (dict(states=M), ["totals_differ"]),
    (dict(walks=W), ["totals_differ"]),
    (dict(sentence=False), ["not_exhaustive_sentence_missing"]),
    (dict(rc=1), ["wrong_exit_code"]),
    (dict(text_line=line(mismatches=2)), ["replay_mismatches"]),
    (dict(stats={"sim_violations": 3, "sim_dump_mismatches": 0}),
     ["stats_sim_violations"]),
    (dict(stats={"sim_violations": 0, "sim_dump_mismatches": 1}),
     ["stats_sim_dump_mismatches"]),
])
def test_the_comparison_reads_each_fault_and_nothing_else(case, want):
    assert wrong_names([answer(), answer(**case)]) == want


def test_two_digests_in_one_window_are_read():
    other = answer(text_line=line(digest="cd" * 32))
    assert wrong_names([answer(), other]) == ["digests_differ"]
    assert wrong_names([answer(), answer()]) == []


def test_a_window_with_no_check_is_not_correct():
    assert wrong_names([]) == ["checks_compared"]


# ---- the bytes -------------------------------------------------------------

def test_the_least_bytes_come_from_the_configurations_shapes():
    config, _traffic = loaded()
    assert config["shapes"]["state_bytes_unpacked"] == 592
    assert sim_bytes.least_bytes(config, 1000) == 2 * 592 * 1000
    other = {"shapes": {"state_bytes_unpacked": 10}}
    assert sim_bytes.least_bytes(other, 7) == 140
    scope_s = {"sim_expand": 1.0, "sim_choose": 0.5, "sim_inv": 0.5,
               "sim_dup": 9.0}
    assert sim_bytes.step_seconds(scope_s) == 2.0
    peaks = {"hbm_bytes_per_s": 1000.0}
    assert sim_bytes.step_hbm_pct(other, 100, scope_s, peaks) == 100.0
    assert sim_bytes.step_hbm_pct(other, 100, {"probe": 1.0}, peaks) is None
    assert sim_bytes.step_hbm_pct(other, 100, scope_s, {}) is None


# ---- the configuration and the manifest ------------------------------------

def test_the_configuration_states_its_five_guarantees_and_one_cut():
    config, traffic = loaded()
    assert len(config["guarantees"]) == 5
    assert set(config["reduced"]) == {"sim_steps"}
    assert list(config["bindings"]) == [traffic["cfg"]]
    assert config["reference"]["comparison"] == {"clean": "sim-walk-replay"}
    assert config["control"]["clean"]["kind"] == "reference-holds-leak"
    assert config["control"]["clean"]["invariant"] == "CompactedLedgerLeak"
    assert config["assumed"]["invariants"] == [
        "TypeSafe", "CompactionHorizonCorrectness"]
    sh = config["shapes"]
    assert (sh["state_bits"], sh["state_leaves"], sh["successor_lanes"],
            sh["initial_states"]) == (618, 15, 34, 1)


def cell_metrics():
    man = run.read_json(MANIFEST)
    return [m["name"] for m in man["per_layer"]
            if CELL in m.get("workloads", ())]


def test_the_cell_adds_no_metric_and_reads_nine_accepted_ones():
    man = run.read_json(MANIFEST)
    assert len(man["per_layer"]) == 128  # the manifest's ceiling
    assert [m for m in man["per_layer"] if m.get("workloads") == [CELL]] == []
    assert cell_metrics() == NINE
    for m in man["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "verdict_s"
    verdict = next(m for m in man["end_to_end"] if m["name"] == "verdict_s")
    assert CELL in verdict["workloads"]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "repeat-simulate", "compaction-simulate")
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["sim_steps"] and len(entry["source"]) <= 200
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


@pytest.mark.parametrize("name", NINE)
def test_every_metric_has_a_reader_that_reads_nothing_where_nothing_is(name):
    ctx = {
        "out": {"answers": [{"stats": {}}], "stats": {"checks": [{}]}},
        "config": {"shapes": {}}, "peaks": {}, "trace": None,
        "compiles": None, "memory_peak_bytes": 0,
        program_spans.CACHE_KEY: None,
    }
    assert run.read_layer_metric(name, ctx) is None


# ---- the driver ------------------------------------------------------------

def test_a_checkout_with_no_simulated_line_is_refused_at_once(monkeypatch):
    from pulsar_tlaplus_tpu import cli

    config, traffic = loaded()
    monkeypatch.delattr(cli, "simulated_line")
    drv = DRIVER.Driver(config, copy.deepcopy(traffic), run.ROOT,
                        run.WORK_DIR, 0, 0)
    with pytest.raises(SystemExit) as e:
        drv.setup(40.0)
    assert e.value.code not in (0, None) and "refused" in str(e.value.code)


def test_the_driver_hands_each_check_the_seed_and_a_prefix_of_its_own(
        tmp_path):
    config, traffic = loaded()
    drv = DRIVER.Driver(config, copy.deepcopy(traffic), run.ROOT,
                        str(tmp_path), 0, 2147483659)
    first, _tel = drv._argv()
    p1 = drv.dump_prefix
    second, _tel = drv._argv()
    assert first[-4:] == ["-sim-seed", "2147483659", "-sim-dump", p1]
    assert second[-1] == drv.dump_prefix != p1
    assert os.path.isdir(os.path.dirname(p1))
    assert first[1] == os.path.join(run.ROOT, "specs/compaction.tla")


# ---- the tiny fixture cell, through the harness ----------------------------

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.simulate.test.json")
ON_CPU = [n for n in NINE if n not in (
    "peak_hbm_gb.cli9m", "device_unscoped_pct.cli9m")]


def test_the_fixture_cell_is_correct_and_reads_its_counters():
    """The real cell's driver, comparison and readers on the cell's own
    binding at 48 walkers of depth 100."""
    r = run.run_cell(FIX, CELL, 2147483659, 5.0, 1, require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0, r
    assert r["attempted"] >= 2
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ON_CPU:
        assert isinstance(m[name], (int, float)), name
    # the warm-up check built the programs: the window's trace nothing
    assert m["jit_body_traces.cli9m"] == 0
    assert m["compiles_in_window.cli9m"] == 0


def test_an_untraced_run_reports_a_checks_wall():
    r = run.run_cell(FIX, CELL, 5, 1.0, 0, require_tpu=False)
    assert r["correct"] is True and r["attempted"] >= 1
    assert set(r["metrics"]) == {"verdict_s", "setup_s"}


def test_the_control_reads_the_early_violation_and_nothing_else():
    from benchmark import control

    rs = control.run_control(FIX, CELL, [0, 1], 40.0, False)
    assert [r["correct"] for r in rs] == [False, False], rs
    for r in rs:
        assert [w["name"] for w in r["wrong"]] == [
            "behaviour_wrong_early_violation"]
