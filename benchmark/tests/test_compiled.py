"""The compiled path's cell, its own pieces, on the CPU: the compiled
line's parser on the line as the CLI prints it; the comparison
``pyeval-prefix-plus-pinned-compiled`` on recorded answers (a sound one;
one with the line missing; one under the hand model's banner; one that
fell back; one at other widths; one that names ``__EvalError__``; traced
ones whose stats differ); the configuration's ten guarantees and the
manifest's lists; the driver's refusal of a checkout with no
``compiled_line``; the tiny fixture cell and its two controls through
the harness."""

import copy
import os

import pytest

from benchmark import run
from benchmark.lib import plug, program_spans, reference

CELL = "cli-compiled"
MANIFEST = os.path.join(run.ROOT, "BENCHMARK.json")
COMPARISON = plug.load_file("comparisons", "pyeval-prefix-plus-pinned-compiled")
DRIVER = plug.load_file("drivers", "repeat-cli-compiled")


def loaded():
    _man, _cell, config, traffic = run.load_cell(MANIFEST, CELL)
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    return config, traffic


SHAPES = loaded()[0]["shapes"]
BINDING = next(iter(loaded()[0]["bindings"].values()))
STATES, LEVELS = BINDING["states"], BINDING["diameter"]
BANNER = (
    "tpu-tlc: checking compaction @ specs/compaction.tla via the "
    f"spec->kernel compiler (state width {SHAPES['state_bits']} bits, "
    f"{SHAPES['successor_lanes']} successor lanes; invariants: "
    "['TypeSafe', 'CompactionHorizonCorrectness'])\n")
HAND_BANNER = (
    "tpu-tlc: checking compaction @ specs/compaction_1m.cfg (state width "
    "43 bits, 19 successor lanes; invariants: ['TypeSafe', "
    "'CompactionHorizonCorrectness'])\n")
VERDICT = (f"{STATES} distinct states found, search depth (diameter) "
           f"{LEVELS}.\nFinished in 3.6s (373424 distinct states/sec).\n")
SOUND = (
    f"Compiled from the .tla: module compaction, state width "
    f"{SHAPES['state_bits']} bits in {SHAPES['state_words']} words, "
    f"{SHAPES['successor_lanes']} successor lanes, "
    f"{SHAPES['initial_states']} initial states, keys hashed, code "
    "generation 2.42 s after 0.01 s of parse and bind.")
STATS = {
    "codegen_s": 2.4225, "codegen_parse_s": 0.0113,
    "codegen_state_bits": SHAPES["state_bits"],
    "codegen_state_words": SHAPES["state_words"],
    "codegen_lanes": SHAPES["successor_lanes"],
    "codegen_initial_states": SHAPES["initial_states"],
    "key_exact": False, "fpset_failures": 0}


@pytest.fixture(scope="module")
def sizes():
    """The reference's prefix, searched once (4 s), and the stored
    levels after it."""
    config, traffic = loaded()
    base = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    prefix, stored = base.wanted_sizes(config, traffic)
    return prefix + stored


def answer(sizes, line=SOUND, stats=None, banner=BANNER, extra=""):
    return {"rc": 0, "level_sizes": list(sizes), "stats": stats or {},
            "text": banner + extra + VERDICT + (line + "\n" if line else "")}


def wrong_names(answers, monkeypatch, sizes):
    config, traffic = loaded()
    monkeypatch.setattr(
        reference, "bfs_levels",
        lambda c, max_levels=None, **kw: (sizes[:max_levels], None))
    return [c["name"] for c in COMPARISON.compare(config, traffic, answers, 7)
            if not c["ok"]]


# ---- the parser ----------------------------------------------------------

def test_the_line_is_read_back_number_for_number():
    got = COMPARISON.parse_compiled_line("x\n" + SOUND + "\ny\n")
    assert got == {
        "module": "compaction", "bits": SHAPES["state_bits"],
        "words": SHAPES["state_words"], "lanes": SHAPES["successor_lanes"],
        "initial": SHAPES["initial_states"], "key_exact": False,
        "codegen_s": 2.42, "parse_s": 0.01}
    exact = COMPARISON.parse_compiled_line(
        SOUND.replace("keys hashed", "keys exact"))
    assert exact["key_exact"] is True


def test_the_line_is_the_one_the_cli_prints():
    from pulsar_tlaplus_tpu import cli

    assert cli.compiled_line("compaction", STATS) == SOUND


@pytest.mark.parametrize("text", [
    VERDICT,                                   # no line
    SOUND + "\n" + SOUND + "\n",               # two
    SOUND.replace(" of parse and bind.", ""),  # cut short
    SOUND.replace("22 successor", "many successor"),
])
def test_no_line_one_cut_short_or_two_reads_as_none(text):
    assert COMPARISON.parse_compiled_line(text) is None


# ---- the comparison, on recorded answers ---------------------------------

def test_a_sound_answer_is_correct(monkeypatch, sizes):
    assert sum(sizes) == STATES and len(sizes) == LEVELS
    assert wrong_names([answer(sizes, stats=STATS), answer(sizes)],
                       monkeypatch, sizes) == []


WIDTHS = "widths_differ_from_{state_bits}_{state_words}_{successor_lanes}" \
    .format(**SHAPES)


@pytest.mark.parametrize("kw, want", [
    (dict(line=None), {"compiled_line_missing"}),
    (dict(line=None, banner=HAND_BANNER),
     {"compiled_line_missing", "hand_model_banner"}),
    (dict(banner=HAND_BANNER), {"hand_model_banner"}),
    (dict(line=None, extra="tpu-tlc: note: spec->kernel compiler declined "
          "(x); falling back to the generic interpreter\n"),
     {"compiled_line_missing", "fell_back", "fallback_or_recovery"}),
    (dict(line=SOUND.replace(f"{SHAPES['state_bits']} bits", "43 bits")),
     {WIDTHS}),
    (dict(line=SOUND.replace(f"in {SHAPES['state_words']} words",
                             "in 2 words")), {WIDTHS}),
    (dict(line=SOUND.replace(f"{SHAPES['successor_lanes']} successor",
                             "19 successor")), {WIDTHS}),
    (dict(line=SOUND.replace("keys hashed", "keys exact")), {WIDTHS}),
    (dict(line=SOUND.replace("module compaction", "module other")),
     {WIDTHS}),
    (dict(extra="Error: Invariant __EvalError__ is violated.\n"),
     {"eval_error"}),
    (dict(stats=dict(STATS, fpset_failures=3)), {"fpset_failures"}),
    (dict(stats=dict(STATS, key_exact=True)), {"stats_differ"}),
    (dict(stats=dict(STATS, codegen_lanes=19)), {"stats_differ"}),
    (dict(stats={k: v for k, v in STATS.items() if k != "codegen_s"}),
     {"stats_differ"}),
])
def test_one_guarantee_broken_is_not_correct(monkeypatch, sizes, kw, want):
    got = wrong_names([answer(sizes), answer(sizes, **kw)], monkeypatch, sizes)
    assert set(got) == want, got


def test_a_wrong_count_is_still_not_correct(monkeypatch, sizes):
    short = list(sizes)
    short[-1] -= 1
    got = wrong_names([answer(short)], monkeypatch, sizes)
    assert [g for g in got
            if g.startswith("level_sizes_differ_from_the_stored")]


# ---- the configuration and the manifest ----------------------------------

def test_the_configuration_states_its_widths_and_ten_guarantees():
    config, traffic = loaded()
    nine = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-9m.json"))
    assert config["program"]["cli"] == nine["program"]["cli"]
    assert config["architecture"] is None
    g = config["guarantees"]
    assert len(g) == 10 and g[:6] == nine["guarantees"]
    assert SHAPES["key_exact"] is False and SHAPES["key_bits"] == 64
    assert SHAPES["state_words"] == -(-SHAPES["state_bits"] // 32)
    for k in ("sub_batch", "visited_cap", "frontier_cap", "rows_window",
              "fuse", "growth"):
        assert config["cli_tiers"][k] == nine["cli_tiers"][k], k
    assert "_explorer_tiers" in config["cli_tiers"]["origin"]
    pinned = config["reference"]["pinned_level_sizes"]
    assert sorted(int(k) for k in pinned) == list(range(
        config["reference"]["prefix_levels"] + 1, LEVELS + 1))
    assert traffic["argv"] == [
        "check", "specs/compaction.tla", "-config", traffic["cfg"],
        "-compile"]
    assert list(config["bindings"]) == [traffic["cfg"]]
    assert set(config["reduced"]) == {"binding"}
    kinds = [c["kind"] for c in config["control"]["complete"]["controls"]]
    assert kinds == ["narrow-fingerprint-reference", "program-hand-model"]


def cell_metrics():
    man = run.read_json(MANIFEST)
    return [m["name"] for m in man["per_layer"]
            if CELL in m.get("workloads", ())]


def test_the_cell_adds_no_metric_and_reads_14_accepted_ones():
    man = run.read_json(MANIFEST)
    assert len(man["per_layer"]) == 128  # the manifest's ceiling
    assert [m for m in man["per_layer"] if m.get("workloads") == [CELL]] == []
    assert len(cell_metrics()) == 14
    assert all(n.endswith(".cli9m") for n in cell_metrics())
    for m in man["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "verdict_s"
    # (not "the last of the list": a later cell is appended after it)
    verdict = next(m for m in man["end_to_end"] if m["name"] == "verdict_s")
    assert CELL in verdict["workloads"]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "repeat-check-compiled", "compaction-compiled")
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["binding"] and len(entry["source"]) <= 200


@pytest.mark.parametrize("name", cell_metrics())
def test_every_metric_has_a_reader_that_reads_nothing_where_nothing_is(name):
    ctx = {
        "out": {"answers": [{"stats": {}, "level_sizes": [1, 2]}],
                "stats": {"checks": [{}]}},
        "config": {"shapes": {"key_columns": 2, "state_words": 4}},
        "peaks": {}, "trace": None, "compiles": None,
        "memory_peak_bytes": 0, program_spans.CACHE_KEY: None,
    }
    assert run.read_layer_metric(name, ctx) is None


# ---- the driver ----------------------------------------------------------

def test_a_checkout_with_no_compiled_line_is_refused_at_once(monkeypatch):
    from pulsar_tlaplus_tpu import cli

    config, traffic = loaded()
    monkeypatch.delattr(cli, "compiled_line")
    drv = DRIVER.Driver(config, copy.deepcopy(traffic), run.ROOT,
                        run.WORK_DIR, 0, 0)
    with pytest.raises(SystemExit) as e:
        drv.setup(40.0)
    assert e.value.code not in (0, None) and "refused" in str(e.value.code)


# ---- the tiny fixture cell, through the harness ---------------------------

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.compiled.test.json")
ON_CPU = (
    "compiles_in_window.cli9m", "dispatches_per_level.cli9m",
    "host_dispatch_s.cli9m", "host_grow_s.cli9m", "host_fetch_wait_s.cli9m",
    "host_unaccounted_s.cli9m", "jit_host_s.cli9m", "jit_body_traces.cli9m",
    "grow_events.cli9m",
)


def test_the_fixture_cell_is_correct_and_reads_its_counters():
    """The real cell's driver, comparison and readers on the shipped
    45,198-state binding through ``-compile``."""
    r = run.run_cell(FIX, CELL, 2147483659, 5.0, 1, require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0, r
    assert r["attempted"] >= 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ON_CPU:
        assert isinstance(m[name], (int, float)), name
    assert m["jit_body_traces.cli9m"] == 0
    assert m["compiles_in_window.cli9m"] == 0
    # the CPU's stand-in device plane carries no ptt. scope
    assert "stage_device_s.probe.cli9m" not in m


def test_an_untraced_run_reports_a_checks_wall():
    r = run.run_cell(FIX, CELL, 5, 1.0, 0, require_tpu=False)
    assert r["correct"] is True and r["attempted"] >= 1
    assert set(r["metrics"]) == {"verdict_s", "setup_s"}


def test_both_controls_are_not_correct_each_on_its_own_line():
    from benchmark import control

    rs = control.run_control(FIX, CELL, [0, 1], 40.0, False)
    assert [r["correct"] for r in rs] == [False, False], rs
    assert "compiled_line_missing" in [w["name"] for w in rs[0]["wrong"]]
    # the program without -compile: the exact count, the hand model's
    assert [w["name"] for w in rs[1]["wrong"]] == [
        "compiled_line_missing", "hand_model_banner"]
