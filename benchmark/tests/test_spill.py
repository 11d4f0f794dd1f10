"""The tiered store's cell, its own pieces, on the CPU and without the
program: the tiered line's parser on the line as the CLI prints it; the
comparison ``pyeval-prefix-plus-pinned-tiered`` on recorded answers (a
sound one; one with the line missing, as a check without ``-hbm-budget``
prints; one overridden; one whose hot tier passed the ceiling; one that
evicted nothing; a degraded one); the eviction roofline's bytes on
hand-counted slots; every new metric has a reader, and the counter-fed
ones report nothing on a parent's result."""

import json
import os

import pytest

from benchmark import run
from benchmark.lib import plug, program_spans, reference, spill_bytes

CELL = "cli-complete-spill"
MANIFEST = os.path.join(run.ROOT, "BENCHMARK.json")
LINE = (
    "Tiered store: budget 134217728 B (table <= 4194304 slots, rows <= "
    "4194304, logs <= 4194304), hot tier peak 1966080 keys (20.8% of "
    "9445152), 7 evictions of 8215524 keys, 9012345 cold lookups (1203456 "
    "already visited), 8201235 rows spilled, budget overridden: no.")


def loaded():
    _man, _cell, config, traffic = run.load_cell(MANIFEST, CELL)
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    return config, traffic


# the cell's own budget, ceilings and count (the rung ISSUE 41's rule
# left it on), and a sound check's output at them
BUDGET = loaded()[0]["budget"]
STATES = next(iter(loaded()[0]["bindings"].values()))["states"]
HOT = BUDGET["hot_keys_max"]
SOUND = (
    f"Tiered store: budget {BUDGET['bytes']} B (table <= "
    f"{BUDGET['table_slots']} slots, rows <= {BUDGET['rows']}, logs <= "
    f"{BUDGET['logs']}), hot tier peak {HOT - 9} keys (20.8% of {STATES}), "
    "7 evictions of 8215524 keys, 9012345 cold lookups (1203456 already "
    "visited), 8201235 rows spilled, budget overridden: no.")
VERDICT = (f"{STATES} distinct states found, search depth (diameter) 24.\n"
           "Finished in 41.0s (230369 distinct states/sec).\n")


@pytest.fixture(scope="module")
def sizes():
    """The reference's prefix, searched once (4 s), and the stored
    levels after it."""
    config, traffic = loaded()
    base = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    prefix, stored = base.wanted_sizes(config, traffic)
    return prefix + stored


def answer(sizes, line=SOUND, stats=None):
    return {"rc": 0, "text": VERDICT + (line + "\n" if line else ""),
            "level_sizes": list(sizes), "stats": stats or {}}


def wrong_names(answers, monkeypatch, sizes):
    """The names of the checks that came out wrong, with the reference's
    prefix, searched once for the module, handed back to every load of
    ``pyeval-prefix-plus-pinned``."""
    config, traffic = loaded()
    monkeypatch.setattr(
        reference, "bfs_levels",
        lambda c, max_levels=None, **kw: (sizes[:max_levels], None))
    mod = plug.load_file("comparisons", "pyeval-prefix-plus-pinned-tiered")
    return [c["name"] for c in mod.compare(config, traffic, answers, 7)
            if not c["ok"]]


# ---- the parser ----------------------------------------------------------

def test_the_line_is_read_back_number_for_number():
    got = spill_bytes.parse_tiered_line("x\n" + LINE + "\ny\n")
    assert got == {
        "budget": 134217728, "table": 4194304, "rows": 4194304,
        "logs": 4194304, "hot_peak": 1966080, "hot_pct": 20.8,
        "states": 9445152, "evictions": 7, "keys_evicted": 8215524,
        "lookups": 9012345, "hits": 1203456, "rows_spilled": 8201235,
        "overridden": False}
    yes = spill_bytes.parse_tiered_line(LINE.replace(": no.", ": yes."))
    assert yes["overridden"] is True


@pytest.mark.parametrize("text", [
    VERDICT,                                    # no line
    LINE + "\n" + LINE + "\n",                  # two
    LINE.replace("budget overridden: no.", ""),  # cut short
    LINE.replace("7 evictions", "seven evictions"),
])
def test_no_line_one_cut_short_or_two_reads_as_none(text):
    assert spill_bytes.parse_tiered_line(text) is None


# ---- the comparison, on recorded answers ---------------------------------

def test_a_sound_answer_is_correct(monkeypatch, sizes):
    assert sum(sizes) == STATES and len(sizes) == 24
    ok = answer(sizes, stats={"spill_degraded": False})
    assert wrong_names([ok, answer(sizes)], monkeypatch, sizes) == []


@pytest.mark.parametrize("line, stats, want", [
    (None, None, {"tiered_line_missing"}),
    (SOUND.replace(": no.", ": yes."), None, {"budget_overridden"}),
    (SOUND.replace(f"peak {HOT - 9}", f"peak {HOT + 1}"), None,
     {f"hot_tier_peak_over_{HOT}_of_{STATES}"}),
    (SOUND.replace(f"of {STATES})", f"of {STATES - 1})"), None,
     {f"hot_tier_peak_over_{HOT}_of_{STATES}"}),
    (SOUND.replace(f"{BUDGET['bytes']} B", f"{BUDGET['bytes'] + 1} B"), None,
     {f"budget_or_ceilings_differ_from_{BUDGET['bytes']}"}),
    (SOUND.replace(f"table <= {BUDGET['table_slots']}",
                   f"table <= {2 * BUDGET['table_slots']}"), None,
     {f"budget_or_ceilings_differ_from_{BUDGET['bytes']}"}),
    (SOUND.replace("7 evictions of 8215524", "0 evictions of 0"), None,
     {"nothing_evicted"}),
    (SOUND.replace("9012345 cold", "0 cold"), None, {"no_cold_lookup"}),
    (SOUND.replace("8201235 rows", "0 rows"), None, {"no_row_spilled"}),
    (SOUND, {"spill_degraded": True}, {"spill_degraded"}),
])
def test_one_guarantee_broken_is_not_correct(monkeypatch, sizes, line,
                                             stats, want):
    got = wrong_names([answer(sizes), answer(sizes, line, stats)],
                      monkeypatch, sizes)
    assert set(got) == want, got


def test_a_wrong_count_is_still_not_correct(monkeypatch, sizes):
    short = list(sizes)
    short[-1] -= 1
    got = wrong_names([answer(short)], monkeypatch, sizes)
    assert [g for g in got if g.startswith("level_sizes_differ_from_the_stored")]


# ---- the configuration ---------------------------------------------------

def test_the_configuration_states_its_budget_and_nine_guarantees():
    config, traffic = loaded()
    nine = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-9m.json"))
    assert config["program"] == nine["program"]
    for k in ("state_words", "key_columns", "key_bits", "key_exact",
              "successor_lanes"):
        assert config["shapes"][k] == nine["shapes"][k], k
    g = config["guarantees"]
    assert len(g) == 9 and g[:4] == nine["guarantees"][:4]
    assert g[5] == nine["guarantees"][5]
    assert BUDGET["hot_keys_max"] == BUDGET["table_slots"] // 2
    assert round(100.0 * HOT / STATES, 1) == BUDGET["hot_share_pct"]
    pinned = config["reference"]["pinned_level_sizes"]
    assert sorted(int(k) for k in pinned) == list(range(
        config["reference"]["prefix_levels"] + 1, 25))
    i = traffic["argv"].index("-hbm-budget")
    assert traffic["argv"][i + 1] == BUDGET["flag"].split()[1]
    assert store_bytes(traffic["argv"][i + 1]) == BUDGET["bytes"]
    assert traffic["argv"][:i] == [
        "check", "specs/compaction.tla", "-config", traffic["cfg"]]
    assert list(config["bindings"]) == [traffic["cfg"]]
    assert set(config["reduced"]) == {"binding", "hbm_budget"}
    kinds = [c["kind"] for c in config["control"]["complete"]["controls"]]
    assert kinds == ["narrow-fingerprint-reference", "program-no-budget"]


def store_bytes(text):
    return int(text[:-1]) << {"K": 10, "M": 20, "G": 30}[text[-1]]


# ---- the roofline's bytes ------------------------------------------------

CHECK = {"spill_evict_slots": 10 * (1 << 22), "spill_keys_evicted": 9089816}
MOVED = 4 * (2 * 10 * (1 << 22) * 3 + 9089816 * 2)


def made_up_ctx(evict_s, checks=(CHECK,)):
    return {
        "out": {"answers": [{"stats": c, "level_sizes": [1] * 24}
                            for c in checks],
                "stats": {"checks": list(checks)}},
        "config": {"shapes": {"key_columns": 2}},
        "peaks": {"hbm_bytes_per_s": 819e9},
        program_spans.CACHE_KEY: {
            "device_planes": 1, "scoped": True,
            "scope_s": {"spill_evict": evict_s, "probe": 9.0}},
    }


def test_evict_bytes_are_the_table_read_and_written_and_the_keys_once():
    assert spill_bytes.evict_bytes(CHECK, 2) == MOVED == 1079351488
    assert spill_bytes.evict_bytes({}, 2) is None  # a parent's result
    assert spill_bytes.evict_bytes({"spill_evict_slots": 0}, 2) is None
    assert spill_bytes.window_evict_bytes(
        made_up_ctx(1.0, (CHECK, CHECK))) == 2 * MOVED
    assert spill_bytes.window_evict_bytes(made_up_ctx(1.0, ({},))) is None


def test_spill_evict_hbm_pct_arithmetic():
    read = plug.load_file("layer_metrics", "spill_evict_hbm_pct").read
    want = 100.0 * MOVED / 2.0 / 819e9
    assert read(made_up_ctx(2.0), {}) == pytest.approx(want)
    assert 0.0 < want < 100.0
    assert read(made_up_ctx(0.0), {}) is None
    assert read({**made_up_ctx(2.0), "peaks": {}}, {}) is None
    assert read(made_up_ctx(2.0, ({},)), {}) is None


def test_the_ratios_read_their_counters():
    st = {"spill_syncs": 1953, "spill_misses_resolved": 8822615,
          "spill_miss_hits": 1347818, "spill_d2h_bytes": 200 << 20,
          "spill_d2h_padded_bytes": 300 << 20}
    ctx = made_up_ctx(1.0, (st,))
    assert run.read_layer_metric("spill_syncs_per_level", ctx) == 1953 / 24
    assert run.read_layer_metric("spill_miss_hit_pct", ctx) == \
        pytest.approx(100.0 * 1347818 / 8822615)
    assert run.read_layer_metric("spill_d2h_gb", ctx) == (300 << 20) / 1e9


# ---- every new metric has a reader ---------------------------------------

def new_metrics():
    man = run.read_json(MANIFEST)
    return [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]


def test_the_cell_names_27_metrics_of_its_own():
    assert len(new_metrics()) == 27


@pytest.mark.parametrize("name", new_metrics())
def test_every_new_metric_has_a_reader_that_reads_nothing_on_a_parent(name):
    """A parent's result has none of the counters and its trace none of
    the scopes: the reader returns None and does not raise."""
    ctx = {
        "out": {"answers": [{"stats": {"host_grow_s": 1.0},
                             "level_sizes": [1, 2]}],
                "stats": {"checks": [{}]}},
        "config": {"shapes": {"key_columns": 2}}, "peaks": {},
        "trace": None, "compiles": None, "memory_peak_bytes": 0,
        program_spans.CACHE_KEY: None,
    }
    assert run.read_layer_metric(name, ctx) is None


# ---- the tiny fixture cell, through the harness ---------------------------

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.spill.test.json")
ON_CPU = (
    "host_spill_s.spill", "host_dispatch_s.spill", "host_fetch_wait_s.spill",
    "host_grow_s.spill", "host_unaccounted_s.spill", "spill_lookup_s",
    "spill_fetch_s", "spill_blocked_s", "spill_overlap_ratio",
    "spill_syncs_per_level", "dispatches_per_level.spill",
    "jit_host_s.spill", "jit_body_traces.spill", "compiles_in_window.spill",
    "spill_hot_share_max_pct", "spill_miss_hit_pct", "spill_d2h_gb",
    "spill_bytes_per_state",
)


def test_the_fixture_cell_is_correct_and_reads_its_counters():
    """The real cell's driver, comparison and readers on the shipped
    45,198-state binding under ``-hbm-budget 4M``."""
    r = run.run_cell(FIX, CELL, 2147483659, 4.0, 1, require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0, r
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ON_CPU:
        assert isinstance(m[name], (int, float)), name
    assert m["spill_hot_share_max_pct"] == pytest.approx(38.7097)
    assert m["compiles_in_window.spill"] == 0
    assert abs(m["host_unaccounted_s.spill"]) < 0.05
    # the CPU's stand-in device plane carries no ptt. scope
    assert "stage_device_s.spill_evict" not in m
    assert "spill_evict_hbm_pct" not in m


def test_both_controls_are_not_correct_each_on_its_own_line():
    from benchmark import control

    rs = control.run_control(FIX, CELL, [0, 1], 40.0, False)
    assert [r["correct"] for r in rs] == [False, False], rs
    for r in rs:
        assert "tiered_line_missing" in [w["name"] for w in r["wrong"]]
