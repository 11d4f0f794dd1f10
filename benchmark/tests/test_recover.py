"""The survivability cell, its own pieces, on the CPU: the recovered
line's parser on the line as the CLI prints it; the two legs' progress
lines joined; the comparison ``pyeval-prefix-plus-pinned-recover`` on
recorded cycles (a sound one, and one for each clause it holds a cycle
to); the cluster manager's stream; the cycle's counters from its legs';
every metric of the cell has a reader; the tiny fixture cell through
the harness, traced, and its two controls."""

import copy
import io
import os
import signal

import pytest

from benchmark import run
from benchmark.lib import ckpt_bytes, plug, program_spans, reference

CELL = "cli-recover-kill"
MANIFEST = os.path.join(run.ROOT, "BENCHMARK.json")
LINE = ("Recovered from the checkpoint frame of level 18 (2402570 states): "
        "6 levels expanded after it.")
DRIVER = plug.load_file("drivers", "repeat-cli-recover")


def loaded():
    _man, _cell, config, traffic = run.load_cell(MANIFEST, CELL)
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    return config, traffic


STATES = next(iter(loaded()[0]["bindings"].values()))["states"]
KILL = loaded()[0]["survivability"]["kill_at_level"]
CADENCE = loaded()[0]["survivability"]["cadence_levels"]
VERDICT = (f"{STATES} distinct states found, search depth (diameter) 24.\n"
           "Finished in 21.0s (183935 distinct states/sec).\n")
WARNING = ("WARNING: search preempted (SIGTERM/SIGINT) — a resumable "
           "checkpoint frame is on disk; continue with -recover.\n")


@pytest.fixture(scope="module")
def sizes():
    """The reference's prefix, searched once (4 s), and the stored
    levels after it."""
    config, traffic = loaded()
    base = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    prefix, stored = base.wanted_sizes(config, traffic)
    return prefix + stored


def rows_of(sizes, lo, hi):
    """Progress rows of the levels ``lo..hi`` (from 2)."""
    return [(k, sizes[k - 1], sum(sizes[:k])) for k in range(lo, hi + 1)]


def cycle(sizes, level=KILL, traced=True):
    """A sound cycle as the driver records it: killed at ``level``."""
    n = len(sizes)
    frames1 = level // CADENCE + 1
    leg1 = {"rc": 3, "text": WARNING, "wall_s": 14.0,
            "progress": rows_of(sizes, 2, level), "last_level": level,
            "killed_at": level,
            "stats": {"ckpt_frames": frames1, "ckpt_retries": 0,
                      "hbm_recovered": 0} if traced else {}}
    line = (f"Recovered from the checkpoint frame of level {level} "
            f"({sum(sizes[:level])} states): {n - level} levels expanded "
            "after it.\n")
    leg2 = {"rc": 0, "text": VERDICT + line, "wall_s": 7.0,
            "progress": rows_of(sizes, level + 1, n), "last_level": n,
            "stats": {"ckpt_frames": 1, "ckpt_retries": 0,
                      "hbm_recovered": 0} if traced else {}}
    return {"rc": 0, "rcs": [3, 0], "text": leg2["text"], "wall_s": 21.0,
            "level_sizes": ckpt_bytes.joined_level_sizes(
                leg1["progress"], leg2["progress"]),
            "legs": [leg1, leg2], "frame_after_leg1": True,
            "engine_wall_s": None, "stats": {}}


def wrong_names(answers, monkeypatch, sizes):
    config, traffic = loaded()
    monkeypatch.setattr(
        reference, "bfs_levels",
        lambda c, max_levels=None, **kw: (sizes[:max_levels], None))
    mod = plug.load_file("comparisons", "pyeval-prefix-plus-pinned-recover")
    return [c["name"] for c in mod.compare(config, traffic, answers, 7)
            if not c["ok"]]


# ---- the parser and the join ---------------------------------------------

def test_the_line_is_read_back_number_for_number():
    assert ckpt_bytes.parse_recovered_line("x\n" + LINE + "\ny\n") == {
        "level": 18, "states": 2402570, "levels_run": 6}


@pytest.mark.parametrize("text", [
    VERDICT,                                  # no line
    LINE + "\n" + LINE + "\n",                # two
    LINE.replace(" after it.", ""),           # cut short
    LINE.replace("level 18", "level eighteen"),
])
def test_no_line_one_cut_short_or_two_reads_as_none(text):
    assert ckpt_bytes.parse_recovered_line(text) is None


def test_the_line_is_the_programs(sizes):
    from pulsar_tlaplus_tpu import cli

    st = {"resume_level": 18, "resume_states": 2402570,
          "resume_levels_run": 6}
    assert cli.recovered_line(st) == LINE
    assert sum(sizes[:18]) == 2402570  # the cell's own frame


def test_two_legs_tell_one_search(sizes):
    join = ckpt_bytes.joined_level_sizes
    n = len(sizes)
    assert join(rows_of(sizes, 2, 18), rows_of(sizes, 19, n)) == sizes
    # a second leg that started afresh tells the search alone
    assert join(rows_of(sizes, 2, 18), rows_of(sizes, 2, n)) == sizes
    # a level of the frame expanded again: the first leg's is dropped
    assert join(rows_of(sizes, 2, 18), rows_of(sizes, 18, n)) == sizes
    assert join(rows_of(sizes, 2, 17), rows_of(sizes, 19, n)) is None
    assert join([], []) is None
    assert join(rows_of(sizes, 2, n), []) == sizes


# ---- the comparison, on recorded cycles ----------------------------------

def test_a_sound_cycle_is_correct(monkeypatch, sizes):
    assert sum(sizes) == STATES and len(sizes) == 24
    ok = [cycle(sizes), cycle(sizes, traced=False), cycle(sizes, 20)]
    assert wrong_names(ok, monkeypatch, sizes) == []


def broken(sizes, how):
    a = cycle(sizes)
    leg1, leg2 = a["legs"]
    if how == "leg1_rc":
        leg1["rc"] = 0
    elif how == "no_warning":
        leg1["text"] = ""
    elif how == "no_frame":
        a["frame_after_leg1"] = False
    elif how == "leg2_rc":
        leg2["rc"] = 1
    elif how == "no_line":
        a["text"] = leg2["text"] = VERDICT
    elif how == "early":
        # killed, framed and resumed a level early
        return cycle(sizes, KILL - 1)
    elif how == "other_level":
        a["text"] = leg2["text"] = leg2["text"].replace(
            f"level {KILL} ", f"level {KILL + 1} ")
    elif how == "states":
        a["text"] = leg2["text"] = leg2["text"].replace(
            f"({sum(sizes[:KILL])} states)",
            f"({sum(sizes[:KILL]) - 1} states)")
    elif how == "again":
        leg2["progress"] = rows_of(sizes, KILL, len(sizes))
    elif how == "retry":
        leg1["stats"]["ckpt_retries"] = 1
    elif how == "hbm":
        leg2["stats"]["hbm_recovered"] = 1
    elif how == "frames1":
        leg1["stats"]["ckpt_frames"] -= 1
    elif how == "frames2":
        leg2["stats"]["ckpt_frames"] = 0
    return a


@pytest.mark.parametrize("how, want", [
    ("leg1_rc", {"leg1_exit_code_not_3"}),
    ("no_warning", {"leg1_names_no_resumable_frame"}),
    ("no_frame", {"no_frame_file_after_leg1"}),
    ("leg2_rc", {"leg2_exit_code_not_0"}),
    ("no_line", {"not_resumed"}),
    ("early", {f"resume_level_not_leg1's_last_or_under_{KILL}"}),
    ("other_level", {
        f"resume_level_not_leg1's_last_or_under_{KILL}",
        "resume_states_differ_from_the_reference's_at_that_level",
        "a_level_the_frame_closed_was_expanded_again"}),
    ("states", {"resume_states_differ_from_the_reference's_at_that_level"}),
    ("again", {"a_level_the_frame_closed_was_expanded_again"}),
    ("retry", {"ckpt_retries_or_hbm_recovered"}),
    ("hbm", {"ckpt_retries_or_hbm_recovered"}),
    ("frames1", {f"leg1_frames_not_one_every_{CADENCE}_levels_and_the_"
                 "suspend_frame"}),
    ("frames2", {"leg2_wrote_no_frame"}),
])
def test_one_guarantee_broken_is_not_correct(monkeypatch, sizes, how, want):
    got = wrong_names([cycle(sizes), broken(sizes, how)], monkeypatch, sizes)
    assert set(got) == want, got


def test_a_wrong_count_is_still_not_correct(monkeypatch, sizes):
    short = list(sizes)
    short[-1] -= 1
    a = cycle(sizes)
    a["level_sizes"] = short
    got = wrong_names([a], monkeypatch, sizes)
    assert [g for g in got
            if g.startswith("level_sizes_differ_from_the_stored")]
    # an answer that is no cycle (the reference's, from a control)
    plain = {"rc": 0, "text": VERDICT, "level_sizes": list(sizes)}
    assert set(wrong_names([plain], monkeypatch, sizes)) == {
        "answers_that_are_no_cycle"}


# ---- the configuration ---------------------------------------------------

def test_the_configuration_states_its_cycle_and_ten_guarantees():
    config, traffic = loaded()
    tiered = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-tiered.json"))
    nine = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-9m.json"))
    assert config["program"] == tiered["program"]
    assert config["shapes"] == tiered["shapes"]
    assert config["bindings"] == tiered["bindings"]
    for k in ("prefix_levels", "pinned_level_sizes"):
        assert config["reference"][k] == tiered["reference"][k]
    assert "budget" not in config
    g = config["guarantees"]
    assert len(g) == 10 and g[:6] == nine["guarantees"]
    sv = config["survivability"]
    assert (sv["cadence_levels"], sv["signal"]) == (5, "SIGTERM")
    assert traffic["kill"] == {"signal": sv["signal"],
                               "at_level": sv["kill_at_level"]}
    assert traffic["exit_codes"] == [3, 0] and traffic["exit_code"] == 0
    assert traffic["argv"] == [
        "check", "specs/compaction.tla", "-config", traffic["cfg"]]
    assert list(config["bindings"]) == [traffic["cfg"]]
    assert set(config["reduced"]) == {"binding"}
    kinds = [c["kind"] for c in config["control"]["complete"]["controls"]]
    assert kinds == ["narrow-fingerprint-reference",
                     "program-recover-dropped"]
    assert hasattr(signal, sv["signal"])


# ---- the cluster manager's stream ----------------------------------------

def armed(fn):
    """Run ``fn`` with a handler on SIGTERM that counts."""
    got = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: got.append(s))
    try:
        fn()
    finally:
        signal.signal(signal.SIGTERM, prev)
    return got


def test_the_signal_goes_out_once_at_the_first_whole_line_at_the_level():
    err = DRIVER.KillAtLevel(18, signal.SIGTERM)

    def lines():
        print("  level 17: +5 (total 9, 100 st/s)", file=err)
        assert err.sent_at is None
        err.write("  level 19: +5 (total 14,")  # no whole line yet
        assert err.sent_at is None
        err.write(" 100 st/s)\n")
        assert err.sent_at == 19
        print("  level 20: +5 (total 19, 100 st/s)", file=err)

    assert armed(lines) == [signal.SIGTERM] and err.sent_at == 19
    assert "level 20" in err.getvalue() and not err.unarmed


def test_no_signal_goes_out_while_no_handler_is_armed():
    assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL
    err = DRIVER.KillAtLevel(3, signal.SIGTERM)
    print("  level 3: +5 (total 9, 100 st/s)", file=err)
    assert err.unarmed and err.sent_at is None


# ---- a cycle's counters --------------------------------------------------

def test_a_cycles_counters_are_its_legs_added():
    st1 = {"host_ckpt_s": 9.0, "ckpt_npz_s": 8.0, "ckpt_frames": 4,
           "host_dispatch_s": 1.0, "dispatches_per_level": 2.0,
           "grow_wall_max_s": 0.5, "jit_body_traces": 0}
    st2 = {"host_ckpt_s": 4.0, "ckpt_npz_s": 3.5, "ckpt_frames": 1,
           "host_dispatch_s": 0.5, "dispatches_per_level": 0.5,
           "grow_wall_max_s": 0.7, "jit_body_traces": 0,
           "restore_s": 0.6, "resume_level": 18}
    legs = [{"stats": st1, "last_level": 18}, {"stats": st2, "last_level": 24}]
    got = DRIVER.cycle_stats(legs)
    assert got["host_ckpt_s"] == 13.0 and got["ckpt_npz_s"] == 11.5
    assert got["ckpt_frames"] == 5 and got["grow_wall_max_s"] == 0.7
    assert (got["restore_s"], got["resume_level"]) == (0.6, 18)
    # 36 + 12 dispatches over the search's 24 levels
    assert got["dispatches_per_level"] == pytest.approx(2.0)
    assert "ckpt_bytes" not in got  # in neither leg
    assert DRIVER.cycle_stats([{"stats": {}, "last_level": 18},
                               legs[1]]) == {}


def test_frame_bytes_are_32_a_state_at_the_cells_widths():
    assert ckpt_bytes.frame_bytes(1000, 2, 2) == 32000


# ---- every metric of the cell has a reader -------------------------------

def cell_metrics():
    man = run.read_json(MANIFEST)
    return [m["name"] for m in man["per_layer"]
            if CELL in m.get("workloads", ())]


def test_the_cell_names_3_metrics_of_its_own_and_reads_14_more():
    man = run.read_json(MANIFEST)
    own = [m["name"] for m in man["per_layer"]
           if m.get("workloads") == [CELL]]
    assert own == ["host_ckpt_s.recover", "ckpt_npz_s", "restore_s"]
    assert len(cell_metrics()) == 17
    assert len(man["per_layer"]) == 128  # the manifest's ceiling
    verdict = next(m for m in man["end_to_end"] if m["name"] == "verdict_s")
    assert verdict["workloads"][-1] == CELL


@pytest.mark.parametrize("name", cell_metrics())
def test_every_metric_has_a_reader_that_reads_nothing_where_nothing_is(name):
    """A result with none of the counters and a run with no trace: the
    reader returns None and does not raise."""
    ctx = {
        "out": {"answers": [{"stats": {}, "level_sizes": [1, 2]}],
                "stats": {"checks": [{}]}},
        "config": {"shapes": {"key_columns": 2, "state_words": 2}},
        "peaks": {}, "trace": None, "compiles": None,
        "memory_peak_bytes": 0, program_spans.CACHE_KEY: None,
    }
    assert run.read_layer_metric(name, ctx) is None


def test_the_three_readers_read_a_cycles_counters():
    st = {"host_ckpt_s": 13.0, "ckpt_gather_s": 1.0, "ckpt_pack_s": 0.5,
          "ckpt_npz_s": 11.4, "ckpt_frames": 5, "ckpt_states": 7000000,
          "ckpt_raw_bytes": 224000400, "ckpt_bytes": 150000000,
          "ckpt_d2h_bytes": 300000000, "restore_s": 0.8,
          "restore_load_s": 0.5, "restore_unpack_s": 0.1,
          "restore_upload_s": 0.2, "restore_h2d_bytes": 100000000,
          "resume_level": 18, "resume_states": 2402570,
          "resume_levels_run": 6}
    ctx = {"out": {"answers": [{"stats": st, "legs": [
        {"wall_s": 14.0}, {"wall_s": 7.0}]}], "stats": {"checks": [st]}},
        "config": {"shapes": {"key_columns": 2, "state_words": 2}}}
    assert run.read_layer_metric("host_ckpt_s.recover", ctx) == 13.0
    assert run.read_layer_metric("ckpt_npz_s", ctx) == 11.4
    assert run.read_layer_metric("restore_s", ctx) == 0.8


# ---- the tiny fixture cell, through the harness ---------------------------

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.recover.test.json")
ON_CPU = (
    "host_ckpt_s.recover", "ckpt_npz_s", "restore_s",
    "compiles_in_window.cli9m", "dispatches_per_level.cli9m",
    "host_dispatch_s.cli9m", "host_grow_s.cli9m", "host_fetch_wait_s.cli9m",
    "host_unaccounted_s.cli9m", "jit_host_s.cli9m", "jit_body_traces.cli9m",
    "grow_events.cli9m",
)


def test_the_fixture_cell_is_correct_and_reads_its_counters():
    """The real cell's driver, comparison and readers on the shipped
    45,198-state binding: SIGTERM at level 12, inside a ramp batch that
    ends on level 15's frame boundary."""
    r = run.run_cell(FIX, CELL, 2147483659, 3.0, 1, require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0, r
    assert r["attempted"] >= 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ON_CPU:
        assert isinstance(m[name], (int, float)), name
    assert 0 < m["ckpt_npz_s"] <= m["host_ckpt_s.recover"]
    assert m["jit_body_traces.cli9m"] == 0
    assert m["compiles_in_window.cli9m"] == 0
    # the CPU's stand-in device plane carries no ptt. scope
    assert "stage_device_s.probe.cli9m" not in m


def test_an_untraced_run_reports_a_cycles_wall():
    r = run.run_cell(FIX, CELL, 5, 1.0, 0, require_tpu=False)
    assert r["correct"] is True and r["attempted"] == 1
    assert set(r["metrics"]) == {"verdict_s", "setup_s"}


def test_both_controls_are_not_correct_each_on_its_own_line():
    from benchmark import control

    rs = control.run_control(FIX, CELL, [0, 1], 40.0, False)
    assert [r["correct"] for r in rs] == [False, False], rs
    assert "answers_that_are_no_cycle" in [
        w["name"] for w in rs[0]["wrong"]]
    # the program with -recover dropped: the exact count, found afresh
    assert [w["name"] for w in rs[1]["wrong"]] == ["not_resumed"]


def test_a_checkout_with_no_recovered_line_is_refused_at_once(monkeypatch):
    from pulsar_tlaplus_tpu import cli

    config, traffic = loaded()
    monkeypatch.delattr(cli, "recovered_line")
    drv = DRIVER.Driver(config, copy.deepcopy(traffic), run.ROOT,
                        run.WORK_DIR, 0, 0)
    with pytest.raises(SystemExit) as e:
        drv.setup(40.0)
    assert e.value.code not in (0, None) and "refused" in str(e.value.code)
