"""The one-chip 9.4M-state cell's own pieces, on the CPU: the tiny
fixture cell (``fixtures/BENCHMARK.cli9m.test.json``: the real cell's
driver, comparison, control and per-layer readers on the 253,361-state
binding, the smallest shipped one that crosses growth tiers from the
CLI's start) comes out correct as it stands and not correct with one
level size altered, in the part the reference searches and in the part
the configuration stores; its control comes out not correct; a program
without growth counters (the parent commit's) makes the counter-fed
readers report nothing; and the rehash roofline's arithmetic is held to
made-up numbers."""

import os

import pytest

from benchmark import control, run
from benchmark.lib import grow_bytes, plug, program_spans

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.cli9m.test.json")
ON_CPU = (
    "compiles_in_window.cli9m", "dispatches_per_level.cli9m",
    "host_dispatch_s.cli9m", "host_grow_s.cli9m",
    "host_fetch_wait_s.cli9m", "host_unaccounted_s.cli9m",
    "jit_host_s.cli9m", "jit_body_traces.cli9m",
    "fpset_lanes_presented_per_valid.cli9m", "grow_events.cli9m",
    "grow_wall_max_s.cli9m",
)
COUNTER_FED = ("grow_events.cli9m", "grow_wall_max_s.cli9m",
               "rehash_hbm_pct")


def cell(trace):
    return run.run_cell(FIX, "cli-complete-9m", 2147483659, 4.0, trace,
                        require_tpu=False)


def test_sound_cell_is_correct_and_reads_its_counters():
    r = cell(trace=1)
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] >= 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ON_CPU:
        assert isinstance(m[name], (int, float)), name
    # 2^17 -> 2^21 slots: four doublings, each an event of its own
    assert m["grow_events.cli9m"] == 4
    assert 0.0 < m["grow_wall_max_s.cli9m"] <= m["host_grow_s.cli9m"]
    assert m["jit_body_traces.cli9m"] == 0  # the set-up's check built them
    assert abs(m["host_unaccounted_s.cli9m"]) < 0.05
    # the CPU's stand-in device plane carries no ptt. scope and the CPU
    # has no row in the peaks table
    for name in ("stage_device_s.rehash.cli9m", "stage_device_s.probe.cli9m",
                 "device_unscoped_pct.cli9m", "rehash_hbm_pct"):
        assert name not in m, name


@pytest.mark.parametrize("level", [5, 15])
def test_one_level_size_altered_is_not_correct(monkeypatch, level):
    """Level 5 is one the reference searches in the run, level 15 one
    whose size the configuration stores (``pyeval-prefix-plus-pinned``,
    the fixture's prefix is 12 levels)."""
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    sound = DeviceChecker._log

    def log(self, msg):
        sound(self, msg.replace(f"level {level}: +", f"level {level}: +1"))

    monkeypatch.setattr(DeviceChecker, "_log", log)
    r = cell(trace=0)
    assert r["correct"] is False, r
    assert set(r["metrics"]) == {"verdict_s", "setup_s"}


def test_stored_levels_follow_the_prefix():
    _man, _cell, config, traffic = run.load_cell(FIX, "cli-complete-9m")
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    mod = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    prefix, stored = mod.wanted_sizes(config, traffic)
    assert len(prefix) == 12 and len(stored) == 11
    assert sum(prefix) + sum(stored) == 253361


def test_the_real_cells_stored_levels_are_the_four_worker_cells():
    """``compaction-9m`` copies levels 11-24 of the binding from
    ``compaction-9m-workers4``: one binding, one set of numbers."""
    real = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-9m.json"))
    w4 = run.read_json(os.path.join(
        run.ROOT, "benchmark", "configs", "compaction-9m-workers4.json"))
    assert real["reference"]["pinned_level_sizes"] == \
        w4["reference"]["pinned_level_sizes"]
    assert real["reference"]["prefix_levels"] == 10
    assert real["shapes"] == w4["shapes"]
    assert real["guarantees"] == w4["guarantees"][:6]
    assert 658326 + sum(
        real["reference"]["pinned_level_sizes"].values()
    ) == real["bindings"]["specs/compaction_9m.cfg"]["states"]


def test_control_narrow_fingerprint_is_not_correct():
    rs = control.run_control(FIX, "cli-complete-9m", [1, 2147483659], 40.0,
                             False)
    assert [r["correct"] for r in rs] == [False, False], rs


# ---- the counter-fed readers, on made-up contexts ----------------------

CHECK = {"grow_rehash_slots": (1 << 25) - (1 << 17), "grow_events": 8,
         "grow_wall_max_s": 19.5}
MOVED = ((1 << 25) - (1 << 17)) * 2 * 4 * 3


def made_up_ctx(rehash_s, checks=(CHECK, CHECK)):
    return {
        "out": {"answers": [{"stats": c} for c in checks],
                "stats": {"checks": list(checks)}},
        "config": {"shapes": {"key_columns": 2}},
        "peaks": {"hbm_bytes_per_s": 819e9},
        program_spans.CACHE_KEY: {
            "device_planes": 1, "scoped": True,
            "scope_s": {"rehash": rehash_s, "probe": 9.0}},
    }


def test_rehash_bytes_are_old_slots_read_once_and_twice_that_written():
    assert grow_bytes.rehash_bytes(CHECK, 2) == MOVED == 802160640
    assert grow_bytes.rehash_bytes({}, 2) is None  # a parent's result
    assert grow_bytes.window_bytes(made_up_ctx(1.0)) == 2 * MOVED
    assert grow_bytes.window_bytes(made_up_ctx(1.0, ({}, CHECK))) == MOVED
    assert grow_bytes.window_bytes(made_up_ctx(1.0, ({},))) is None


def test_rehash_hbm_pct_arithmetic():
    read = plug.load_file("layer_metrics", "rehash_hbm_pct").read
    # two checks' doublings over 60 device seconds against 819 GB/s
    want = 100.0 * 2 * MOVED / 60.0 / 819e9
    assert read(made_up_ctx(60.0), {}) == pytest.approx(want)
    assert 0.0 < want < 0.01  # a probe, bound by latency: far under 1
    assert grow_bytes.share_pct(819e9, 1.0, 819e9) == 100.0
    # nothing to read: no rehash traced, or a run off the chip
    assert read(made_up_ctx(0.0), {}) is None
    assert read({**made_up_ctx(60.0), "peaks": {}}, {}) is None


@pytest.mark.parametrize("name", COUNTER_FED)
def test_a_program_without_growth_counters_reports_nothing(name):
    """The parent commit's result event has no ``grow_*`` key: each
    counter-fed reader returns None and does not raise."""
    ctx = made_up_ctx(60.0, ({"host_grow_s": 33.5}, {}))
    assert run.read_layer_metric(name, ctx) is None
