"""The comparisons shown to fail: each control comes out not correct, and
a run whose timed path is broken underneath comes out not correct, while
the sound run of the same tiny cell comes out correct.

These drive ``run.run_cell`` with ``require_tpu=False`` (the harness's own
test hook; the command line cannot set it) on a pair of tiny cells under
``fixtures/`` that the CPU can hold: the same drivers, comparisons and
readers as the real cells, found through the same kind of manifest.
"""

import os

import pytest

from benchmark import control, run
from benchmark.lib import reference, tlafmt
from benchmark.ref import pyeval as pe

ROOT = run.ROOT
TINY = os.path.join(ROOT, "benchmark", "tests", "fixtures", "BENCHMARK.test.json")
REAL = os.path.join(ROOT, "BENCHMARK.json")
SEEDS = [1, 77, 2147483659]


def cell(name, trace=0, seconds=60.0, seed=2147483659):
    return run.run_cell(TINY, name, seed, seconds, trace, require_tpu=False)


def test_selfcheck_recomputes_the_recorded_trace():
    assert run.selfcheck() == 0


def test_refuses_without_a_tpu():
    with pytest.raises(run.Refused, match="no TPU"):
        run.run_cell(TINY, "cli-leak-trace", 1, 5.0, 0)


def test_cfg_and_trace_text_readers():
    c = tlafmt.constants_from_cfg(os.path.join(ROOT, "specs", "compaction.cfg"))
    assert c == pe.SHIPPED_CFG
    c2 = tlafmt.constants_from_cfg(
        os.path.join(ROOT, "specs", "compaction_253k.cfg"))
    assert (c2.model_producer, c2.retain_null_key) == (True, False)
    # the program's own renderer writes the format the benchmark parses
    from pulsar_tlaplus_tpu.utils.render import render_trace

    ref = pe.check(c, invariants=("CompactedLedgerLeak",))
    names = [pe.ACTION_NAMES[a] for a in ref.trace_actions]
    text = ("Error: Invariant CompactedLedgerLeak is violated.\n"
            + render_trace(ref.trace, names, c))
    assert tlafmt.parse_trace(text, c.compaction_times_limit) == (
        "CompactedLedgerLeak", ref.trace, names)


def test_level_sizes_from_the_cli_progress_lines():
    from benchmark.lib import plug

    read = plug.load_file("drivers", "repeat-cli").level_sizes_from_progress
    text = ("  level 2: +10 (total 739, 1 st/s)\n  level start: nf=3\n"
            "  level 3: +99 (total 838, 15 st/s)\n")
    assert read(text) == [729, 10, 99]
    assert read(text.replace("level 3", "level 4")) is None
    assert read("") is None


# ------------------------------------------------------------- controls


def test_control_narrow_fingerprint_reference_is_not_correct():
    # the real cell's size: 253,361 states are seconds of Python
    rs = control.run_control(REAL, "cli-complete", SEEDS, 40.0, False)
    assert [r["correct"] for r in rs] == [False] * len(SEEDS), rs
    assert all(
        any(w["name"].startswith("distinct_states") for w in r["wrong"])
        for r in rs)


def test_control_program_simulate_is_not_correct():
    rs = control.run_control(TINY, "cli-leak-trace", SEEDS, 40.0, False)
    assert [r["correct"] for r in rs] == [False] * len(SEEDS), rs


def test_control_narrow_fingerprint_program_is_not_correct():
    rs = control.run_control(TINY, "scaled-window", SEEDS, 60.0, False)
    assert [r["correct"] for r in rs] == [False] * len(SEEDS), rs
    # it ran through the window and fails the comparison on counts
    for r in rs:
        names = {w["name"] for w in r["wrong"]}
        assert "control_crashed" not in names, r
        assert "seed_level_sizes" in names, r
        assert any(n.startswith("level_") and n.endswith("_size")
                   for n in names), r


# ------------------------------------- sound runs, and broken timed paths


@pytest.mark.parametrize(
    "name", ["scaled-window", "cli-complete", "cli-leak-trace"])
def test_sound_tiny_cell_is_correct(name):
    r = cell(name, trace=1, seconds=8.0 if name.startswith("cli") else 60.0)
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"] and "breakdown" in r


def _break_level_size(monkeypatch):
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    sound = DeviceChecker._result

    def result(self, t0, nv, level_sizes, bufs, *a, **kw):
        sizes = list(level_sizes)
        sizes[4] -= 1  # one state of level 5 lost where the answer is made
        return sound(self, t0, nv - 1, sizes, bufs, *a, **kw)

    monkeypatch.setattr(DeviceChecker, "_result", result)


def _break_lane_log(monkeypatch):
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    sound = DeviceChecker._result

    def result(self, t0, nv, level_sizes, bufs, *a, **kw):
        bufs = dict(bufs, lane=bufs["lane"] * 0)  # every step "lane 0"
        return sound(self, t0, nv, level_sizes, bufs, *a, **kw)

    monkeypatch.setattr(DeviceChecker, "_result", result)


def _break_count(monkeypatch):
    from pulsar_tlaplus_tpu import cli

    sound = cli._report

    def report(r, *a, **kw):
        r.distinct_states -= 1
        return sound(r, *a, **kw)

    monkeypatch.setattr(cli, "_report", report)


def _break_one_level(monkeypatch):
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    sound = DeviceChecker._log

    def log(self, msg):
        # count and diameter stay right; one level is reported a state
        # short where the CLI prints its progress
        sound(self, msg.replace("level 5: +", "level 5: +1"))

    monkeypatch.setattr(DeviceChecker, "_log", log)


def _break_trace(monkeypatch):
    from pulsar_tlaplus_tpu.utils import render

    sound = render.render_trace

    def render_trace(trace, actions, c):
        # a state dropped where the counterexample is printed
        return sound(trace[:4] + trace[5:], actions[:4] + actions[5:], c)

    monkeypatch.setattr(render, "render_trace", render_trace)


@pytest.mark.parametrize("name,breaker", [
    ("scaled-window", _break_level_size),
    ("scaled-window", _break_lane_log),
    ("cli-complete", _break_count),
    ("cli-complete", _break_one_level),
    ("cli-leak-trace", _break_trace),
], ids=["level-size", "lane-log", "cli-count", "cli-one-level", "cli-trace"])
def test_broken_timed_path_is_not_correct(monkeypatch, name, breaker):
    breaker(monkeypatch)
    r = cell(name, seconds=8.0 if name.startswith("cli") else 60.0)
    assert r["correct"] is False, r
