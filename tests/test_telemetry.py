"""Unified-telemetry tests (ISSUE r8): the structured JSONL event
stream, zero-sync device counters, the TLC-style progress heartbeat,
resume linking across kill->resume runs, frame-write stall accounting,
and the schema validator that gates BENCH artifacts."""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import FPM_N, DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import report, telemetry
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.utils import ckpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KW = dict(sub_batch=2048, visited_cap=1 << 16, frontier_cap=1 << 15)


def _shipped():
    return CompactionModel(pe.SHIPPED_CFG)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def checker_mod():
    return _load_script("check_telemetry_schema")


@pytest.fixture(scope="module")
def std_run(tmp_path_factory):
    """One telemetry-instrumented device run on the shipped config
    (with checkpointing), shared by the schema/report/counter tests."""
    tmp = tmp_path_factory.mktemp("tel")
    stream = str(tmp / "run.jsonl")
    frame = str(tmp / "run.npz")
    ck = DeviceChecker(
        _shipped(), telemetry=stream, checkpoint_path=frame,
        checkpoint_every=5, **KW,
    )
    r = ck.run()
    events = [json.loads(x) for x in open(stream)]
    return stream, frame, ck, r, events


# ---- stream schema ---------------------------------------------------


def test_stream_validates_and_has_lifecycle(std_run, checker_mod):
    """Every line parses and carries the base envelope; the stream has
    the run lifecycle: header, levels, per-flush records, checkpoint
    frames, and a result whose stats carry the zero-sync counters."""
    stream, _frame, ck, r, events = std_run
    assert r.distinct_states == 45198
    assert checker_mod.validate_stream(stream) == []
    kinds = {e["event"] for e in events}
    assert {"run_header", "level", "flush", "ckpt_frame", "result"} \
        <= kinds
    for e in events:
        assert e["v"] == telemetry.SCHEMA_VERSION
        assert isinstance(e["t"], (int, float))
        assert e["run_id"]
    # seq is strictly increasing within the stream
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    hdr = events[0]
    assert hdr["event"] == "run_header"
    assert hdr["engine"] == "device_bfs"
    res = events[-1]
    assert res["event"] == "result"
    assert res["distinct_states"] == 45198
    assert res["diameter"] == 20


def test_run_header_and_stats_carry_the_kernel_fields(std_run):
    """Streams, frames and warm artifacts written while the kernel
    stages had selectors carry ``visited_impl``, ``compact_impl``,
    ``probe_impl``, ``expand_impl`` and ``sieve_impl``; a new run
    writes all five at the one value each has left (v16 requires the
    last three on every header)."""
    _stream, _frame, ck, _r, events = std_run
    want = {
        "visited_impl": "fpset", "compact_impl": "logshift",
        "probe_impl": "legacy", "expand_impl": "legacy",
        "sieve_impl": "legacy",
    }
    assert telemetry.IMPL_FIELDS == want
    hdr = events[0]
    assert hdr["v"] == 16
    stats = [e for e in events if e["event"] == "result"][-1]["stats"]
    for k, v in want.items():
        assert hdr[k] == v, k
        assert stats[k] == v and ck.last_stats[k] == v, k


def test_zero_sync_counters_ride_the_stats_fetch(std_run):
    """The device counters vector carries flushes/rounds/failures/
    valid_lanes/max_rounds (FPM_N) and their aggregates agree between
    the stream's flush deltas and the final result stats — with no
    telemetry-specific fetches (one flush record per stats fetch at
    most)."""
    _stream, _frame, ck, r, events = std_run
    # r12: valid_lanes split into hi/lo uint32 words (int32-wrap fix);
    # PR 28: lane_rounds appended the same way; PR 37: this engine's
    # vector carries the rounds of each ladder step behind those, and
    # PR 42 the lanes kept from the column scatters in the word after
    assert fpset.FPM_N == 8 and FPM_N == 8 + fpset.FPM_STEPS + 1 == 16
    stats = [e for e in events if e["event"] == "result"][-1]["stats"]
    flushes = [e for e in events if e["event"] == "flush"]
    assert stats["fpset_flushes"] == sum(e["flushes"] for e in flushes)
    assert stats["fpset_probe_rounds"] == sum(
        e["probe_rounds"] for e in flushes
    )
    assert stats["fpset_valid_lanes"] == sum(
        e["valid_lanes"] for e in flushes
    )
    # every distinct state was a valid candidate lane once
    assert stats["fpset_valid_lanes"] >= r.distinct_states
    assert stats["fpset_max_probe_rounds"] >= 1
    assert 0.0 <= stats["fpset_duplicate_ratio"] < 1.0
    # dispatch counters ride for free (no PTT_STAGE_TIMING barrier).
    # Since r13 the level megakernel runs its flushes in-device: the
    # device flush count = stage-chain flush dispatches (the init
    # path) + the flushes the `fuse` records account per dispatch
    fuse_flushes = sum(
        e.get("flushes", 0)
        for e in events
        if e["event"] == "fuse"
    )
    assert (
        stats["stage_flush_n"] + fuse_flushes == stats["fpset_flushes"]
    )
    assert "stage_flush_s" not in stats  # timing stays legacy-only
    # flush records only ever ride an existing fetch
    assert len(flushes) <= stats["stats_fetches"]


def test_lane_rounds_ride_the_result_stats_and_the_stream_validates(
    std_run, checker_mod
):
    """The probe's presented-lane counter (PR 28) reaches ``last_stats``
    and the ``result`` event's free-form ``stats`` with no schema bump:
    the stream validates as before.  Every valid lane is presented at
    least once, and no flush presents more than the fixed schedule's
    ceiling of 10 accumulator widths."""
    stream, _frame, ck, _r, events = std_run
    stats = [e for e in events if e["event"] == "result"][-1]["stats"]
    for k in ("fpset_lane_rounds", "fpset_lanes_presented_per_valid"):
        assert stats[k] == ck.last_stats[k], k
    assert stats["fpset_lane_rounds"] >= stats["fpset_valid_lanes"]
    assert stats["fpset_lane_rounds"] <= (
        10 * ck.ACAP * stats["fpset_flushes"]
    )
    assert stats["fpset_lanes_presented_per_valid"] == round(
        stats["fpset_lane_rounds"] / stats["fpset_valid_lanes"], 4
    )
    assert checker_mod.validate_stream(stream) == []


def test_six_wide_fpm_frame_restores_zero_padded(std_run, tmp_path):
    """A frame written before PR 28 carries a 6-wide ``fpm``: it resumes
    with the old counters where they were and the new words at zero, to
    the published count."""
    import numpy as np

    _stream, frame, ck, _r, _events = std_run
    with np.load(frame) as d:
        arrays = {k: d[k] for k in d.files}
    assert arrays["fpm"].shape == (FPM_N,)
    old_flushes, old_valid = int(arrays["fpm"][0]), int(arrays["fpm"][3])
    arrays["fpm"] = arrays["fpm"][:6]
    narrow = str(tmp_path / "narrow.npz")
    np.savez_compressed(narrow, **arrays)
    ck2 = DeviceChecker(_shipped(), checkpoint_path=narrow, **KW)
    r2 = ck2.run(resume=True)
    assert r2.distinct_states == 45198 and r2.diameter == 20
    st = ck2.last_stats
    assert st["fpset_flushes"] == ck.last_stats["fpset_flushes"]
    assert st["fpset_valid_lanes"] == ck.last_stats["fpset_valid_lanes"]
    assert st["fpset_flushes"] > old_flushes
    # the new counter restarts at the frame: it covers the resumed part
    assert 0 < st["fpset_lane_rounds"] < ck.last_stats["fpset_lane_rounds"]
    assert st["fpset_lane_rounds"] >= st["fpset_valid_lanes"] - old_valid
    # so do the rounds by ladder step (PR 37): an 8-wide frame of PR
    # 28-36 restores the same way
    assert 0 < sum(st["fpset_step_rounds"]) < st["fpset_probe_rounds"]
    assert sum(ck.last_stats["fpset_step_rounds"]) == (
        ck.last_stats["fpset_probe_rounds"]
    )


def test_ckpt_frame_stall_accounting(std_run):
    """Frame writes record their write-stall seconds per frame and the
    run total lands in last_stats (the BENCH_r07 ckpt_write_s ask)."""
    _stream, frame, ck, r, events = std_run
    frames = [e for e in events if e["event"] == "ckpt_frame"]
    assert frames and os.path.exists(frame)
    for i, e in enumerate(frames):
        assert e["frame_seq"] == i + 1
        assert e["bytes"] > 0
        assert e["write_s"] >= 0.0
        assert e["stall_s"] >= e["write_s"]
    assert ck.last_stats["ckpt_frames"] == len(frames)
    assert ck.last_stats["ckpt_write_s"] >= sum(
        e["write_s"] for e in frames
    ) * 0.5  # rounding slack


def test_frame_meta_roundtrip(tmp_path):
    p = str(tmp_path / "f.npz")
    import numpy as np

    nbytes, write_s, retries = ckpt.save_frame(
        p, "sig", {"a": np.arange(3)},
        meta={"run_id": "abc", "frame_seq": 7},
    )
    assert nbytes > 0 and write_s >= 0.0 and retries == 0
    d = ckpt.load_frame(p, "sig")
    assert ckpt.frame_meta(d) == {"run_id": "abc", "frame_seq": 7}
    # frames without meta read back as {}
    nbytes, _, _ = ckpt.save_frame(p, "sig", {"a": np.arange(3)})
    assert ckpt.frame_meta(ckpt.load_frame(p, "sig")) == {}


# ---- kill -> resume stream linking -----------------------------------


def _run_sub(args, fault=None, expect_kill=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PTT_FAULT", None)
    if fault:
        env["PTT_FAULT"] = fault
    proc = subprocess.run(
        [sys.executable, "-m", "tests._survivable_run", *args],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=ROOT,
    )
    if expect_kill:
        assert proc.returncode == 137, (
            proc.returncode, proc.stdout, proc.stderr,
        )
        return None
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_kill_resume_stream_links_prior_frame(tmp_path, checker_mod):
    """A killed run's stream ends with a fault breadcrumb and complete
    frames; the resumed run's header links the prior run's last frame
    event (run_id + frame_seq) — the chain the ISSUE demands."""
    frame = str(tmp_path / "kill.npz")
    s1 = str(tmp_path / "s1.jsonl")
    s2 = str(tmp_path / "s2.jsonl")
    _run_sub(
        ["--checkpoint", frame, "--every", "2", "--telemetry", s1],
        fault="kill@level:8", expect_kill=True,
    )
    assert os.path.exists(frame)
    out = _run_sub(
        ["--checkpoint", frame, "--telemetry", s2, "--resume"]
    )
    assert out["distinct_states"] == 45198
    # both streams validate line-for-line, even the killed one
    assert checker_mod.validate_stream(s1) == []
    assert checker_mod.validate_stream(s2) == []
    e1 = [json.loads(x) for x in open(s1)]
    e2 = [json.loads(x) for x in open(s2)]
    # the kill left a breadcrumb BEFORE dying
    faults_seen = [e for e in e1 if e["event"] == "fault"]
    assert faults_seen and faults_seen[0]["kind"] == "kill"
    assert e1[-1] is not None  # last line is complete (validated above)
    frames1 = [e for e in e1 if e["event"] == "ckpt_frame"]
    assert frames1
    hdr2 = [e for e in e2 if e["event"] == "run_header"][0]
    assert hdr2["resume"] is True
    assert hdr2["resume_of"] == e1[0]["run_id"]
    assert hdr2["resume_frame_seq"] == frames1[-1]["frame_seq"]
    # and the resumed run is a different run_id (streams chain, not mix)
    assert hdr2["run_id"] != e1[0]["run_id"]


# ---- heartbeat -------------------------------------------------------


def test_heartbeat_cadence_and_zero_extra_syncs(tmp_path):
    """The heartbeat emits at its cadence on a small oracle run and
    adds ZERO device syncs: the stats-fetch count is identical with
    the heartbeat on and off."""
    m = _shipped()
    base = DeviceChecker(m, **KW)
    r0 = base.run()
    stream = str(tmp_path / "hb.jsonl")
    hb = DeviceChecker(
        m, telemetry=stream, heartbeat_s=0.05, **KW
    )
    r1 = hb.run()
    assert r1.distinct_states == r0.distinct_states == 45198
    assert hb._fetch_n == base._fetch_n  # the zero-sync contract
    beats = [
        json.loads(x)
        for x in open(stream)
        if json.loads(x)["event"] == "progress"
    ]
    # a ~5+s run at 50 ms cadence: plenty of beats, each well-formed
    assert len(beats) >= 3
    for b in beats:
        assert b["distinct_states"] >= 0
        assert "states_per_sec" in b
    # beats carry snapshot data (level/occupancy) once levels exist
    assert any("level" in b and "occupancy" in b for b in beats)


def test_heartbeat_sigterm_clean_exit(tmp_path, checker_mod):
    """A preemption (SIGTERM mid-run) with the heartbeat on exits
    resumably with a COMPLETE stream: no torn lines, a final result
    record with stop_reason=preempted, and the heartbeat thread never
    outlives the run."""
    frame = str(tmp_path / "pre.npz")
    stream = str(tmp_path / "pre.jsonl")
    out = _run_sub(
        [
            "--checkpoint", frame, "--every", "2",
            "--telemetry", stream, "--progress", "0.05",
        ],
        fault="sigterm@level:4",
    )
    assert out["truncated"] is True
    assert out["stop_reason"] == "preempted"
    assert checker_mod.validate_stream(stream) == []
    events = [json.loads(x) for x in open(stream)]
    assert events[-1]["event"] == "result"
    assert events[-1]["stop_reason"] == "preempted"
    assert any(e["event"] == "fault" for e in events)
    assert any(e["event"] == "progress" for e in events)


# ---- report layer ----------------------------------------------------


def test_report_reproduces_bench_keys(std_run):
    """scripts/telemetry_report.py --bench-keys reproduces every
    fpset_*/ckpt_* BENCH key from the stream alone — no hand-editing."""
    stream, _frame, ck, r, events = std_run
    keys = report.bench_keys(events)
    for k in (
        "fpset_flushes", "fpset_probe_rounds", "fpset_avg_probe_rounds",
        "fpset_failures", "fpset_occupancy", "fpset_valid_lanes",
        "fpset_max_probe_rounds", "ckpt_frames", "ckpt_bytes",
        "ckpt_write_s",
    ):
        assert k in keys, k
        assert keys[k] == ck.last_stats[k], k
    assert keys["distinct_states"] == r.distinct_states
    assert keys["stop_reason"] is None
    # the CLI front-end agrees with the library
    rep = _load_script("telemetry_report")
    rc = rep.main([stream, "--bench-keys"])
    assert rc == 0


def test_report_rtt_correction():
    """Legacy stage timings are corrected by n x rtt (satellite 2: the
    ~130 ms/drain RTT was documented but never subtracted)."""
    events = [
        {
            "v": 1, "event": "run_header", "t": 0.0, "seq": 0,
            "run_id": "x", "engine": "device_bfs",
            "visited_impl": "fpset", "config_sig": "s",
        },
        {
            "v": 1, "event": "result", "t": 9.0, "seq": 1,
            "run_id": "x", "distinct_states": 10, "diameter": 2,
            "wall_s": 9.0, "truncated": False,
            "stats": {
                "rtt_s": 0.13,
                "stage_flush_s": 5.0, "stage_flush_n": 10,
                "stage_expand_s": 1.0, "stage_expand_n": 20,
            },
        },
    ]
    split = report.stage_split(events)
    assert split["flush"]["device_s"] == pytest.approx(5.0 - 1.3)
    # over-subtraction floors at zero instead of going negative
    assert split["expand"]["device_s"] == 0.0
    table = report.render_stage_table([("run", events)])
    assert "flush" in table and "RTT-corrected" in table


def test_stage_table_differential_shape():
    """Two streams render the BASELINE round-6 comparison table with a
    ratio column."""
    def mk(flush_s):
        return [
            {
                "v": 1, "event": "result", "t": 1.0, "seq": 0,
                "run_id": "x", "distinct_states": 1, "diameter": 1,
                "wall_s": 44.3, "truncated": False,
                "stats": {
                    "stage_flush_s": flush_s, "stage_flush_n": 45,
                    "rtt_s": 0.0,
                },
            }
        ]

    table = report.render_stage_table(
        [("sort-merge", mk(38.8)), ("fpset", mk(7.5))]
    )
    assert "| Stage | sort-merge | fpset | ratio |" in table
    assert "5.2x" in table


# ---- schema validator (the tier-1 gate) ------------------------------


def test_validator_rejects_bad_streams(tmp_path, checker_mod):
    p = str(tmp_path / "bad.jsonl")
    with open(p, "w") as f:
        f.write('{"v": 1, "event": "level", "t": 1.0}\n')  # no run_id
        f.write("not json\n")
        f.write(
            '{"v": 99, "event": "x", "t": 0.5, "seq": 2, "run_id": "r"}\n'
        )
    errs = checker_mod.validate_stream(p)
    assert len(errs) == 3
    assert any("missing base fields" in e for e in errs)
    assert any("unparseable" in e for e in errs)
    assert any("newer than supported" in e for e in errs)
    # monotonic-t violation within one run_id
    p2 = str(tmp_path / "order.jsonl")
    with open(p2, "w") as f:
        f.write(
            '{"v": 1, "event": "a", "t": 2.0, "seq": 0, "run_id": "r"}\n'
        )
        f.write(
            '{"v": 1, "event": "a", "t": 1.0, "seq": 1, "run_id": "r"}\n'
        )
    assert any(
        "went backwards" in e for e in checker_mod.validate_stream(p2)
    )


def _write_stream(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return path


def test_profile_sig_is_required_from_v8_and_reads_null(
    std_run, tmp_path, checker_mod
):
    """The header field of the tuner's time: REQUIRED since v8, a
    constant null since the tuner went; a v7 header without it stays
    clean (committed streams validate)."""
    evs = [dict(e) for e in std_run[4]]
    assert evs[0]["event"] == "run_header"
    assert evs[0]["profile_sig"] is None and "adapt" not in evs[0]
    del evs[0]["profile_sig"]
    errs = checker_mod.validate_stream(
        _write_stream(str(tmp_path / "bad.jsonl"), evs)
    )
    assert errs and "profile_sig" in errs[0]
    evs[0]["v"] = 7
    assert checker_mod.validate_stream(
        _write_stream(str(tmp_path / "v7.jsonl"), evs)
    ) == []


def test_historic_tune_event_still_validates(
    std_run, tmp_path, checker_mod
):
    """No engine emits ``tune`` any more; a stream of the controller's
    time, with its records, validates, and one without ``knob`` does
    not."""
    assert not [e for e in std_run[4] if e["event"] == "tune"]
    head = dict(std_run[4][0])
    tune = dict(
        v=head["v"], event="tune", t=head["t"], seq=head["seq"] + 1,
        run_id=head["run_id"], knob="fuse_cap", value=4, prev=8,
        reason="early_exit",
    )
    assert checker_mod.validate_stream(
        _write_stream(str(tmp_path / "ok.jsonl"), [head, tune])
    ) == []
    del tune["knob"]
    errs = checker_mod.validate_stream(
        _write_stream(str(tmp_path / "bad.jsonl"), [head, tune])
    )
    assert errs and "knob" in errs[0]


def test_validator_accepts_legacy_bench_artifacts(checker_mod, bench_dir):
    """Driver-wrapper BENCH_*.json files (pre-schema r1-r4, schema-2
    r5) validate under their declared bench_schema."""
    import glob

    arts = sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json")))
    assert len(arts) == 5
    for p in arts:
        assert checker_mod.validate_bench_artifact(p) == [], p


def test_validator_bench_schema3_requirements(checker_mod):
    good = {
        "bench_schema": 3, "metric": "m", "value": 1.0, "unit": "u",
        "vs_baseline": 1.0, "vs_baseline_definition": "d",
        "distinct_states": 1, "levels": 1, "compile_warmup_s": 0.0,
        "stop_reason": None, "truncated": False, "hbm_recovered": 0,
        "ckpt_frames": 0, "ckpt_bytes": 0, "ckpt_write_s": 0.0,
        "fpset_flushes": 1, "fpset_probe_rounds": 1,
        "fpset_avg_probe_rounds": 1.0, "fpset_failures": 0,
        "fpset_occupancy": 0.1, "fpset_valid_lanes": 1,
        "fpset_max_probe_rounds": 1, "visited_impl": "fpset",
        "max_states": 1, "stats_fetches": 1,
    }
    assert checker_mod.validate_bench_artifact(dict(good), "g") == []
    bad = dict(good)
    del bad["ckpt_write_s"]
    errs = checker_mod.validate_bench_artifact(bad, "b")
    assert errs and "ckpt_write_s" in errs[0]
    # schema 2 artifacts are NOT held to the r8 key set
    v2 = {
        k: good[k]
        for k in (
            "metric", "value", "unit", "vs_baseline",
            "vs_baseline_definition", "distinct_states", "levels",
            "compile_warmup_s",
        )
    }
    v2["bench_schema"] = 2
    assert checker_mod.validate_bench_artifact(v2, "v2") == []


# ---- telemetry primitives --------------------------------------------


def test_null_telemetry_and_as_telemetry(tmp_path):
    assert telemetry.as_telemetry(None) is telemetry.NULL
    telemetry.NULL.emit("anything", x=1)  # no-op, no error
    p = str(tmp_path / "t.jsonl")
    t = telemetry.as_telemetry(p, run_id="rid1")
    assert telemetry.as_telemetry(t) is t
    t.emit("custom_event", foo="bar")
    t.close()
    t.emit("after_close")  # swallowed, never raises
    recs = [json.loads(x) for x in open(p)]
    assert len(recs) == 1
    assert recs[0]["run_id"] == "rid1"
    assert recs[0]["foo"] == "bar"
    # ownership: engines close streams they opened, never caller-passed
    assert telemetry.owns_stream(p) and telemetry.owns_stream(None)
    assert not telemetry.owns_stream(t)
    assert not telemetry.owns_stream(telemetry.NULL)


def test_caller_owned_stream_survives_engine_run(tmp_path):
    """A caller-passed Telemetry instance collects MULTIPLE runs into
    one stream: the engine must not close it (code-review finding)."""
    p = str(tmp_path / "shared.jsonl")
    t = telemetry.Telemetry(p, run_id="shared1")
    m = _shipped()
    DeviceChecker(m, telemetry=t, max_states=2_000, **KW).run()
    DeviceChecker(m, telemetry=t, max_states=2_000, **KW).run()
    t.close()
    recs = [json.loads(x) for x in open(p)]
    assert sum(1 for r in recs if r["event"] == "result") == 2
    # monotonic t holds across both runs (single stream clock)
    ts = [r["t"] for r in recs]
    assert ts == sorted(ts)


def test_heartbeat_thread_stops_cleanly():
    snap = {"distinct_states": 0}
    hb = telemetry.Heartbeat(0.02, snap, log=lambda m: None)
    with hb:
        snap["distinct_states"] = 10
        time.sleep(0.15)
    assert hb.beats >= 2
    assert hb._thread is None  # joined


def test_fpset_wrapper_emits(tmp_path):
    import jax.numpy as jnp

    from pulsar_tlaplus_tpu.ops.fpset import FPSet

    p = str(tmp_path / "fp.jsonl")
    s = FPSet(2, cap=1 << 12, telemetry=p)
    k = (
        jnp.arange(100, dtype=jnp.uint32),
        jnp.arange(100, dtype=jnp.uint32) * 7,
    )
    s.insert(k)
    s.close()
    recs = [json.loads(x) for x in open(p)]
    assert recs and recs[0]["event"] == "fpset_insert"
    assert recs[0]["n"] == 100


def test_fault_observer_breadcrumb(monkeypatch):
    from pulsar_tlaplus_tpu.utils import faults

    seen = []
    monkeypatch.setenv("PTT_FAULT", "oom@level:3")
    faults.reset()
    faults.set_observer(lambda k, s, c: seen.append((k, s, c)))
    try:
        assert faults.poll("level", 3) == ("oom",)
    finally:
        faults.set_observer(None)
        faults.reset()
    assert seen == [("oom", "level", 3)]


# ---- schema v14 + bench_schema 11 (fleet survivability, r21) ---------


def test_validator_v14_survivability_events(tmp_path, checker_mod):
    """The r21 events — ``reconcile`` (a lost job answered for by its
    rejoined backend), ``partition`` (a drained backend rejoined
    still holding its jobs), ``recover`` (a ``--recover`` table
    rebuild) — validate with their required fields and fail without
    them; v13-and-older records are NOT held to them (FIELD_SINCE)."""
    good = str(tmp_path / "v14.jsonl")
    with open(good, "w") as f:
        for seq, (event, fields) in enumerate([
            ("recover", {"jobs": 3}),
            ("partition", {"backend": "b0.sock"}),
            ("reconcile", {"backend": "b0.sock", "job_id": "j1",
                           "state": "done"}),
        ]):
            f.write(json.dumps({
                "v": 14, "event": event, "t": float(seq),
                "seq": seq, "run_id": "surv", **fields,
            }) + "\n")
    assert checker_mod.validate_stream(good) == []

    bad = str(tmp_path / "v14-bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({  # reconcile without the real state
            "v": 14, "event": "reconcile", "t": 0.0, "seq": 0,
            "run_id": "surv", "backend": "b0.sock",
        }) + "\n")
    errs = checker_mod.validate_stream(bad)
    assert any("reconcile missing" in e for e in errs), errs

    # committed v13 streams stay clean: the fields are since-14
    old = str(tmp_path / "v13.jsonl")
    with open(old, "w") as f:
        f.write(json.dumps({
            "v": 13, "event": "reconcile", "t": 0.0, "seq": 0,
            "run_id": "surv",
        }) + "\n")
    assert checker_mod.validate_stream(old) == []


def test_validator_v14_multi_incarnation_stream(tmp_path, checker_mod):
    """A dispatcher restarted after kill -9 APPENDS to its stream:
    distinct run_ids interleave legally (per-run monotonicity only),
    but one run's writer repeating a seq is still a torn stream."""
    p = str(tmp_path / "incarnations.jsonl")
    with open(p, "w") as f:
        for rid in ("life1", "life2", "life3"):
            for seq in range(2):
                f.write(json.dumps({
                    "v": 14, "event": "route", "t": float(seq),
                    "seq": seq, "run_id": rid, "backend": "b0",
                    "tenant": "local",
                }) + "\n")
    assert checker_mod.validate_stream(p) == []
    with open(p, "a") as f:
        f.write(json.dumps({  # life2 repeats seq 1: torn
            "v": 14, "event": "route", "t": 9.0, "seq": 1,
            "run_id": "life2", "backend": "b0", "tenant": "local",
        }) + "\n")
    errs = checker_mod.validate_stream(p)
    assert any("seq not increasing" in e for e in errs), errs


def test_bench_schema11_requires_fleet_survivability_keys(checker_mod):
    d = {k: None for k in checker_mod.BENCH_KEYS_V11}
    d.update(bench_schema=11, value=1.0)
    assert checker_mod.validate_bench_artifact(d) == []
    for k in ("fleet_failover_ms", "fleet_reconcile_ms"):
        broken = dict(d)
        del broken[k]
        errs = checker_mod.validate_bench_artifact(broken)
        assert any(k in e for e in errs), (k, errs)
    # schema-10 artifacts (committed r20 history) do NOT need them
    d10 = {k: None for k in checker_mod.BENCH_KEYS_V10}
    d10.update(bench_schema=10, value=1.0)
    assert checker_mod.validate_bench_artifact(d10) == []
