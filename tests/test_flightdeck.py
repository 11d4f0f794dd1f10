"""Flight-deck tests (r12): Perfetto trace export, the daemon
``metrics`` verb + file-scrape parity, the schema-v5 context-switch
fields, and the ``top`` dashboard's one-frame render.

The acceptance bar (ISSUE 8):

- ``cli.py trace`` on the 2-job service fixture stream produces a
  Perfetto-loadable JSON whose job-slice spans and context-switch gap
  spans sum (within 5%) to the daemon wall clock;
- a ``metrics`` scrape of a live daemon returns parseable Prometheus
  text with >= 10 metric families and adds ZERO device stats fetches
  (the same fetch-count harness as the heartbeat tests);
- stream-tail scraping exports identically-named engine families;
- trace export round-trips: valid JSON, every complete span has a
  non-negative duration, level spans nest monotonically per run.
"""

import importlib.util
import json
import os

import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import metrics as metrics_mod
from pulsar_tlaplus_tpu.obs import report
from pulsar_tlaplus_tpu.obs import top as top_mod
from pulsar_tlaplus_tpu.obs import trace as trace_mod
from pulsar_tlaplus_tpu.obs.telemetry import Telemetry
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.service import jobs as jobmod
from pulsar_tlaplus_tpu.service.client import ServiceClient
from pulsar_tlaplus_tpu.service.scheduler import (
    CheckerPool,
    Scheduler,
    ServiceConfig,
)
from pulsar_tlaplus_tpu.service.server import ServiceDaemon

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BK_CFG = os.path.join(ROOT, "specs", "bookkeeper.cfg")

GEOM = dict(
    sub_batch=64,
    visited_cap=1 << 10,
    frontier_cap=1 << 8,
    max_states=1 << 20,
    checkpoint_every=1,
)

SMALL_COMPACTION_CFG = """
CONSTANTS
    MessageSentLimit = 2
    CompactionTimesLimit = 2
    ModelConsumer = FALSE
    ConsumeTimesLimit = 2
    KeySpace = {1}
    ValueSpace = {1}
    RetainNullKey = TRUE
    MaxCrashTimes = 1
    ModelProducer = TRUE
SPECIFICATION Spec
INVARIANTS
"""


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    return CheckerPool(
        ServiceConfig(
            state_dir=str(tmp_path_factory.mktemp("fd-pool")), **GEOM
        )
    )


@pytest.fixture(scope="module")
def solo_stream(tmp_path_factory):
    """One telemetry-instrumented solo run on the shipped 45,198-state
    oracle (checkpointing on) — the single-run trace/metrics fixture."""
    tmp = tmp_path_factory.mktemp("fd-solo")
    stream = str(tmp / "run.jsonl")
    ck = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG),
        telemetry=stream,
        checkpoint_path=str(tmp / "run.npz"),
        checkpoint_every=5,
        sub_batch=2048,
        visited_cap=1 << 16,
        frontier_cap=1 << 15,
    )
    r = ck.run()
    assert r.distinct_states == 45198
    events, errors = report.load_events(stream)
    assert not errors
    return stream, ck, r, events


@pytest.fixture(scope="module")
def service_run(tmp_path_factory, pool):
    """The 2-job time-sliced service fixture: both jobs queued before
    the loop starts (every slice expiry sees a waiter), a daemon-style
    telemetry stream collecting the v5 job lifecycle."""
    state = tmp_path_factory.mktemp("fd-two-job")
    (state / "small_compaction.cfg").write_text(SMALL_COMPACTION_CFG)
    config = ServiceConfig(
        state_dir=str(state / "state"), slice_s=0.02, **GEOM
    )
    svc_stream = str(state / "service.jsonl")
    tel = Telemetry(svc_stream)
    sched = Scheduler(config, pool=pool, telemetry=tel)
    j1 = sched.submit(
        "compaction", str(state / "small_compaction.cfg"),
        invariants=[],
    )
    j2 = sched.submit("bookkeeper", BK_CFG)
    sched.run_until_idle()
    tel.close()
    assert j1.state == j2.state == jobmod.DONE
    assert j1.suspends >= 1 and j2.suspends >= 1  # genuinely sliced
    events, errors = report.load_events(svc_stream)
    assert not errors
    return config, j1, j2, svc_stream, events


# ---- schema v5: the measured context switch -------------------------


def test_v5_suspend_resume_fields_and_validator(service_run):
    """Every job_resume carries the measured restore_s and every
    job_suspend its slice_wall_s + suspend-frame costs; the stream is
    v5-validator-clean."""
    _config, j1, j2, svc_stream, events = service_run
    checker = _load_script("check_telemetry_schema")
    assert checker.validate_stream(svc_stream) == []
    resumes = [e for e in events if e["event"] == "job_resume"]
    suspends = [e for e in events if e["event"] == "job_suspend"]
    assert len(suspends) == j1.suspends + j2.suspends
    assert len(resumes) == len(suspends)  # every suspend was resumed
    for e in resumes:
        assert e["v"] >= 5
        assert isinstance(e["restore_s"], float) and e["restore_s"] >= 0
    for e in suspends:
        assert isinstance(e["slice_wall_s"], float)
        assert e["slice_wall_s"] >= 0
        # the suspend frame's write/stall cost rides along
        assert e.get("frame_stall_s", 0.0) >= e.get(
            "frame_write_s", 0.0
        )
    # a v5 job_resume without restore_s must FAIL validation
    bad = dict(resumes[0])
    del bad["restore_s"]
    import tempfile

    with tempfile.NamedTemporaryFile(
        "w", suffix=".jsonl", delete=False
    ) as f:
        f.write(json.dumps(bad) + "\n")
    errs = checker.validate_stream(f.name)
    os.unlink(f.name)
    assert any("restore_s" in e for e in errs)


def test_jobs_report_overhead_columns(service_run):
    """telemetry_report --jobs carries the per-slice suspend-overhead
    columns: frame write+stall per suspend, restore per resume."""
    _config, j1, j2, _svc, events = service_run
    rows = {r["job_id"]: r for r in report.job_table(events)}
    for j in (j1, j2):
        r = rows[j.job_id]
        assert r["suspends"] == j.suspends
        assert r["resumes"] == j.suspends  # each suspend resumed once
        assert r["restore_s"] > 0
        assert r["slice_wall_s"] > 0
        assert r["frame_stall_s"] >= r["frame_write_s"] >= 0
    table = report.render_job_table(events)
    assert "susp s (write+stall)" in table and "restore s" in table
    # averages render as numbers, not the pre-v5 em-dash
    row1 = next(
        ln for ln in table.splitlines() if j1.job_id in ln
    )
    assert "—" not in row1.split("|")[6] + row1.split("|")[7]


# ---- trace export ---------------------------------------------------


def test_trace_roundtrip_solo_run(solo_stream, tmp_path):
    """Single-run export: valid JSON file, structurally valid events,
    one span per BFS level with monotonically increasing, non-
    overlapping extents, ckpt stalls as spans."""
    _stream, _ck, r, events = solo_stream
    out = str(tmp_path / "trace.json")
    tr = trace_mod.write_trace([("run", events)], out)
    with open(out) as f:
        again = json.load(f)  # valid JSON round-trip
    assert again["traceEvents"]
    assert trace_mod.validate_trace(out) == []
    levels = [
        e
        for e in tr["traceEvents"]
        if e.get("ph") == "X"
        and str(e.get("name", "")).startswith("level ")
    ]
    # one span per level record (r13: the fused engine emits exactly
    # one boundary record per level past the init level — no
    # intra-level fetch records on this no-growth shape)
    n_level_records = sum(1 for e in events if e["event"] == "level")
    assert len(levels) == n_level_records == r.diameter - 1
    ends = 0.0
    for e in sorted(levels, key=lambda e: e["ts"]):
        assert e["dur"] >= 0
        assert e["ts"] >= ends - 1e-6  # spans nest monotonically
        ends = e["ts"] + e["dur"]
    stalls = [
        e
        for e in tr["traceEvents"]
        if str(e.get("name", "")).startswith("ckpt frame")
    ]
    assert stalls and all(e["dur"] >= 0 for e in stalls)
    # counters ride beside the spans
    assert any(e.get("ph") == "C" for e in tr["traceEvents"])


def test_trace_job_slices_and_gaps_sum_to_daemon_wall(
    service_run, tmp_path
):
    """THE acceptance criterion: exporting the 2-job fixture stream
    yields job-slice spans and context-switch gap spans whose total
    duration equals (within 5%) the daemon wall clock between the
    first slice start and the last slice end."""
    _config, j1, j2, svc_stream, events = service_run
    from pulsar_tlaplus_tpu import cli

    out = str(tmp_path / "service_trace.json")
    assert cli.main(["trace", svc_stream, "-o", out]) == 0
    assert trace_mod.validate_trace(out) == []
    with open(out) as f:
        tr = json.load(f)
    slices = [
        e for e in tr["traceEvents"] if e.get("cat") == "job-slice"
    ]
    gaps = [
        e
        for e in tr["traceEvents"]
        if e.get("cat") == "context-switch"
    ]
    # both jobs' slices are on the device track, suspends made gaps
    assert len(slices) == (j1.suspends + 1) + (j2.suspends + 1)
    assert len(gaps) == len(slices) - 1
    total_us = sum(e["dur"] for e in slices) + sum(
        e["dur"] for e in gaps
    )
    t0 = min(e["ts"] for e in slices)
    t1 = max(e["ts"] + e["dur"] for e in slices)
    wall_us = t1 - t0
    assert wall_us > 0
    assert total_us == pytest.approx(wall_us, rel=0.05)
    # gaps into RESUMED slices carry the v5 restore cost (a gap into a
    # fresh job's first slice has no frame to restore)
    with_restore = [
        g for g in gaps if "restore_s" in (g.get("args") or {})
    ]
    assert len(with_restore) == j1.suspends + j2.suspends


def test_trace_unified_daemon_plus_job_streams(service_run, tmp_path):
    """Daemon + per-job streams export onto ONE aligned timeline: the
    engine level spans of a job land inside [first, last] extent of
    that job's device slices (wall_unix anchor alignment)."""
    _config, j1, _j2, svc_stream, events = service_run
    job_events, errs = report.load_events(j1.events_path)
    assert not errs
    tr = trace_mod.build_trace(
        [("service", events), ("job1", job_events)]
    )
    assert trace_mod.validate_trace(tr) == []
    slices = [
        e
        for e in tr["traceEvents"]
        if e.get("cat") == "job-slice"
        and j1.job_id[:6] in str(e.get("name", ""))
    ]
    levels = [
        e
        for e in tr["traceEvents"]
        if e.get("pid") == 2
        and e.get("ph") == "X"
        and str(e.get("name", "")).startswith("level ")
    ]
    assert slices and levels
    lo = min(e["ts"] for e in slices)
    hi = max(e["ts"] + e["dur"] for e in slices)
    span_us = hi - lo
    # alignment tolerance: one slice length of clock skew, not hours
    for e in levels:
        assert lo - 0.5 * span_us <= e["ts"] <= hi + 0.5 * span_us


def test_trace_daemon_restart_run_ids_align_not_splice():
    """A restart-appended service.jsonl (two daemon run_ids, each with
    its own t axis) must pair slices WITHIN a run_id and order them by
    their wall anchors — never splice two clocks into one span or
    render inverted context-switch gaps."""
    def rec(rid, seq, t, event, **kw):
        return {
            "v": 5, "event": event, "t": t, "run_id": rid, "seq": seq,
            **kw,
        }

    events = [
        # daemon lifetime 1: job A runs t=1..5, daemon dies mid-slice
        # of job B (open slice at stream end of this run_id)
        rec("d1", 0, 0.5, "job_submit", job_id="A", spec="s",
            wall_unix=1000.5),
        rec("d1", 1, 1.0, "job_start", job_id="A", spec="s", slice=1),
        rec("d1", 2, 5.0, "job_suspend", job_id="A", slice=1,
            slice_wall_s=4.0),
        rec("d1", 3, 6.0, "job_start", job_id="B", spec="s", slice=1),
        # daemon lifetime 2 (restart): fresh clock, later wall anchor
        rec("d2", 0, 0.2, "job_submit", job_id="C", spec="s",
            wall_unix=2000.2),
        rec("d2", 1, 1.0, "job_resume", job_id="A", spec="s", slice=2,
            restore_s=0.1),
        rec("d2", 2, 3.0, "job_result", job_id="A", status="ok",
            wall_s=6.0),
        rec("d2", 3, 4.0, "job_start", job_id="C", spec="s", slice=1),
        rec("d2", 4, 5.0, "job_result", job_id="C", status="ok",
            wall_s=1.0),
    ]
    tr = trace_mod.build_trace([("svc", events)])
    slices = [
        e for e in tr["traceEvents"] if e.get("cat") == "job-slice"
    ]
    # d1's open job-B slice is dropped (no honest end); A#1, A#2, C#1
    assert len(slices) == 3
    by_ts = sorted(slices, key=lambda e: e["ts"])
    names = [e["name"] for e in by_ts]
    # wall order: A slice 1 (d1 @1001) < A slice 2 (d2 @2001) < C
    assert "A" in names[0] and "slice 1" in names[0]
    assert "A" in names[1] and "slice 2" in names[1]
    assert "C" in names[2]
    # no overlap, no inverted gap spans
    gaps = [
        e
        for e in tr["traceEvents"]
        if e.get("cat") == "context-switch"
    ]
    assert all(g["dur"] >= 0 for g in gaps)
    ends = 0.0
    for e in by_ts:
        assert e["ts"] >= ends
        ends = e["ts"] + e["dur"]
    # the d2 restart really landed ~1000s after d1 on the shared axis
    assert by_ts[1]["ts"] - by_ts[0]["ts"] >= 900 * 1e6


def test_stream_metrics_and_top_use_newest_progress():
    """Heartbeat-only streams (no level records) must report the
    NEWEST snapshot — a dashboard showing the first heartbeat beside
    the latest rate reads as a frozen run."""
    def prog(seq, n, rate):
        return {
            "v": 5, "event": "progress", "t": float(seq),
            "run_id": "r", "seq": seq, "distinct_states": n,
            "states_per_sec": rate, "level": seq + 1,
        }

    events = [prog(0, 1_000, 10.0), prog(1, 9_000_000, 500_000.0)]
    fams, _types = metrics_mod.parse_exposition(
        metrics_mod.render_stream_metrics(events)
    )
    assert fams["ptt_distinct_states"][0][1] == 9_000_000
    assert fams["ptt_states_per_sec"][0][1] == 500_000.0
    model = top_mod.TopModel("x")
    model.ingest_events(events)
    assert "9.0M" in model.status_line


def test_job_table_total_wall_includes_final_slice(service_run):
    """The --jobs wall column uses job_result's cumulative wall_s —
    the suspended-slices sum alone misses every job's final slice."""
    _config, j1, _j2, _svc, events = service_run
    row = {
        r["job_id"]: r for r in report.job_table(events)
    }[j1.job_id]
    assert row["wall_s"] == pytest.approx(j1.wall_s, abs=0.01)
    # and it is strictly more than the suspended slices could account
    assert row["wall_s"] > row["slice_wall_s"] - 0.01
    table = report.render_job_table(events)
    line = next(ln for ln in table.splitlines() if j1.job_id in ln)
    assert f"{row['wall_s']:.2f}" in line


def test_trace_validator_rejects_garbage(tmp_path):
    p = str(tmp_path / "bad.json")
    with open(p, "w") as f:
        json.dump({"nope": []}, f)
    assert trace_mod.validate_trace(p)
    with open(p, "w") as f:
        json.dump(
            {
                "traceEvents": [
                    {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
                     "name": "x", "dur": -5},
                    {"ph": "Z", "pid": 1, "tid": 1, "ts": 0.0,
                     "name": "y"},
                ]
            },
            f,
        )
    errs = trace_mod.validate_trace(p)
    assert any("dur" in e for e in errs)
    assert any("unknown phase" in e for e in errs)
    # the script front-end drives the same validation
    checker = _load_script("check_telemetry_schema")
    assert checker.main([p, "--trace"]) == 1


# ---- metrics exposition ---------------------------------------------


def test_daemon_metrics_scrape_zero_fetches(tmp_path, pool):
    """Live scrape: >= 10 parseable families, zero device stats
    fetches added (the heartbeat harness's fetch-count assertion),
    job-table families consistent with the daemon's state."""
    config = ServiceConfig(
        state_dir=str(tmp_path / "state"), slice_s=0.2, **GEOM
    )
    daemon = ServiceDaemon(config, pool=pool)
    daemon.start()
    try:
        cl = ServiceClient(config.socket_path, timeout=120.0)
        jid = cl.submit("bookkeeper", BK_CFG)
        r = cl.wait(jid, timeout=240.0)
        assert r["state"] == jobmod.DONE
        fetches_before = {
            k: ck._fetch_n for k, ck in pool._checkers.items()
        }
        text = cl.metrics()
        assert fetches_before == {
            k: ck._fetch_n for k, ck in pool._checkers.items()
        }  # the zero-sync contract, now for scrapes
        fams, types = metrics_mod.parse_exposition(text)
        assert len(fams) >= 10
        assert fams["ptt_daemon_up"][0][1] == 1
        assert types["ptt_fpset_flushes_total"] == "counter"
        assert types["ptt_distinct_states"] == "gauge"
        done = [
            v
            for labels, v in fams["ptt_jobs"]
            if labels.get("state") == jobmod.DONE
        ]
        assert done == [1.0]
        assert fams["ptt_distinct_states"][0][1] == 297  # bk oracle
        assert fams["ptt_queue_depth"][0][1] == 0
        # scraping twice is stable and still fetch-free
        text2 = cl.metrics()
        assert metrics_mod.parse_exposition(text2)[0].keys() == (
            fams.keys()
        )
    finally:
        daemon.shutdown()


def test_stream_scrape_parity_with_live_families(solo_stream):
    """File-scrape mode exports identically-named engine families, and
    the values agree with the run's own last_stats."""
    _stream, ck, r, events = solo_stream
    fams, _types = metrics_mod.parse_exposition(
        metrics_mod.render_stream_metrics(events)
    )
    # the engine family set live daemon scrapes emit (metrics.py
    # _engine_families is the shared source)
    live_names = {
        f.name
        for f in metrics_mod._engine_families(
            ck.last_stats, {"distinct_states": r.distinct_states}
        )
        if f.samples
    }
    assert live_names <= set(fams)
    assert fams["ptt_distinct_states"][0][1] == r.distinct_states
    assert (
        fams["ptt_fpset_flushes_total"][0][1]
        == ck.last_stats["fpset_flushes"]
    )
    assert (
        fams["ptt_fpset_valid_lanes_total"][0][1]
        == ck.last_stats["fpset_valid_lanes"]
    )
    assert (
        fams["ptt_ckpt_frames_total"][0][1]
        == ck.last_stats["ckpt_frames"]
    )
    assert fams["ptt_bfs_level"][0][1] == r.diameter


def test_exposition_parser_roundtrip():
    fams = [
        metrics_mod.Family("ptt_x_total", "counter", "help text")
        .add(3)
        .add(4.5, {"state": "done", "q": 'a"b'}),
        metrics_mod.Family("ptt_empty", "gauge", "skipped"),
    ]
    text = metrics_mod.render_exposition(fams)
    assert "ptt_empty" not in text  # sample-less families are absent
    parsed, types = metrics_mod.parse_exposition(text)
    assert types["ptt_x_total"] == "counter"
    assert parsed["ptt_x_total"][0] == ({}, 3.0)
    assert parsed["ptt_x_total"][1] == (
        {"state": "done", "q": 'a"b'}, 4.5
    )


def test_service_stream_scrape_exports_job_families(service_run):
    """The daemon's own stream file scrapes into the job families the
    live verb also serves (identically named)."""
    _config, j1, j2, _svc, events = service_run
    fams, _types = metrics_mod.parse_exposition(
        metrics_mod.render_stream_metrics(events)
    )
    assert fams["ptt_job_slices_total"][0][1] == j1.slices + j2.slices
    assert (
        fams["ptt_job_suspends_total"][0][1]
        == j1.suspends + j2.suspends
    )
    done = [
        v
        for labels, v in fams["ptt_jobs"]
        if labels.get("state") == jobmod.DONE
    ]
    assert done == [2.0]


# ---- top ------------------------------------------------------------


def test_top_one_frame_render_from_stream(service_run, capsys):
    """`top --stream --once` renders one complete frame from a stream
    tail: header, job table rows, sparkline, status line — no daemon,
    no ANSI clear codes in --once mode.  Passing the per-job streams
    alongside joins their level-record sparklines onto the job rows
    via the r12 engine_run_id fields."""
    _config, j1, j2, svc_stream, _events = service_run
    from pulsar_tlaplus_tpu import cli

    assert cli.main(["top", "--stream", svc_stream, "--once"]) == 0
    out = capsys.readouterr().out
    assert "tpu-tlc top" in out
    assert j1.job_id[:12] in out and j2.job_id[:12] in out
    assert "ok" in out  # both jobs' terminal status rendered
    assert top_mod.CLEAR not in out  # --once never clears the screen
    assert cli.main([
        "top", "--stream", svc_stream,
        "--stream", j1.events_path, "--stream", j2.events_path,
        "--once",
    ]) == 0
    out2 = capsys.readouterr().out
    j1_row = next(
        ln
        for ln in out2.splitlines()
        if ln.startswith(j1.job_id[:12])  # the table row, not the
        #                                   header's stream paths
    )
    # the job row carries a real sparkline joined from the job
    # stream's level records
    assert any(c in j1_row for c in top_mod.SPARK_CHARS)
    assert "/s" in j1_row
    # a lone engine stream (no job events) still shows per-run rates
    model = top_mod.TopModel("job")
    frame = top_mod.tail_stream_frame(j1.events_path, model)
    assert "RUN" in frame
    assert any(c in frame for c in top_mod.SPARK_CHARS)


def test_top_frame_model_and_sparkline(solo_stream):
    _stream, _ck, r, events = solo_stream
    model = top_mod.TopModel("run.jsonl")
    model.ingest_events(events)
    # level records fed the run's sparkline history
    assert any(len(h) > 3 for h in model.rates.values())
    assert str(r.diameter) in model.status_line  # final level
    frame = top_mod.render_frame(model, now=0.0)
    assert "tpu-tlc top" in frame.splitlines()[0]
    assert model.status_line in frame
    # sparkline scales to its own max and clamps to the char set
    s = top_mod.sparkline([0, 1, 2, 4, 8])
    assert len(s) == 5 and s[-1] == top_mod.SPARK_CHARS[-1]
    assert top_mod.sparkline([]) == ""
    assert top_mod.sparkline([0, 0]) == top_mod.SPARK_CHARS[0] * 2
    assert top_mod.fmt_si(1_234_567) == "1.2M"


def test_top_daemon_poll_frame(tmp_path, pool):
    """One daemon poll paints pid/uptime, the job row, and a status
    line fed by the metrics scrape."""
    config = ServiceConfig(
        state_dir=str(tmp_path / "state"), slice_s=0.2, **GEOM
    )
    daemon = ServiceDaemon(config, pool=pool)
    daemon.start()
    try:
        cl = ServiceClient(config.socket_path, timeout=120.0)
        jid = cl.submit("bookkeeper", BK_CFG)
        cl.wait(jid, timeout=240.0)
        model = top_mod.TopModel(config.socket_path)
        frame = top_mod.poll_daemon_frame(cl, model)
        assert f"pid {os.getpid()}" in frame
        assert jid[:12] in frame
        assert "297" in frame or "done" in frame
    finally:
        daemon.shutdown()
