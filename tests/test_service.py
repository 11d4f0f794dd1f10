"""Checking-as-a-service tests (r11, ``pulsar_tlaplus_tpu/service/``).

The acceptance bar (ISSUE 6 / docs/service.md):

- >= 2 concurrent queued jobs time-slice ONE device, each job's result
  state-for-state equal (states, verdict, violation trace/gid) to a
  solo run of the same spec + .cfg;
- SIGTERM mid-job + ``serve --recover`` completes the queue with the
  same results (the in-process tests drive the exact code path the
  signal handler arms; the subprocess drill with a real SIGTERM is the
  ``slow``-marked load test);
- a warm-start submit against an already-warmed spec pays ZERO jit
  compiles (the capacity-tier prewarm harness from test_compact.py);
- the daemon's telemetry stream (schema v4 ``job_*`` events) and every
  per-job engine stream pass the schema validator.

One module-scoped CheckerPool is shared across tests — exactly the
resident-daemon shape: compiled programs persist while queues, state
dirs, and jobs come and go.
"""

import importlib.util
import json
import os
import time

import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.bookkeeper import (
    BookkeeperConstants,
    BookkeeperModel,
)
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import report
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.service import jobs as jobmod
from pulsar_tlaplus_tpu.service.client import ServiceClient, ServiceError
from pulsar_tlaplus_tpu.service.scheduler import (
    CheckerPool,
    Scheduler,
    ServiceConfig,
)
from pulsar_tlaplus_tpu.service.server import ServiceDaemon
from tests.helpers import SMALL_CONFIGS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BK_CFG = os.path.join(ROOT, "specs", "bookkeeper.cfg")

# one engine geometry for the whole module (the daemon's "one geometry
# for the whole registry" rule): small caps so growth paths exercise,
# cheap enough for the CPU mesh
GEOM = dict(
    sub_batch=64,
    visited_cap=1 << 10,
    frontier_cap=1 << 8,
    max_states=1 << 20,
    checkpoint_every=1,
)

# small compaction binding == SMALL_CONFIGS["producer_on"] (1,654
# states, diameter 16 — asserted against the Python oracle below)
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

# bookkeeper crash2 violates ConfirmedEntryReadable with a pinned
# 9-state counterexample (test_bookkeeper.py) — the violation/trace
# parity workload
BK_CRASH2_CFG = """
CONSTANTS
    NumBookies = 3
    WriteQuorum = 2
    AckQuorum = 2
    EntryLimit = 2
    MaxBookieCrashes = 2
SPECIFICATION Spec
INVARIANTS
    ConfirmedEntryReadable
"""


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
def cfg_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("cfgs")
    (d / "small_compaction.cfg").write_text(SMALL_COMPACTION_CFG)
    (d / "bk_crash2.cfg").write_text(BK_CRASH2_CFG)
    return d


def _config(state_dir, **kw) -> ServiceConfig:
    base = dict(GEOM)
    base.update(kw)
    return ServiceConfig(state_dir=str(state_dir), **base)


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    """The resident pool: warmed checkers shared by every scheduler
    instance in this module (exactly what the daemon holds)."""
    return CheckerPool(
        _config(tmp_path_factory.mktemp("pool-anchor"))
    )


def _solo(model, invariants) -> object:
    """A solo run with the pool's exact engine geometry — the parity
    baseline the acceptance criteria name."""
    return DeviceChecker(
        model,
        invariants=invariants,
        sub_batch=GEOM["sub_batch"],
        visited_cap=GEOM["visited_cap"],
        frontier_cap=GEOM["frontier_cap"],
        max_states=GEOM["max_states"],
    ).run()


@pytest.fixture(scope="module")
def solo_compaction():
    """Solo baseline for the small compaction binding (computed once;
    oracle-pinned here so every parity consumer inherits the pin)."""
    want = pe.check(SMALL_CONFIGS["producer_on"], invariants=())
    solo = _solo(CompactionModel(SMALL_CONFIGS["producer_on"]), ())
    assert solo.distinct_states == want.distinct_states == 1654
    assert solo.diameter == want.diameter == 16
    return solo


@pytest.fixture(scope="module")
def solo_bk_crash2():
    """Solo baseline for the bookkeeper violation binding (pinned
    9-state ConfirmedEntryReadable counterexample)."""
    solo = _solo(
        BookkeeperModel(BookkeeperConstants(max_bookie_crashes=2)),
        ("ConfirmedEntryReadable",),
    )
    assert solo.violation == "ConfirmedEntryReadable"
    assert len(solo.trace) == 9
    return solo


def assert_result_matches_solo(job, solo):
    """State-for-state job-vs-solo equality: counts, per-level sizes,
    verdict, violation gid, and the full rendered trace."""
    r = job.result
    assert r is not None, (job.state, job.error)
    assert r["distinct_states"] == solo.distinct_states
    assert r["diameter"] == solo.diameter
    assert r["level_sizes"] == [int(x) for x in solo.level_sizes]
    assert r["violation"] == solo.violation
    assert r["violation_gid"] == solo.violation_gid
    assert r["deadlock"] == bool(solo.deadlock)
    if solo.trace is None:
        assert r["trace"] is None
    else:
        assert r["trace"] == [repr(s) for s in solo.trace]
        assert r["trace_actions"] == list(solo.trace_actions)


# ---- the 2-job time-slicing smoke (tier-1 acceptance) ---------------


@pytest.fixture(scope="module")
def two_job_run(tmp_path_factory, pool, cfg_dir):
    """ONE shared 2-job time-sliced run (both queued before the loop
    starts, so every slice expiry sees another waiter and the run
    genuinely interleaves) — the parity test and the telemetry test
    both read it."""
    from pulsar_tlaplus_tpu.obs.telemetry import Telemetry

    state = tmp_path_factory.mktemp("two-job")
    config = _config(state / "state", slice_s=0.02)
    svc_stream = str(state / "service.jsonl")
    tel = Telemetry(svc_stream)
    sched = Scheduler(config, pool=pool, telemetry=tel)
    j1 = sched.submit(
        "compaction", str(cfg_dir / "small_compaction.cfg"),
        invariants=[],
    )
    j2 = sched.submit("bookkeeper", str(cfg_dir / "bk_crash2.cfg"))
    sched.run_until_idle()
    tel.close()
    return config, j1, j2, svc_stream


def test_two_jobs_time_slice_one_device_with_solo_parity(
    two_job_run, solo_compaction, solo_bk_crash2
):
    """Two concurrent queued jobs share one device via suspend/resume
    at checkpoint-frame boundaries; both finish with results equal to
    their solo runs — one clean pass, one invariant violation with a
    replayed counterexample trace."""
    config, j1, j2, _stream = two_job_run
    assert j1.state == j2.state == jobmod.DONE
    # time-slicing actually happened: each job was suspended at a
    # frame boundary at least once and resumed in a later slice
    assert j1.suspends >= 1 and j2.suspends >= 1
    assert j1.slices == j1.suspends + 1
    assert len(j1.run_ids) == j1.slices  # one engine run_id per slice
    assert len(set(j1.run_ids) & set(j2.run_ids)) == 0

    assert_result_matches_solo(j1, solo_compaction)
    assert j1.result["status"] == "ok"
    assert_result_matches_solo(j2, solo_bk_crash2)
    assert j2.result["status"] == "violation"

    # durable artifacts: per-job result.json matches, the terminal
    # frame is gone, the queue snapshot marks both done
    for j in (j1, j2):
        assert json.load(open(j.result_path)) == j.result
        assert not os.path.exists(j.frame_path)
    snap = json.load(open(config.queue_path))
    assert {d["state"] for d in snap["jobs"]} == {jobmod.DONE}


def test_per_job_streams_and_daemon_events_validate(
    two_job_run, checker_mod
):
    """Per-job telemetry isolation: each job's events.jsonl carries
    only that job's slice run_ids, chains resume frames, and passes
    the v4 validator; the scheduler's own stream carries the job_*
    lifecycle in order."""
    _config_, j1, j2, svc_stream = two_job_run
    for j in (j1, j2):
        assert checker_mod.validate_stream(j.events_path) == []
        evs = [json.loads(x) for x in open(j.events_path)]
        rids = [e["run_id"] for e in evs if e["event"] == "run_header"]
        assert rids == j.run_ids  # one header per slice, this job only
        resumed = [
            e for e in evs
            if e["event"] == "run_header" and e.get("resume")
        ]
        assert len(resumed) == j.suspends
    assert checker_mod.validate_stream(svc_stream) == []
    rows = report.job_table(
        [json.loads(x) for x in open(svc_stream)]
    )
    by_id = {r["job_id"]: r for r in rows}
    assert by_id[j1.job_id]["status"] == "ok"
    assert by_id[j1.job_id]["slices"] == j1.slices
    assert by_id[j1.job_id]["suspends"] == j1.suspends
    assert by_id[j2.job_id]["status"] == "violation"
    assert "| job | spec |" in report.render_job_table(
        [json.loads(x) for x in open(svc_stream)]
    )


# ---- shutdown mid-job -> recover (the SIGTERM contract) -------------


def test_shutdown_mid_job_then_recover_same_results(
    tmp_path, pool, cfg_dir, solo_compaction, solo_bk_crash2
):
    """Stop the scheduler while a job is mid-run (the code path the
    SIGTERM handler arms): the running job suspends at its next frame
    boundary, the queue persists, and a recovered scheduler completes
    BOTH jobs with solo-run results."""
    config = _config(tmp_path / "state", slice_s=30.0)
    sched = Scheduler(config, pool=pool)
    j1 = sched.submit(
        "compaction", str(cfg_dir / "small_compaction.cfg"),
        invariants=[],
    )
    j2 = sched.submit("bookkeeper", str(cfg_dir / "bk_crash2.cfg"))
    sched.start()
    deadline = time.monotonic() + 120.0
    while j1.state == jobmod.QUEUED:
        assert time.monotonic() < deadline, "job never started"
        time.sleep(0.02)
    sched.stop(timeout=120.0)  # what the daemon's SIGTERM path calls
    assert j1.state in (jobmod.SUSPENDED, jobmod.QUEUED, jobmod.DONE)
    assert j2.state == jobmod.QUEUED
    if j1.state == jobmod.SUSPENDED:
        assert os.path.exists(j1.frame_path)  # resumable frame on disk

    # "serve --recover": a fresh scheduler over the same state dir
    sched2 = Scheduler(config, pool=pool)
    n = sched2.recover()
    assert n >= 1
    r1, r2 = sched2.get(j1.job_id), sched2.get(j2.job_id)
    sched2.run_until_idle()
    assert r1.state == r2.state == jobmod.DONE
    assert_result_matches_solo(r1, solo_compaction)
    assert_result_matches_solo(r2, solo_bk_crash2)


def test_recover_edge_cases(tmp_path, pool):
    config = _config(tmp_path / "state")
    sched = Scheduler(config, pool=pool)
    assert sched.recover() == 0  # no queue.json: fresh daemon
    os.makedirs(config.state_dir, exist_ok=True)
    with open(config.queue_path, "w") as f:
        f.write("{not json")
    # r17 torn-queue recovery: a corrupt queue.json is QUARANTINED
    # and the queue rebuilt from the job dirs (none here) — never a
    # crash (tests/test_robustness_service.py drills the full path)
    assert Scheduler(config, pool=pool).recover() == 0
    assert not os.path.exists(config.queue_path) or json.load(
        open(config.queue_path)
    )["jobs"] == []
    assert [
        f for f in os.listdir(config.state_dir)
        if f.startswith("queue.json.corrupt.")
    ]


def test_recover_resumes_first_slice_frame(
    tmp_path, pool, cfg_dir, solo_compaction
):
    """A daemon killed mid-FIRST-slice last persisted the job as it
    was claimed (slices=0, running) while its engine had already
    written a frame; recovery must RESUME that frame — a slice-count
    guard must never throw the progress away."""
    config = _config(tmp_path / "state", slice_s=0.0)
    sched = Scheduler(config, pool=pool)
    j1 = sched.submit(
        "compaction", str(cfg_dir / "small_compaction.cfg"),
        invariants=[],
    )
    sched.submit("bookkeeper", BK_CFG)  # waiter -> j1's slice expires
    job = sched._claim()
    assert job is j1
    sched._run_slice(job)
    assert j1.state == jobmod.SUSPENDED
    assert os.path.exists(j1.frame_path)
    assert j1.progress["distinct_states"] > 0
    # forge the crash shape: the last snapshot to reach disk was
    # _claim()'s (slices=0, running), THEN the frame landed
    with sched.cv:
        j1.state = jobmod.RUNNING
        j1.slices = 0
        sched.fifo.remove(j1.job_id)
        sched._running_id = j1.job_id
    sched.persist()

    sched2 = Scheduler(config, pool=pool)
    assert sched2.recover() == 2
    r1 = sched2.get(j1.job_id)
    assert r1.state == jobmod.SUSPENDED  # frame on disk -> resumable
    sched2.run_until_idle()
    assert r1.state == jobmod.DONE
    assert_result_matches_solo(r1, solo_compaction)
    # the frame was USED: a later slice's engine run resumed it
    evs = [json.loads(x) for x in open(r1.events_path)]
    assert any(
        e.get("event") == "run_header" and e.get("resume")
        for e in evs
    )


def test_terminal_retention_prune(tmp_path, pool):
    """``keep_terminal`` bounds the resident job table: the oldest
    terminal records — and their jobs/<id>/ dirs — are pruned on every
    persist, so a long-lived daemon does not grow per-submit forever."""
    config = _config(tmp_path / "state", keep_terminal=2)
    sched = Scheduler(config, pool=pool)
    jids = []
    for _ in range(5):
        j = sched.submit("bookkeeper", BK_CFG)
        sched.cancel(j.job_id)  # cheap terminal transition
        jids.append(j.job_id)
    assert [jid for jid in jids if jid in sched.jobs] == jids[-2:]
    for jid in jids[:3]:
        assert not os.path.exists(os.path.join(config.jobs_dir, jid))
    with open(config.queue_path) as f:
        snap = json.load(f)
    assert {d["job_id"] for d in snap["jobs"]} == set(jids[-2:])


def test_state_dir_single_instance_lock(tmp_path, pool):
    """A second daemon on the same state dir must fail fast — not
    unlink the live daemon's socket and split-brain queue.json."""
    config = _config(tmp_path / "state")
    d1 = ServiceDaemon(config, pool=pool)
    try:
        with pytest.raises(RuntimeError, match="already serves"):
            ServiceDaemon(config, pool=pool)
    finally:
        d1.shutdown()
    d2 = ServiceDaemon(config, pool=pool)  # flock died with the fd
    d2.shutdown()


def test_client_transport_failure_exits_2(tmp_path):
    """Daemon-down is exit 2 (no verdict) — NEVER 1, which the exit
    contract reserves for a confirmed violation/deadlock (a CI lane
    must not report a spec bug because the daemon was down)."""
    from pulsar_tlaplus_tpu import cli

    with pytest.raises(SystemExit) as ei:
        cli.main([
            "submit", "bookkeeper", BK_CFG,
            "--socket", str(tmp_path / "no_daemon.sock"),
        ])
    assert ei.value.code == 2


# ---- cancel + budget ------------------------------------------------


def test_cancel_queued_running_and_time_budget(
    tmp_path, pool, cfg_dir
):
    config = _config(tmp_path / "state", slice_s=30.0)
    sched = Scheduler(config, pool=pool)
    # a queued job cancels immediately (never touches the device)
    jq = sched.submit("bookkeeper", BK_CFG)
    assert sched.cancel(jq.job_id).state == jobmod.CANCELLED
    assert sched.cancel(jq.job_id).state == jobmod.CANCELLED  # idempotent
    # an exhausted time budget truncates honestly (no verdict claimed)
    jb = sched.submit(
        "compaction", str(cfg_dir / "small_compaction.cfg"),
        invariants=[], time_budget_s=1e-6,
    )
    # a running job exits via the suspend hook's "cancelled" answer
    jr = sched.submit(
        "compaction", str(cfg_dir / "small_compaction.cfg"),
        invariants=[],
    )
    sched.start()
    deadline = time.monotonic() + 120.0
    while jr.state in (jobmod.QUEUED,) or jb.state == jobmod.QUEUED:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    sched.cancel(jr.job_id)
    sched.wait(jr.job_id, timeout=120.0)
    sched.stop(timeout=120.0)
    assert jr.state == jobmod.CANCELLED
    assert not os.path.exists(jr.frame_path)  # no dead-weight frame
    assert jb.result["status"] == "truncated"
    assert jb.result["stop_reason"] == "time_budget"
    # a bad submit fails eagerly, not in the queue
    with pytest.raises(ValueError, match="not in the compiled registry"):
        sched.submit("no_such_spec", BK_CFG)
    with pytest.raises(ValueError, match="unknown invariant"):
        sched.submit("bookkeeper", BK_CFG, invariants=["Nope"])
    with pytest.raises(ValueError, match="service ceiling"):
        sched.submit("bookkeeper", BK_CFG, max_states=1 << 40)


# ---- warm-start: zero jit compiles ----------------------------------


def test_warm_submit_pays_zero_jit_compiles(tmp_path):
    """The resident-daemon payoff: after ``prewarm`` (capacity-tier
    warmup, r10), a submit against the warmed key adds ZERO jitted
    programs — the same ``set(ck._jits)`` harness as
    test_compact.py's prewarm proofs."""
    config = _config(
        tmp_path / "state",
        visited_cap=1 << 8, frontier_cap=1 << 7, max_states=1 << 12,
    )
    own_pool = CheckerPool(config)
    key, _compile_s = own_pool.warm("bookkeeper", BK_CFG)
    ck = own_pool._checkers[key]
    assert ck._jits  # genuinely warmed
    key2, compile_s2 = own_pool.warm("bookkeeper", BK_CFG)
    assert key2 == key and compile_s2 == 0.0  # idempotent
    keys_before = set(ck._jits)

    sched = Scheduler(config, pool=own_pool)
    job = sched.submit("bookkeeper", BK_CFG)
    sched.run_until_idle()
    assert job.state == jobmod.DONE
    assert job.result["status"] == "ok"
    assert job.result["distinct_states"] == 297  # pinned oracle
    assert set(ck._jits) == keys_before  # ZERO post-warm compiles


# ---- the wire protocol + daemon -------------------------------------


def test_daemon_protocol_roundtrip(tmp_path, pool, cfg_dir):
    """Socket-level lifecycle: ping, submit, status, watch (streamed
    per-slice engine telemetry relayed under the job's run_ids),
    result, error paths, shutdown op, socket cleanup."""
    config = _config(tmp_path / "state", slice_s=0.2)
    daemon = ServiceDaemon(config, pool=pool)
    daemon.start()
    try:
        cl = ServiceClient(config.socket_path, timeout=120.0)
        pong = cl.ping()
        assert pong["pid"] == os.getpid() and pong["jobs"] == {}

        with pytest.raises(ServiceError, match="not in the compiled"):
            cl.submit("no_such_spec", BK_CFG)
        with pytest.raises(ServiceError, match="unknown job"):
            cl.status("nope")

        jid1 = cl.submit(
            "compaction", str(cfg_dir / "small_compaction.cfg"),
            invariants=[],
        )
        jid2 = cl.submit("bookkeeper", str(cfg_dir / "bk_crash2.cfg"))
        seen_events = []
        done = None
        for msg in cl.watch(jid2, timeout_s=240.0):
            if "event" in msg:
                seen_events.append(msg["event"])
            elif "done" in msg:
                done = msg["done"]
        assert done is not None and done["state"] == jobmod.DONE
        assert done["result"]["status"] == "violation"
        kinds = {e["event"] for e in seen_events}
        assert "run_header" in kinds  # engine telemetry relayed
        assert {e["run_id"] for e in seen_events} == set(
            done["run_ids"]
        )
        r1 = cl.wait(jid1, timeout=240.0)
        assert r1["state"] == jobmod.DONE
        assert r1["result"]["status"] == "ok"
        assert r1["result"]["distinct_states"] == 1654

        jobs = cl.status()
        assert {j["job_id"] for j in jobs} == {jid1, jid2}
        assert {j["state"] for j in jobs} == {jobmod.DONE}
        one = cl.status(jid1)
        assert one["distinct_states"] == 1654

        # cancel on a terminal job is a no-op answer, not an error
        assert cl.cancel(jid1) == jobmod.DONE

        assert cl.shutdown()["stopping"] is True
    finally:
        daemon.shutdown()
    assert not os.path.exists(config.socket_path)  # socket removed
    # daemon stream: serve start/stop + full job lifecycle, v4-clean
    evs = [json.loads(x) for x in open(config.telemetry_path)]
    assert [
        e["action"] for e in evs if e["event"] == "serve"
    ] == ["start", "stop"]
    assert {
        e["event"] for e in evs if e["event"].startswith("job_")
    } >= {"job_submit", "job_start", "job_result"}


def test_protocol_rejects_garbage(tmp_path, pool):
    import socket as socketmod

    from pulsar_tlaplus_tpu.service import protocol

    config = _config(tmp_path / "state")
    daemon = ServiceDaemon(config, pool=pool)
    daemon.start()
    try:
        resp = protocol.request(config.socket_path, "frobnicate")
        assert not resp["ok"] and "unknown op" in resp["error"]

        s = socketmod.socket(socketmod.AF_UNIX, socketmod.SOCK_STREAM)
        s.connect(config.socket_path)
        s.sendall(b"this is not json\n")
        r = s.makefile("r")
        assert not json.loads(r.readline())["ok"]
        s.close()
    finally:
        daemon.shutdown()


# ---- v4 schema: interleaved run_ids + per-run seq monotonicity ------


def test_validator_accepts_interleaved_runs_rejects_torn_seq(
    tmp_path, checker_mod
):
    def rec(run_id, seq, t, event="progress", **kw):
        base = {
            "v": 4, "event": event, "t": t, "run_id": run_id,
            "seq": seq, "distinct_states": 1, "level": 1,
            "states_per_sec": 1.0,
        }
        base.update(kw)
        return base

    good = tmp_path / "interleaved.jsonl"
    good.write_text(
        "\n".join(
            json.dumps(r)
            for r in [
                rec("run-a", 0, 0.1),
                rec("run-b", 0, 0.2),  # interleaved run_ids: legal
                rec("run-a", 1, 0.3),
                rec("run-b", 1, 0.4),
                rec("run-a", 2, 0.5),
            ]
        )
        + "\n"
    )
    assert checker_mod.validate_stream(str(good)) == []

    torn = tmp_path / "torn.jsonl"
    torn.write_text(
        "\n".join(
            json.dumps(r)
            for r in [
                rec("run-a", 0, 0.1),
                rec("run-b", 7, 0.2),
                rec("run-a", 1, 0.3),
                rec("run-a", 1, 0.4),  # duplicated seq within run-a
                rec("run-b", 8, 0.5),
            ]
        )
        + "\n"
    )
    errs = checker_mod.validate_stream(str(torn))
    assert len(errs) == 1 and "seq not increasing" in errs[0]

    noseq = tmp_path / "noseq.jsonl"
    rec_noseq = rec("run-a", 0, 0.1)
    del rec_noseq["seq"]  # seq is a BASE envelope field
    rec_badseq = rec("run-a", "7", 0.2)  # present but not an int
    noseq.write_text(
        json.dumps(rec_noseq) + "\n" + json.dumps(rec_badseq) + "\n"
    )
    errs = checker_mod.validate_stream(str(noseq))
    assert any("missing base fields" in e for e in errs)
    assert any("non-integer seq" in e for e in errs)

    # v4 job events: required fields enforced at v4, not before
    misstream = tmp_path / "jobs.jsonl"
    ok_job = {
        "v": 4, "event": "job_submit", "t": 0.1, "run_id": "d", "seq": 0,
        "job_id": "j1", "spec": "compaction",
    }
    bad_job = {
        "v": 4, "event": "job_result", "t": 0.2, "run_id": "d", "seq": 1,
        "job_id": "j1",  # missing "status"
    }
    old_style = {
        "v": 3, "event": "job_result", "t": 0.3, "run_id": "e", "seq": 0,
        "job_id": "j1",  # pre-v4 record: job fields not yet required
    }
    misstream.write_text(
        "\n".join(json.dumps(r) for r in (ok_job, bad_job, old_style))
        + "\n"
    )
    errs = checker_mod.validate_stream(str(misstream))
    assert len(errs) == 1 and "status" in errs[0]


# ---- bench stale-stream hygiene (satellite) -------------------------


def test_bench_cleans_stale_telemetry_streams(tmp_path):
    import subprocess
    import sys

    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)

    # a genuinely dead pid (reaped child), our own pid, and noise
    child = subprocess.Popen(["true"])
    child.wait()
    dead = tmp_path / f"bench_telemetry_{child.pid}.jsonl"
    live = tmp_path / f"bench_telemetry_{os.getpid()}.jsonl"
    other = tmp_path / "not_a_bench_stream.jsonl"
    for p in (dead, live, other):
        p.write_text("{}\n")
    assert bench.cleanup_stale_streams(str(tmp_path)) == 1
    assert not dead.exists()
    assert live.exists() and other.exists()
    assert bench.cleanup_stale_streams(str(tmp_path / "missing")) == 0

    args = bench.parse_args(["--telemetry-path", str(tmp_path)])
    assert args.telemetry_path == str(tmp_path)
    assert args.telemetry == bench._DEFAULT_TELEMETRY  # resolved in main


# ---- load test: many jobs, mixed specs, real SIGTERM (slow) ---------


@pytest.mark.slow
def test_load_many_jobs_mixed_specs(tmp_path, pool, cfg_dir):
    """>= 2-job load: six queued jobs across three bindings time-slice
    one device; every result equals its solo baseline."""
    config = _config(tmp_path / "state", slice_s=0.02)
    sched = Scheduler(config, pool=pool)
    jobs = []
    for i in range(2):
        jobs.append(
            (
                sched.submit(
                    "compaction",
                    str(cfg_dir / "small_compaction.cfg"),
                    invariants=[],
                ),
                "compaction",
            )
        )
        jobs.append((sched.submit("bookkeeper", BK_CFG), "bk"))
        jobs.append(
            (
                sched.submit(
                    "bookkeeper", str(cfg_dir / "bk_crash2.cfg")
                ),
                "bk2",
            )
        )
    sched.run_until_idle()
    solos = {
        "compaction": _solo(
            CompactionModel(SMALL_CONFIGS["producer_on"]), ()
        ),
        "bk": _solo(
            BookkeeperModel(BookkeeperConstants()),
            ("TypeOK", "LacIsConfirmed", "AckImpliesStoredOrCrashed",
             "ConfirmedEntryReadable"),
        ),
        "bk2": _solo(
            BookkeeperModel(BookkeeperConstants(max_bookie_crashes=2)),
            ("ConfirmedEntryReadable",),
        ),
    }
    assert sum(j.suspends for j, _k in jobs) >= 4
    for j, k in jobs:
        assert j.state == jobmod.DONE
        assert_result_matches_solo(j, solos[k])


@pytest.mark.slow
def test_serve_cli_sigterm_recover_subprocess(tmp_path, cfg_dir):
    """The full acceptance drill as real processes: `cli.py serve`,
    client submits over the socket, SIGTERM mid-job, then
    `serve --recover --drain` completes the queue with solo results."""
    import signal
    import subprocess
    import sys

    state = tmp_path / "state"
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn(*extra):
        return subprocess.Popen(
            [
                sys.executable, "-m", "pulsar_tlaplus_tpu.cli",
                "serve", str(state), "--no-prewarm", "--slice", "0.2",
                "--maxstates", str(GEOM["max_states"]),
                "--checkpoint-every", "1", "-chunk", "64", *extra,
            ],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, cwd=ROOT, env=env,
        )

    daemon = spawn()
    try:
        assert "serving on" in daemon.stdout.readline()
        cl = ServiceClient(
            str(state / "serve.sock"), timeout=120.0
        )
        jid1 = cl.submit(
            "compaction", str(cfg_dir / "small_compaction.cfg"),
            invariants=[],
        )
        jid2 = cl.submit("bookkeeper", str(cfg_dir / "bk_crash2.cfg"))
        deadline = time.monotonic() + 180.0
        while cl.status(jid1)["state"] == jobmod.QUEUED:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        daemon.send_signal(signal.SIGTERM)
        assert daemon.wait(timeout=180.0) == 0  # graceful exit
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()

    recov = spawn("--recover", "--drain")
    try:
        assert recov.wait(timeout=600.0) == 0  # drained + stopped
    finally:
        if recov.poll() is None:
            recov.kill()
            recov.wait()

    # results from the job dirs (the daemon is gone)
    snap = json.load(open(state / "queue.json"))
    by_id = {d["job_id"]: d for d in snap["jobs"]}
    assert by_id[jid1]["state"] == by_id[jid2]["state"] == jobmod.DONE
    res1 = json.load(
        open(state / "jobs" / jid1 / "result.json")
    )
    res2 = json.load(
        open(state / "jobs" / jid2 / "result.json")
    )
    assert res1["status"] == "ok"
    assert res1["distinct_states"] == 1654
    assert res2["status"] == "violation"
    assert res2["violation"] == "ConfirmedEntryReadable"
    assert len(res2["trace"]) == 9
