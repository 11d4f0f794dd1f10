"""Round-9 survivability: mesh-wide OOM recovery on the sharded
engine, liveness-engine checkpoint frames, and the hardened frame
writer (retry/backoff + ``ckpt_retries`` breadcrumb + stale-tmp
cleanup) — every new recovery path proven by deterministic
crash/recover differential drills.

The PTT_FAULT smoke matrix at the bottom is the tier-1 gate that keeps
fault paths from silently rotting: one fast kill/oom/ckpt_fail drill
per engine (kill drills ride the existing subprocess parity tests in
test_survivability.py; the in-process rows here use the shallow
DuplicateNullKeyMessage oracle so each run stops at depth 4)."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.utils import ckpt, faults
from tests.helpers import SMALL_CONFIGS

KW = dict(sub_batch=2048, visited_cap=1 << 16, frontier_cap=1 << 15)
SKW = dict(n_devices=4, sub_batch=512, visited_cap=1 << 13)
# the lasso liveness oracle: the stub consumer never advances, so
# Termination is violated under wf_next (a fair not-goal cycle)
CONSUMER_CFG = dataclasses.replace(
    SMALL_CONFIGS["producer_on"], model_consumer=True
)


def _shipped():
    return CompactionModel(pe.SHIPPED_CFG)


def _run_sub(*args, fault=None, expect_kill=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PTT_FAULT", None)
    if fault:
        env["PTT_FAULT"] = fault
    proc = subprocess.run(
        [sys.executable, "-m", "tests._survivable_run", *args],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if expect_kill:
        assert proc.returncode == 137, (
            proc.returncode, proc.stdout, proc.stderr,
        )
        return None
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---- hardened frame writer -------------------------------------------


def test_save_frame_retries_transient_oserror(tmp_path, monkeypatch):
    """One transient OSError is absorbed by the retry/backoff path;
    the frame lands intact and the retry count comes back."""
    calls = {"n": 0}
    real = ckpt._write_npz

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError(28, "No space left on device")
        return real(*a, **k)

    monkeypatch.setattr(ckpt, "_write_npz", flaky)
    monkeypatch.setattr(ckpt, "WRITE_BACKOFF_S", 0.001)
    p = str(tmp_path / "f.npz")
    nbytes, write_s, retries = ckpt.save_frame(
        p, "sig", {"x": np.arange(4)}
    )
    assert retries == 1 and nbytes > 0
    assert list(ckpt.load_frame(p, "sig")["x"]) == [0, 1, 2, 3]


def test_save_frame_persistent_failure_raises(tmp_path, monkeypatch):
    """A persistent failure still raises (bounded retries, never an
    infinite loop) and leaves no half-written tmp behind."""
    def dead(*a, **k):
        raise OSError(5, "Input/output error")

    monkeypatch.setattr(ckpt, "_write_npz", dead)
    monkeypatch.setattr(ckpt, "WRITE_BACKOFF_S", 0.001)
    p = str(tmp_path / "f.npz")
    with pytest.raises(OSError, match="Input/output"):
        ckpt.save_frame(p, "sig", {"x": np.arange(2)})
    assert not os.path.exists(p)
    assert not os.path.exists(p + ".tmp.npz")


def test_cleanup_stale_tmp(tmp_path):
    p = str(tmp_path / "c.npz")
    with open(p + ".tmp.npz", "wb") as f:
        f.write(b"dead half-frame")
    assert ckpt.cleanup_stale_tmp(p)
    assert not os.path.exists(p + ".tmp.npz")
    assert not ckpt.cleanup_stale_tmp(p)  # nothing left
    assert not ckpt.cleanup_stale_tmp(None)  # no checkpoint configured


def test_ckpt_fail_injection_retries_and_completes(monkeypatch, tmp_path):
    """Acceptance: ``ckpt_fail@frame:1`` — the first frame write fails
    transiently, the retry absorbs it, the run completes, and
    ``ckpt_retries >= 1`` lands in last_stats AND the stream (whose
    ckpt_frame record carries ``retries``); the schema validator
    passes on the stream."""
    monkeypatch.setenv("PTT_FAULT", "ckpt_fail@frame:1")
    faults.reset()
    stream = str(tmp_path / "s.jsonl")
    path = str(tmp_path / "ck.npz")
    ck = DeviceChecker(
        _shipped(), invariants=("DuplicateNullKeyMessage",),
        checkpoint_path=path, checkpoint_every=1, telemetry=stream,
        **KW,
    )
    r = ck.run()
    assert r.violation == "DuplicateNullKeyMessage"  # run completed
    assert ck.last_stats["ckpt_retries"] >= 1
    evs = [json.loads(l) for l in open(stream)]
    frames = [e for e in evs if e["event"] == "ckpt_frame"]
    assert frames and frames[0]["retries"] >= 1
    assert sum(e["retries"] for e in frames) == ck.last_stats[
        "ckpt_retries"
    ]
    # the breadcrumb flushed BEFORE the failed write's retry succeeded
    faults_seen = [e for e in evs if e["event"] == "fault"]
    assert any(e["kind"] == "ckpt_fail" for e in faults_seen)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from check_telemetry_schema import validate_stream

    assert validate_stream(stream) == []


def test_level1_fault_site_has_breadcrumb(monkeypatch, tmp_path):
    """The observer is installed before the level-1 poll (the r9 fix):
    a sigterm@level:1 drill leaves its fault breadcrumb in the stream
    and the run exits preempted at the very first boundary."""
    monkeypatch.setenv("PTT_FAULT", "sigterm@level:1")
    faults.reset()
    stream = str(tmp_path / "l1.jsonl")
    path = str(tmp_path / "l1.npz")
    r = DeviceChecker(
        _shipped(), checkpoint_path=path, telemetry=stream, **KW
    ).run()
    assert r.truncated and r.stop_reason == "preempted"
    evs = [json.loads(l) for l in open(stream)]
    assert any(
        e["event"] == "fault" and e["kind"] == "sigterm"
        and e["site"] == "level" and e["count"] == 1
        for e in evs
    )


# ---- mesh-wide OOM recovery on the sharded engine --------------------


@pytest.mark.parametrize(
    "invariant,oom_level,depth",
    [
        ("CompactedLedgerLeak", 8, 12),
        ("DuplicateNullKeyMessage", 3, 4),
    ],
)
def test_sharded_oom_recovery_parity(
    monkeypatch, tmp_path, invariant, oom_level, depth
):
    """Acceptance: ``oom@level:N`` on the sharded engine completes with
    ``hbm_recovered >= 1`` and a state-for-state identical reachable
    set, violation trace, and violation_gid versus an unfaulted run —
    on both published bug oracles."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    monkeypatch.setenv("PTT_FAULT", f"oom@level:{oom_level}")
    faults.reset()
    path = str(tmp_path / "soom.npz")
    ck = ShardedDeviceChecker(
        _shipped(), invariants=(invariant,), checkpoint_path=path,
        checkpoint_every=1, **SKW,
    )
    r = ck.run()
    assert r.hbm_recovered >= 1
    assert not r.truncated and r.stop_reason is None
    assert ck._headroom_frozen  # degraded capacity actually applied
    monkeypatch.delenv("PTT_FAULT")
    faults.reset()
    full = ShardedDeviceChecker(
        _shipped(), invariants=(invariant,), **SKW
    ).run()
    assert r.violation == full.violation == invariant
    assert r.diameter == full.diameter == depth
    assert r.distinct_states == full.distinct_states
    assert r.level_sizes == full.level_sizes
    assert r.violation_gid == full.violation_gid
    assert r.trace == full.trace


def test_sharded_oom_at_flush_recovers(monkeypatch, tmp_path):
    """The new flush-site drill hits the sharded fpset flush: recovery
    rebuilds mesh-wide and the full published count is reached."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    monkeypatch.setenv("PTT_FAULT", "oom@flush:8")
    faults.reset()
    path = str(tmp_path / "sfl.npz")
    r = ShardedDeviceChecker(
        _shipped(), checkpoint_path=path, checkpoint_every=1, **SKW
    ).run()
    assert r.hbm_recovered >= 1
    assert not r.truncated
    assert r.distinct_states == 45198 and r.diameter == 20


def test_sharded_oom_without_frame_truncates(monkeypatch):
    """No checkpoint configured: exhaustion keeps the honest
    truncate contract (stop_reason "hbm") instead of crashing."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    monkeypatch.setenv("PTT_FAULT", "oom@level:3")
    faults.reset()
    r = ShardedDeviceChecker(_shipped(), **SKW).run()
    assert r.truncated and r.stop_reason == "hbm"
    assert r.hbm_recovered == 0
    assert 0 < r.distinct_states < 45198


def test_sharded_oom_then_kill_resume_parity(tmp_path):
    """Subprocess drill: the run recovers from an injected OOM, is
    then hard-killed, and ``-recover`` still reproduces the unfaulted
    verdict exactly (trace + gid) — recovery state survives frames."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    path = str(tmp_path / "sok.npz")
    _run_sub(
        "--engine", "sharded", "--checkpoint", path,
        "--invariant", "CompactedLedgerLeak", "--every", "1",
        fault="oom@level:5,kill@level:8", expect_kill=True,
    )
    assert os.path.exists(path)
    resumed = _run_sub(
        "--engine", "sharded", "--checkpoint", path,
        "--invariant", "CompactedLedgerLeak", "--resume",
    )
    full = ShardedDeviceChecker(
        _shipped(), invariants=("CompactedLedgerLeak",), **SKW
    ).run()
    assert resumed["violation"] == "CompactedLedgerLeak"
    assert resumed["distinct_states"] == full.distinct_states
    assert resumed["level_sizes"] == full.level_sizes
    assert resumed["violation_gid"] == full.violation_gid
    assert resumed["trace"] == [repr(s) for s in full.trace]


# ---- liveness-engine checkpoint frames -------------------------------


def test_liveness_kill_sweep_resume_lasso_verdict(tmp_path):
    """Acceptance: kill mid-sweep (subprocess) -> ``run(resume=True)``
    reproduces the unfaulted verdict from the last sweep frame — on the
    lasso oracle (consumer modeled: Termination violated under
    wf_next), without re-exploration."""
    path = str(tmp_path / "lk.npz")
    stream = str(tmp_path / "lk.jsonl")
    common = (
        "--engine", "liveness", "--config", "consumer_on",
        "--frontier-chunk", "256", "--sweep-chunk", "256",
        "--checkpoint", path, "--every", "1",
    )
    _run_sub(
        *common, "--telemetry", stream,
        fault="kill@sweep:3", expect_kill=True,
    )
    assert os.path.exists(path)
    # the killed run's stream ends with the breadcrumb
    evs = [json.loads(l) for l in open(stream)]
    assert any(
        e["event"] == "fault" and e["kind"] == "kill"
        and e["site"] == "sweep" for e in evs
    )
    assert any(e["event"] == "sweep" for e in evs)
    resumed = _run_sub(*common, "--resume")
    want_holds, _ = pe.check_eventually(CONSUMER_CFG, "wf_next")
    assert resumed["holds"] == want_holds is False
    assert not resumed["truncated"]
    assert resumed["distinct_states"] == 1654
    assert resumed["lasso_cycle"]  # the lasso skeleton survived resume


def test_liveness_preempt_and_resume_inprocess(monkeypatch, tmp_path):
    """Acceptance: ``stop_reason="preempted"`` on SIGTERM mid-sweep;
    resume completes with the unfaulted (no-lasso) verdict."""
    monkeypatch.setenv("PTT_FAULT", "sigterm@sweep:2")
    faults.reset()
    path = str(tmp_path / "lp.npz")
    lkw = dict(
        goal="Termination", fairness="wf_next", frontier_chunk=256,
        sweep_chunk=256, visited_cap=1 << 13, checkpoint_path=path,
        checkpoint_every=1,
    )
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    r = LivenessChecker(m, **lkw).run()
    assert r.truncated and r.stop_reason == "preempted"
    assert os.path.exists(path)
    monkeypatch.delenv("PTT_FAULT")
    faults.reset()
    r2 = LivenessChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), **lkw
    ).run(resume=True)
    assert not r2.truncated
    assert r2.holds  # producer_on: Termination holds under wf_next
    assert r2.distinct_states == 1654


def test_liveness_resume_from_exploration_frame(tmp_path):
    """A kill during the EXPLORATION phase leaves the inner engine's
    frame; liveness resume re-enters exploration from it and still
    reaches the verdict."""
    path = str(tmp_path / "le.npz")
    common = (
        "--engine", "liveness", "--config", "shipped",
        "--checkpoint", path, "--every", "2",
    )
    _run_sub(*common, fault="kill@level:8", expect_kill=True)
    assert os.path.exists(path)
    resumed = _run_sub(*common, "--resume")
    assert resumed["holds"] is True  # shipped: Termination holds (wf)
    assert resumed["distinct_states"] == 45198


def test_liveness_telemetry_zero_extra_fetches(tmp_path):
    """Satellite 2: heartbeat + telemetry on the sweep add ZERO device
    fetches — asserted fetch-count-identical like the BFS engines."""
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    lkw = dict(
        goal="Termination", fairness="wf_next", frontier_chunk=256,
        sweep_chunk=256, visited_cap=1 << 13,
    )
    plain = LivenessChecker(CompactionModel(
        SMALL_CONFIGS["producer_on"]), **lkw)
    r1 = plain.run()
    stream = str(tmp_path / "lt.jsonl")
    loud = LivenessChecker(
        m, telemetry=stream, heartbeat_s=0.05, **lkw
    )
    r2 = loud.run()
    assert r1.holds == r2.holds
    assert plain._fetch_n == loud._fetch_n  # zero extra syncs
    evs = [json.loads(l) for l in open(stream)]
    kinds = {e["event"] for e in evs}
    assert {"run_header", "sweep", "result"} <= kinds
    headers = [e for e in evs if e["event"] == "run_header"]
    assert any(h["engine"] == "liveness" for h in headers)
    sweeps = [e for e in evs if e["event"] == "sweep"]
    assert sweeps[-1]["swept"] == 1654
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from check_telemetry_schema import validate_stream

    assert validate_stream(stream) == []


def test_validator_accepts_pre_r9_v1_streams(tmp_path):
    """Schema versioning: a v1 (pre-r9) ckpt_frame record has no
    ``retries`` field and must stay valid — records are held only to
    their OWN version's required fields (FIELD_SINCE); a v2 record
    without it fails."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from check_telemetry_schema import validate_stream

    base = dict(
        event="ckpt_frame", t=0.1, seq=0, run_id="r", frame_seq=1,
        bytes=10, write_s=0.0, distinct_states=5,
    )
    p1 = str(tmp_path / "v1.jsonl")
    with open(p1, "w") as f:
        f.write(json.dumps(dict(base, v=1)) + "\n")
    assert validate_stream(p1) == []
    p2 = str(tmp_path / "v2.jsonl")
    with open(p2, "w") as f:
        f.write(json.dumps(dict(base, v=2)) + "\n")
    errs = validate_stream(p2)
    assert errs and "retries" in errs[0]


def test_validator_bench_schema4_requires_ckpt_retries():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from check_telemetry_schema import (
        BENCH_KEYS_V4,
        validate_bench_artifact,
    )

    good = {k: 1 for k in BENCH_KEYS_V4}
    good.update(bench_schema=4, value=1.0)
    assert validate_bench_artifact(dict(good), "g") == []
    bad = dict(good)
    del bad["ckpt_retries"]
    errs = validate_bench_artifact(bad, "b")
    assert errs and "ckpt_retries" in errs[0]
    # a schema-3 artifact is NOT held to the r9 key
    v3 = dict(bad)
    v3["bench_schema"] = 3
    assert validate_bench_artifact(v3, "v3") == []


# ---- PTT_FAULT smoke matrix (tier-1 gate; satellite 6) ---------------
# One fast drill per engine x fault kind.  kill drills are covered by
# the subprocess parity tests (test_survivability.py and above); the
# rows here are in-process and use the shallow depth-4 oracle.


def test_smoke_device_oom(monkeypatch, tmp_path):
    monkeypatch.setenv("PTT_FAULT", "oom@level:3")
    faults.reset()
    ck = DeviceChecker(
        _shipped(), invariants=("DuplicateNullKeyMessage",),
        checkpoint_path=str(tmp_path / "d.npz"), checkpoint_every=1,
        **KW,
    )
    r = ck.run()
    assert r.hbm_recovered == 1
    assert r.violation == "DuplicateNullKeyMessage" and r.diameter == 4


def test_smoke_device_oom_at_flush(monkeypatch, tmp_path):
    monkeypatch.setenv("PTT_FAULT", "oom@flush:4")
    faults.reset()
    r = DeviceChecker(
        _shipped(), invariants=("DuplicateNullKeyMessage",),
        checkpoint_path=str(tmp_path / "df.npz"), checkpoint_every=1,
        **KW,
    ).run()
    assert r.hbm_recovered == 1
    assert r.violation == "DuplicateNullKeyMessage"


def test_smoke_sharded_fpset_fail(monkeypatch):
    """The sharded fpset_fail drill must fail-stop like a real probe
    overflow — one synthetic dropped lane, on one shard."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    monkeypatch.setenv("PTT_FAULT", "fpset_fail@flush:2")
    faults.reset()
    with pytest.raises(RuntimeError, match="probe overflow on 1 shard"):
        ShardedDeviceChecker(_shipped(), **SKW).run()


def test_smoke_sharded_ckpt_fail(monkeypatch, tmp_path):
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    monkeypatch.setenv("PTT_FAULT", "ckpt_fail@frame:1")
    faults.reset()
    ck = ShardedDeviceChecker(
        _shipped(), invariants=("DuplicateNullKeyMessage",),
        checkpoint_path=str(tmp_path / "s.npz"), checkpoint_every=1,
        **SKW,
    )
    r = ck.run()
    assert r.violation == "DuplicateNullKeyMessage"
    assert ck.last_stats["ckpt_retries"] >= 1


def test_smoke_liveness_ckpt_fail(monkeypatch, tmp_path):
    monkeypatch.setenv("PTT_FAULT", "ckpt_fail@frame:1")
    faults.reset()
    lck = LivenessChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]),
        goal="Termination", fairness="wf_next", frontier_chunk=256,
        sweep_chunk=256, visited_cap=1 << 13,
        checkpoint_path=str(tmp_path / "l.npz"), checkpoint_every=1,
    )
    r = lck.run()
    assert r.holds and not r.truncated  # the retry absorbed the fault
    # frame 1 is the inner explorer's first exploration frame (the
    # sweep's frames come later in the same sequence-per-writer);
    # whichever writer hit the injection, the retry count surfaced
    assert lck._ckpt_retries + lck._checker._ckpt_retries >= 1


def test_smoke_liveness_oom_fails_loudly(monkeypatch, tmp_path):
    """The sweep has no degraded-capacity rebuild: an injected OOM
    must abort loudly, never produce a verdict over partial edges."""
    monkeypatch.setenv("PTT_FAULT", "oom@sweep:1")
    faults.reset()
    lck = LivenessChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]),
        goal="Termination", fairness="wf_next", frontier_chunk=256,
        sweep_chunk=256, visited_cap=1 << 13,
    )
    with pytest.raises(faults.FaultError, match="RESOURCE_EXHAUSTED"):
        lck.run()
