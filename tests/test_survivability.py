"""Run-survivability tests (ISSUE r7): device_bfs checkpoint/resume,
HBM-exhaustion recovery, preemption-safe shutdown, and the
deterministic fault-injection harness — interrupted+resumed runs must
match uninterrupted runs state-for-state on the published oracles."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.utils import ckpt, faults
from tests.helpers import assert_valid_counterexample

KW = dict(sub_batch=2048, visited_cap=1 << 16, frontier_cap=1 << 15)


def _shipped():
    return CompactionModel(pe.SHIPPED_CFG)


# ---- checkpoint/resume on the device engine --------------------------


def test_device_checkpoint_resume_exact_count(tmp_path):
    """A budget-truncated device run leaves a frame; resume reaches the
    published 45,198-state count with level sizes identical to an
    uninterrupted run's."""
    m = _shipped()
    path = str(tmp_path / "dev.npz")
    r1 = DeviceChecker(
        m, checkpoint_path=path, checkpoint_every=3,
        max_states=10_000, **KW,
    ).run()
    assert r1.truncated and r1.stop_reason == "max_states"
    assert r1.distinct_states < 45198
    assert os.path.exists(path)
    r2 = DeviceChecker(m, checkpoint_path=path, **KW).run(resume=True)
    assert r2.distinct_states == 45198
    assert r2.diameter == 20
    assert not r2.truncated
    full = DeviceChecker(m, **KW).run()
    assert r2.level_sizes == full.level_sizes


def test_device_checkpoint_rejects_other_config(tmp_path):
    import dataclasses

    path = str(tmp_path / "dev.npz")
    DeviceChecker(
        _shipped(), checkpoint_path=path, checkpoint_every=2,
        max_states=5_000, **KW,
    ).run()
    other = CompactionModel(
        dataclasses.replace(pe.SHIPPED_CFG, max_crash_times=2)
    )
    with pytest.raises(ValueError, match="different configuration"):
        DeviceChecker(other, checkpoint_path=path, **KW).run(resume=True)
    # a non-frame file fails with one clean message, not a zip error
    bad = str(tmp_path / "bad.npz")
    with open(bad, "wb") as f:
        f.write(b"not a frame")
    with pytest.raises(ValueError, match="unrecognized checkpoint"):
        DeviceChecker(
            _shipped(), checkpoint_path=bad, **KW
        ).run(resume=True)


def test_device_resume_trace_spans_checkpoint(tmp_path):
    """A violation found after resume replays a valid counterexample
    THROUGH the checkpointed prefix, with the same violating gid as an
    uninterrupted run (dedup order is deterministic)."""
    m = _shipped()
    inv = ("CompactedLedgerLeak",)
    path = str(tmp_path / "dev.npz")
    full = DeviceChecker(m, invariants=inv, **KW).run()
    r1 = DeviceChecker(
        m, invariants=inv, checkpoint_path=path, checkpoint_every=2,
        max_states=6_000, **KW,
    ).run()
    assert r1.truncated and r1.violation is None
    r2 = DeviceChecker(
        m, invariants=inv, checkpoint_path=path, **KW
    ).run(resume=True)
    assert r2.violation == "CompactedLedgerLeak"
    assert r2.diameter == 12
    assert r2.violation_gid == full.violation_gid
    assert r2.trace == full.trace
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r2.trace, r2.trace_actions, "CompactedLedgerLeak"
    )


def test_device_frontier_window_resume(tmp_path):
    """Frontier-window mode checkpoints only the live rows window;
    resume restores it at window offset 0 and stays exact."""
    m = _shipped()
    path = str(tmp_path / "fw.npz")
    fkw = dict(
        sub_batch=256, visited_cap=1 << 16,
        rows_window="frontier", row_cap_states=1 << 13,
    )
    DeviceChecker(
        m, checkpoint_path=path, checkpoint_every=4, max_states=9_000,
        **fkw,
    ).run()
    r = DeviceChecker(m, checkpoint_path=path, **fkw).run(resume=True)
    assert r.distinct_states == 45198 and r.diameter == 20


# ---- HBM-exhaustion recovery -----------------------------------------


def test_device_oom_recovery_completes(monkeypatch, tmp_path):
    """An injected RESOURCE_EXHAUSTED mid-run rebuilds from the last
    frame at degraded capacity and COMPLETES — hbm_recovered >= 1, no
    truncation, exact published count (the acceptance criterion)."""
    monkeypatch.setenv("PTT_FAULT", "oom@level:7")
    faults.reset()
    path = str(tmp_path / "oom.npz")
    ck = DeviceChecker(
        m := _shipped(), checkpoint_path=path, checkpoint_every=1, **KW
    )
    r = ck.run()
    assert r.hbm_recovered == 1
    assert not r.truncated and r.stop_reason is None
    assert r.distinct_states == 45198 and r.diameter == 20
    # degraded capacity was actually applied
    assert ck._headroom_frozen
    full = DeviceChecker(m, **KW).run()
    assert r.level_sizes == full.level_sizes


def test_device_oom_without_frame_truncates(monkeypatch):
    """No checkpoint configured: exhaustion keeps the honest
    poison-and-truncate contract (stop_reason "hbm")."""
    monkeypatch.setenv("PTT_FAULT", "oom@level:3")
    faults.reset()
    r = DeviceChecker(_shipped(), **KW).run()
    assert r.truncated and r.stop_reason == "hbm"
    assert r.hbm_recovered == 0
    assert 0 < r.distinct_states < 45198


def test_fpset_fail_injection_fail_stops(monkeypatch):
    """An injected fpset stage overflow must abort loudly (states were
    dropped; the counts cannot be trusted) — never a silent drop."""
    monkeypatch.setenv("PTT_FAULT", "fpset_fail@flush:2")
    faults.reset()
    with pytest.raises(RuntimeError, match="probe overflow"):
        DeviceChecker(_shipped(), **KW).run()


# ---- preemption-safe shutdown ----------------------------------------


def test_device_preemption_checkpoints_and_resumes(monkeypatch, tmp_path):
    """SIGTERM mid-run (delivered by the sigterm fault — exactly what a
    TPU-VM preemption sends) checkpoints at the next level boundary and
    exits with stop_reason "preempted"; resume is exact."""
    monkeypatch.setenv("PTT_FAULT", "sigterm@level:6")
    faults.reset()
    m = _shipped()
    path = str(tmp_path / "pre.npz")
    r1 = DeviceChecker(
        m, checkpoint_path=path, checkpoint_every=100, **KW
    ).run()
    assert r1.truncated and r1.stop_reason == "preempted"
    assert os.path.exists(path)  # the preemption wrote the frame
    assert 0 < r1.distinct_states < 45198
    monkeypatch.delenv("PTT_FAULT")
    faults.reset()
    r2 = DeviceChecker(m, checkpoint_path=path, **KW).run(resume=True)
    assert r2.distinct_states == 45198 and r2.diameter == 20


# ---- crash (kill -9 class) + resume parity: the subprocess drill -----


def _run_sub(tmp_path, *args, fault=None, expect_kill=False):
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


@pytest.mark.parametrize(
    "invariant,kill_level,every,depth",
    [
        ("CompactedLedgerLeak", 8, 2, 12),
        ("DuplicateNullKeyMessage", 3, 2, 4),
    ],
)
def test_kill_resume_parity_device(
    tmp_path, invariant, kill_level, every, depth
):
    """kill@level:k (hard os._exit mid-run, subprocess) + -recover
    reproduces the uninterrupted run's level sizes, first-violation
    gid, and trace exactly — on both published bug oracles."""
    path = str(tmp_path / "kill.npz")
    _run_sub(
        tmp_path, "--checkpoint", path, "--invariant", invariant,
        "--every", str(every),
        fault=f"kill@level:{kill_level}", expect_kill=True,
    )
    assert os.path.exists(path)  # died after frames were written
    resumed = _run_sub(
        tmp_path, "--checkpoint", path, "--invariant", invariant,
        "--resume",
    )
    full = DeviceChecker(
        _shipped(), invariants=(invariant,), **KW
    ).run()
    assert resumed["violation"] == invariant == full.violation
    assert resumed["diameter"] == depth == full.diameter
    assert resumed["distinct_states"] == full.distinct_states
    assert resumed["level_sizes"] == full.level_sizes
    assert resumed["violation_gid"] == full.violation_gid
    assert resumed["trace"] == [repr(s) for s in full.trace]
    assert resumed["trace_actions"] == list(full.trace_actions)


@pytest.mark.parametrize(
    "invariant,kill_level,depth",
    [
        ("CompactedLedgerLeak", 8, 12),
        ("DuplicateNullKeyMessage", 3, 4),
    ],
)
def test_kill_resume_parity_sharded(tmp_path, invariant, kill_level, depth):
    """The same crash-resume drill on the sharded engine (CPU mesh)."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    path = str(tmp_path / "skill.npz")
    _run_sub(
        tmp_path, "--engine", "sharded", "--checkpoint", path,
        "--invariant", invariant, "--every", "2",
        fault=f"kill@level:{kill_level}", expect_kill=True,
    )
    assert os.path.exists(path)
    resumed = _run_sub(
        tmp_path, "--engine", "sharded", "--checkpoint", path,
        "--invariant", invariant, "--resume",
    )
    full = ShardedDeviceChecker(
        _shipped(), n_devices=4, invariants=(invariant,),
        sub_batch=512, visited_cap=1 << 13,
    ).run()
    assert resumed["violation"] == invariant == full.violation
    assert resumed["diameter"] == depth == full.diameter
    assert resumed["distinct_states"] == full.distinct_states
    assert resumed["level_sizes"] == full.level_sizes
    assert resumed["violation_gid"] == full.violation_gid
    assert resumed["trace"] == [repr(s) for s in full.trace]


# ---- fault-harness + frame-codec units -------------------------------


def test_fault_spec_parsing(monkeypatch):
    monkeypatch.setenv("PTT_FAULT", "oom@level:7, fpset_fail@flush:3")
    faults.reset()
    assert faults.poll("level", 6) == ()
    assert faults.poll("level", 7) == ("oom",)
    assert faults.poll("level", 7) == ()  # single-shot per process
    assert faults.poll("flush", 3) == ("fpset_fail",)
    monkeypatch.setenv("PTT_FAULT", "bogus@level:1")
    faults.reset()
    with pytest.raises(ValueError, match="unknown PTT_FAULT kind"):
        faults.poll("level", 1)
    monkeypatch.setenv("PTT_FAULT", "oom@level")
    faults.reset()
    with pytest.raises(ValueError, match="bad PTT_FAULT spec"):
        faults.poll("level", 1)
    monkeypatch.delenv("PTT_FAULT")
    faults.reset()
    assert faults.poll("level", 1) == ()


def test_fpset_frame_codec_roundtrip():
    """pack_fpset/unpack_fpset: occupied slots round-trip exactly, for
    single-device (1-D) and per-shard (2-D) column layouts."""
    S = 0xFFFFFFFF
    rng = np.random.RandomState(7)
    for shape in [(65,), (4, 33)]:
        cols = [
            np.full(shape, S, np.uint32) for _ in range(2)
        ]
        cap = shape[-1] - 1
        flat_occ = rng.rand(*cols[0][..., :cap].shape) < 0.3
        vals0 = rng.randint(0, S, size=flat_occ.shape).astype(np.uint32)
        vals1 = rng.randint(0, S, size=flat_occ.shape).astype(np.uint32)
        cols[0][..., :cap][flat_occ] = vals0[flat_occ]
        cols[1][..., :cap][flat_occ] = vals1[flat_occ]
        packed = ckpt.pack_fpset(cols)
        # npz round-trip (the codec feeds save_frame)
        out = ckpt.unpack_fpset(
            {k: np.asarray(v) for k, v in packed.items()}, 2
        )
        for a, b in zip(cols, out):
            assert np.array_equal(a, b), shape


def test_frame_format_version_gate(tmp_path):
    path = str(tmp_path / "f.npz")
    ckpt.save_frame(path, "sig1", {"x": np.arange(3)})
    d = ckpt.load_frame(path, "sig1")
    assert list(d["x"]) == [0, 1, 2]
    with pytest.raises(ValueError, match="different configuration"):
        ckpt.load_frame(path, "sig2")
    # a frame from a NEWER format must be refused, not misread
    np.savez_compressed(
        path,
        __format__=np.int64(ckpt.FORMAT_VERSION + 1),
        sig=np.frombuffer(b"sig1", dtype=np.uint8),
    )
    with pytest.raises(ValueError, match="newer than this build"):
        ckpt.load_frame(path, "sig1")
    with pytest.raises(FileNotFoundError):
        ckpt.load_frame(str(tmp_path / "missing.npz"), "sig1")


def test_preemption_watcher_signal_sets_flag():
    import signal

    with ckpt.PreemptionWatcher(enabled=True) as w:
        assert not w.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert w.requested
    # handlers restored on exit
    assert signal.getsignal(signal.SIGTERM) != w._handle


# ---- the configuration signature: frames and warm artifacts survive ---

_SIG_SHIPPED = (
    "Constants(message_sent_limit=3, compaction_times_limit=3, "
    "model_consumer=False, consume_times_limit=2, num_keys=2, "
    "num_values=2, retain_null_key=True, max_crash_times=1, "
    "model_producer=False)"
)
_SIG_253K = _SIG_SHIPPED.replace(
    "retain_null_key=True", "retain_null_key=False"
).replace("model_producer=False", "model_producer=True")
_SIG_INVS = ("TypeSafe", "CompactionHorizonCorrectness")


@pytest.mark.parametrize("engine", ["device", "sharded"])
@pytest.mark.parametrize(
    "cfg,model_sig",
    [("compaction.cfg", _SIG_SHIPPED), ("compaction_253k.cfg", _SIG_253K)],
)
def test_config_signature_is_the_one_frames_were_written_under(
    cfg, model_sig, engine
):
    """The signature a frame or a warm artifact must agree on, as the
    commit before the kernel selectors went computed it (PR 30): it
    still names ``visited_impl='fpset'`` / ``sharded_device_r6_fpset``,
    so what that commit wrote loads under this one."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )
    from pulsar_tlaplus_tpu.utils import cfg as cfgmod
    from tests.helpers import SPECS

    with open(os.path.join(SPECS, cfg)) as f:
        m = CompactionModel(cfgmod.to_constants(cfgmod.parse_cfg(f.read())))
    if engine == "device":
        got = DeviceChecker(m)._config_sig()
        want = repr(
            (
                ("check_deadlock", "True"),
                ("engine", "'device_bfs_r7'"),
                ("invariants", repr(_SIG_INVS)),
                ("key_cols", "2"),
                ("key_exact", "True"),
                ("model", repr(model_sig)),
                ("rows_window", "'all'"),
                ("state_bits", "42"),
                ("visited_impl", "'fpset'"),
            )
        )
    else:
        got = ShardedDeviceChecker(m, n_devices=4)._config_sig()
        want = repr(
            (
                model_sig, _SIG_INVS, True, 42, 2, True, 4, ("shard",),
                28, "sharded_device_r6_fpset",
            )
        )
    assert got == want
