"""Differential tests for the device-resident sharded engine
(engine/sharded_device.py): counts, diameters, and verdicts must be
identical to the Python oracle for EVERY shard count (SURVEY.md §4e —
multi-node determinism on a virtual CPU mesh), and counterexamples must
replay through the model exactly like the single-chip engine's."""

import pytest

from pulsar_tlaplus_tpu.engine.sharded_device import ShardedDeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample


@pytest.mark.parametrize("n", [1, 2, 8])
def test_sharded_device_counts_identical_across_meshes(n):
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        CompactionModel(c), n_devices=n, invariants=(), sub_batch=128,
        visited_cap=1 << 10,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_sharded_device_shipped_cfg_published_count():
    """45,198 distinct states / diameter 20 (compaction.tla:23) on an
    8-shard mesh — the init fanout (729 states) is routed too."""
    got = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=8, sub_batch=512,
        visited_cap=1 << 13,
    ).run()
    assert got.distinct_states == 45198
    assert got.diameter == 20
    assert got.violation is None and not got.deadlock


def test_sharded_device_leak_counterexample_replays():
    got = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=4,
        invariants=("CompactedLedgerLeak",), sub_batch=512,
        visited_cap=1 << 13,
    ).run()
    assert got.violation == "CompactedLedgerLeak"
    assert got.diameter == 12
    assert len(got.trace) == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, got.trace, got.trace_actions, "CompactedLedgerLeak"
    )


def test_sharded_device_growth_matches_oracle():
    """Tiny initial capacities force visited + store growth mid-run on
    every shard; counts must stay exact."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        CompactionModel(c), n_devices=4, invariants=(), sub_batch=64,
        visited_cap=1 << 6, group=2,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_sharded_device_flush_factor_matches_oracle():
    c = SMALL_CONFIGS["two_crashes"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        CompactionModel(c), n_devices=2, invariants=(), sub_batch=128,
        visited_cap=1 << 10, flush_factor=3,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_sharded_device_truncation():
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    r = ShardedDeviceChecker(
        m, n_devices=4, invariants=(), sub_batch=64,
        visited_cap=1 << 10, max_states=64,
    ).run()
    assert r.truncated


def test_sharded_device_checkpoint_resume_exact_count(tmp_path):
    """Truncate-and-resume on an 8-shard mesh must reach the published
    45,198 / diameter-20 oracle exactly (VERDICT r3 #6): run with a
    tiny max_states to force truncation, then resume (repeatedly, to
    cross several checkpoints) until complete."""
    ckpt = str(tmp_path / "sd.npz")

    def make(max_states):
        ck = ShardedDeviceChecker(
            CompactionModel(pe.SHIPPED_CFG), n_devices=8, sub_batch=512,
            visited_cap=1 << 13, max_states=max_states,
            checkpoint_path=ckpt, checkpoint_every=2,
        )
        return ck

    r = make(2_000).run()
    assert r.truncated
    r = make(20_000).run(resume=True)
    assert r.truncated
    r = make(1 << 26).run(resume=True)
    assert not r.truncated
    assert r.distinct_states == 45198
    assert r.diameter == 20
    assert r.violation is None and not r.deadlock


def test_sharded_device_resume_rejects_other_config(tmp_path):
    ckpt = str(tmp_path / "sd.npz")
    ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=4, sub_batch=512,
        visited_cap=1 << 13, max_states=2_000, checkpoint_path=ckpt,
    ).run()
    other = ShardedDeviceChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), n_devices=4,
        sub_batch=128, visited_cap=1 << 10, checkpoint_path=ckpt,
    )
    with pytest.raises(ValueError, match="different configuration"):
        other.run(resume=True)


def test_sharded_device_trace_spans_resume(tmp_path):
    """A counterexample found after a resume must replay across the
    checkpoint boundary (parent chain lives in the restored logs)."""
    ckpt = str(tmp_path / "sd.npz")
    r = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=4,
        invariants=("CompactedLedgerLeak",), sub_batch=512,
        visited_cap=1 << 13, max_states=9_000,
        checkpoint_path=ckpt, checkpoint_every=1,
    ).run()
    assert r.truncated and r.violation is None
    r = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=4,
        invariants=("CompactedLedgerLeak",), sub_batch=512,
        visited_cap=1 << 13, checkpoint_path=ckpt,
    ).run(resume=True)
    assert r.violation == "CompactedLedgerLeak"
    assert r.diameter == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )


def test_sharded_device_route_overflow_autorecovers():
    """A deliberately starved route capacity (route_slack << 1) must
    auto-recover (double slack, re-jit, retry the level) and still
    reach the oracle count exactly (VERDICT r3 #8)."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    ck = ShardedDeviceChecker(
        CompactionModel(c), n_devices=4, invariants=(), sub_batch=128,
        visited_cap=1 << 10, route_slack=0.03,
    )
    got = ck.run()
    assert ck.route_slack > 0.03  # recovery actually fired
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


@pytest.mark.parametrize("slices,per", [(2, 4), (4, 2)])
def test_sharded_device_2d_mesh_counts_identical(slices, per):
    """Hierarchical dcn x ici routing (owner-slice over dcn, then
    owner-chip over ici) inside the jitted round step must reproduce
    the oracle exactly on a 2-D virtual mesh (VERDICT r3 #7)."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        CompactionModel(c), n_devices=slices * per, n_slices=slices,
        invariants=(), sub_batch=128, visited_cap=1 << 10,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_sharded_device_2d_shipped_cfg_published_count():
    got = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=8, n_slices=2,
        sub_batch=512, visited_cap=1 << 13,
    ).run()
    assert got.distinct_states == 45198
    assert got.diameter == 20


def test_sharded_device_2d_counterexample_replays():
    got = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=8, n_slices=4,
        invariants=("DuplicateNullKeyMessage",), sub_batch=512,
        visited_cap=1 << 13,
    ).run()
    assert got.violation == "DuplicateNullKeyMessage"
    assert_valid_counterexample(
        pe.SHIPPED_CFG, got.trace, got.trace_actions,
        "DuplicateNullKeyMessage",
    )


def test_sharded_device_2d_route_overflow_autorecovers():
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    ck = ShardedDeviceChecker(
        CompactionModel(c), n_devices=8, n_slices=2, invariants=(),
        sub_batch=128, visited_cap=1 << 10, route_slack=0.03,
    )
    got = ck.run()
    assert ck.route_slack > 0.03
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_sharded_device_host_seeded_matches_oracle():
    """Round 5 (VERDICT r4 #4): a host-enumerated BFS prefix loads onto
    the mesh (rows round-robin by BFS index, keys routed to owners)
    without changing counts, diameter, or verdicts."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    m = CompactionModel(c)
    seed = m.host_seed(max_level_states=40, max_total=120)
    assert len(seed[3]) > 1
    got = ShardedDeviceChecker(
        m, n_devices=4, invariants=(), sub_batch=64,
        visited_cap=1 << 10,
    ).run(seed=seed)
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_sharded_device_host_seeded_violation_trace():
    """A violation found beyond the seeded prefix must replay a valid
    counterexample through remapped cross-shard parent chains."""
    m = CompactionModel(pe.SHIPPED_CFG)
    seed = m.host_seed(max_level_states=300, max_total=900)
    r = ShardedDeviceChecker(
        m, n_devices=4, invariants=("CompactedLedgerLeak",),
        sub_batch=256, visited_cap=1 << 12,
    ).run(seed=seed)
    assert r.violation == "CompactedLedgerLeak"
    assert r.diameter == 12
    assert len(r.trace) == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )
