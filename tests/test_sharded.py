"""Multi-chip determinism tests (SURVEY.md §4e) on a virtual CPU mesh:
n_devices in {1, 2, 4, 8} must produce identical distinct-state counts,
diameters, and verdicts."""

import pytest

from pulsar_tlaplus_tpu.engine.sharded import ShardedChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS


@pytest.mark.parametrize("nd", [1, 2, 4, 8])
def test_sharded_matches_oracle(nd):
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedChecker(
        CompactionModel(c),
        n_devices=nd,
        invariants=(),
        frontier_chunk=256,
        visited_cap=1 << 12,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_sharded_hash_dedup_matches_oracle():
    """Hash-table visited sets per shard (with growth/rehash) produce
    the exact oracle counts."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedChecker(
        CompactionModel(c),
        n_devices=4,
        invariants=(),
        frontier_chunk=256,
        visited_cap=1 << 8,  # force rehash growth
        dedup_mode="hash",
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_sharded_2d_mesh_matches_oracle():
    """2-D (dcn, ici) mesh with hierarchical fingerprint routing:
    identical counts on a 2x4 virtual mesh (SURVEY.md §2.2-E11)."""
    from pulsar_tlaplus_tpu.parallel.mesh import make_mesh2d

    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedChecker(
        CompactionModel(c),
        mesh=make_mesh2d(2, 4),
        invariants=(),
        frontier_chunk=256,
        visited_cap=1 << 12,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_sharded_checkpoint_resume(tmp_path):
    """Interrupt a sharded run at a level-boundary checkpoint and resume;
    the final counts must match an uninterrupted run."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    ckpt = str(tmp_path / "sharded.npz")
    metrics = str(tmp_path / "metrics.jsonl")
    first = ShardedChecker(
        CompactionModel(c),
        n_devices=2,
        invariants=(),
        frontier_chunk=256,
        visited_cap=1 << 12,
        checkpoint_path=ckpt,
        checkpoint_every=2,
        metrics_path=metrics,
        time_budget_s=0.0,  # truncate ASAP after the first checkpoint
    )
    r1 = first.run()
    assert r1.truncated
    import os

    assert os.path.exists(ckpt)
    second = ShardedChecker(
        CompactionModel(c),
        n_devices=2,
        invariants=(),
        frontier_chunk=256,
        visited_cap=1 << 12,
        checkpoint_path=ckpt,
    )
    r2 = second.run(resume=True)
    assert r2.distinct_states == want.distinct_states
    assert r2.diameter == want.diameter
    assert os.path.getsize(metrics) > 0


def test_sharded_violation_trace_valid():
    c = SMALL_CONFIGS["shipped"]
    got = ShardedChecker(
        CompactionModel(c),
        n_devices=4,
        invariants=("CompactedLedgerLeak",),
        frontier_chunk=512,
        visited_cap=1 << 13,
    ).run()
    assert got.violation == "CompactedLedgerLeak"
    assert got.diameter == 12  # shortest-counterexample depth is device-count
    # independent (BFS level = depth), even if the reported state differs
    from tests.helpers import assert_valid_counterexample

    assert_valid_counterexample(
        c, got.trace, got.trace_actions, "CompactedLedgerLeak"
    )
