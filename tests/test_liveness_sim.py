"""Liveness (SURVEY.md §2.2-E10) and simulation-mode (E9) tests."""

import dataclasses

import pytest

from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
from pulsar_tlaplus_tpu.engine.simulate import Simulator
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample

LIVENESS_CASES = {
    "producer_on": SMALL_CONFIGS["producer_on"],
    "two_crashes": SMALL_CONFIGS["two_crashes"],
    # Consumer modeled: consumeTimes never advances (the spec's stub
    # consumer, compaction.tla:185-186 and the TODO at :299), so the goal is
    # unreachable and the Consumer self-loop is a fair not-goal cycle.
    "consumer_on": dataclasses.replace(
        SMALL_CONFIGS["producer_on"], model_consumer=True
    ),
}


@pytest.mark.parametrize("name", sorted(LIVENESS_CASES))
@pytest.mark.parametrize("fairness", ["none", "wf_next"])
def test_liveness_matches_oracle(name, fairness):
    c = LIVENESS_CASES[name]
    want_holds, _ = pe.check_eventually(c, fairness)
    got = LivenessChecker(
        CompactionModel(c),
        fairness=fairness,
        frontier_chunk=512,
        visited_cap=1 << 13,
    ).run()
    assert got.holds == want_holds


def test_liveness_scales_past_round2_cap():
    """VERDICT r2 #8: liveness exploration now runs on the device
    engine, so a state space far beyond the old host-staged explorer's
    comfort zone (253,361 states, the published full-cfg oracle) gets a
    Termination verdict in one run."""
    c = dataclasses.replace(
        pe.SHIPPED_CFG, model_producer=True, retain_null_key=False
    )
    got = LivenessChecker(
        CompactionModel(c),
        fairness="none",
        frontier_chunk=4096,
        visited_cap=1 << 18,
    ).run()
    assert got.distinct_states == 253361
    want_holds, _ = pe.check_eventually(c, "none")
    assert got.holds == want_holds


def test_liveness_wf_holds_on_plain_configs():
    # the substantive verdict: Termination genuinely holds under
    # WF_vars(Next) (ledger ids grow monotonically to the limit), and is
    # trivially violated without fairness (TLC's stuttering semantics)
    c = SMALL_CONFIGS["producer_on"]
    assert LivenessChecker(CompactionModel(c), fairness="wf_next",
                           visited_cap=1 << 13).run().holds
    assert not LivenessChecker(CompactionModel(c), fairness="none",
                               visited_cap=1 << 13).run().holds


def test_simulation_finds_leak_violation():
    m = CompactionModel(pe.SHIPPED_CFG)
    sim = Simulator(
        m,
        invariants=("TypeSafe", "CompactedLedgerLeak"),
        n_walkers=512,
        depth=48,
        seed=1,
    )
    r = sim.run()
    assert r.violation == "CompactedLedgerLeak"
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )


def test_simulation_clean_on_active_invariants():
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    r = Simulator(m, n_walkers=256, depth=32, seed=0).run()
    assert r.violation is None
    assert r.states_visited == 256 * 33


def test_liveness_wf_next_at_full_cfg_scale():
    """VERDICT r3 #5: wf_next must materialize the full edge list at
    the 253,361-state published-oracle scale — the round-3 scale test
    used fairness="none", which never builds edges.  The device
    merge-join sweep (key->gid table + one sort per chunk) makes this
    tractable; the verdict must match the Python oracle's wf_next
    semantics on the same config."""
    c = dataclasses.replace(
        pe.SHIPPED_CFG, model_producer=True, retain_null_key=False
    )
    got = LivenessChecker(
        CompactionModel(c),
        fairness="wf_next",
        frontier_chunk=8192,
        visited_cap=1 << 18,
    ).run()
    assert got.distinct_states == 253361
    # the oracle's graph analysis at 253k states is slow but feasible
    want_holds, _ = pe.check_eventually(c, "wf_next")
    assert got.holds == want_holds


@pytest.mark.parametrize("fairness", ["none", "wf_next"])
def test_liveness_sharded_exploration_matches_oracle(fairness):
    """Round 5 (VERDICT r4 #7): LivenessChecker can explore on the
    mesh-sharded engine; the per-shard row stores are remapped to a
    dense gid space before the (single-device) edge sweep, and the
    verdict matches the oracle exactly."""
    c = LIVENESS_CASES["producer_on"]
    want_holds, _ = pe.check_eventually(c, fairness)
    got = LivenessChecker(
        CompactionModel(c),
        fairness=fairness,
        frontier_chunk=512,
        visited_cap=1 << 13,
        n_devices=4,
    ).run()
    assert got.holds == want_holds
    want = pe.check(c, invariants=())
    assert got.distinct_states == want.distinct_states
