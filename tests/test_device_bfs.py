"""Differential tests for the device-resident engine (engine/device_bfs.py):
must match the Python oracle exactly on counts, diameters, verdicts, and
produce replayable counterexample traces — same bar as the round-1 engine
(SURVEY.md §4a/§4b), plus growth/truncation behaviors specific to the
bound-tracking driver."""

import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample


@pytest.mark.parametrize("name", sorted(set(SMALL_CONFIGS) - {"shipped"}))
def test_device_engine_matches_oracle_small(name):
    c = SMALL_CONFIGS[name]
    want = pe.check(c, invariants=())
    got = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=256,
        visited_cap=1 << 12, frontier_cap=1 << 12,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_device_engine_growth_matches_oracle():
    """Start every capacity tiny so the run forces visited + frontier
    growth (and the mid-level sync path); counts must still be exact."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=64,
        visited_cap=1 << 6, frontier_cap=1 << 6, group=2,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_device_engine_shipped_cfg_published_count():
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, sub_batch=2048, visited_cap=1 << 16, frontier_cap=1 << 15
    ).run()
    assert r.distinct_states == 45198  # compaction.tla:23
    assert r.diameter == 20
    assert r.violation is None and not r.deadlock


def test_device_engine_leak_counterexample():
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, invariants=("CompactedLedgerLeak",), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    ).run()
    assert r.violation == "CompactedLedgerLeak"
    assert r.diameter == 12  # oracle's shortest-trace depth
    assert len(r.trace) == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )


def test_device_engine_duplicate_null_key_counterexample():
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, invariants=("DuplicateNullKeyMessage",), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    ).run()
    assert r.violation == "DuplicateNullKeyMessage"
    assert r.diameter == 4
    assert len(r.trace) == 4
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "DuplicateNullKeyMessage"
    )


def test_device_engine_host_seeded_matches_oracle():
    """A host-enumerated BFS prefix (warm start) must not change counts,
    diameter, or verdicts; the handoff level structure must line up."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    m = CompactionModel(c)
    seed = m.host_seed(max_level_states=40, max_total=120)
    assert len(seed[3]) > 1  # actually seeds multiple levels
    got = DeviceChecker(
        m, invariants=(), sub_batch=64, visited_cap=1 << 10,
        frontier_cap=1 << 10,
    ).run(seed=seed)
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_device_engine_host_seeded_violation_trace():
    """A violation discovered after the seeded prefix must replay a
    valid counterexample THROUGH the prefix (seed parents/lanes exact)."""
    m = CompactionModel(pe.SHIPPED_CFG)
    seed = m.host_seed(max_level_states=3000, max_total=5000)
    assert len(seed[3]) > 2
    r = DeviceChecker(
        m, invariants=("CompactedLedgerLeak",), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    ).run(seed=seed)
    assert r.violation == "CompactedLedgerLeak"
    assert r.diameter == 12
    assert len(r.trace) == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )


def test_device_engine_host_seeded_violation_inside_seed():
    """An invariant violated by a state inside the seed prefix is still
    reported (the seed pipeline fuses the same invariant checks), and
    the diameter is the violation's level even when the seed runs much
    deeper than the violating state."""
    m = CompactionModel(pe.SHIPPED_CFG)
    seed = m.host_seed(max_level_states=12000, max_total=20000)
    assert len(seed[3]) > 4  # seed strictly deeper than the depth-4 bug
    r = DeviceChecker(
        m, invariants=("DuplicateNullKeyMessage",), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    ).run(seed=seed)
    assert r.violation == "DuplicateNullKeyMessage"
    assert r.diameter == 4  # depth-4 bug: inside the seeded prefix
    assert len(r.trace) == 4


def test_device_engine_append_chunking_matches_oracle():
    """Force the chunked append scan (C > 1) with an append_chunk that
    does NOT divide ACAP, so the scan's padded tail window is exercised
    — a clamped payload slice here would silently corrupt the row store
    (round-3 review regression)."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    m = CompactionModel(c)
    assert (64 * m.A) % 96  # ACAP not a multiple -> pad path taken
    got = DeviceChecker(
        m, invariants=(), sub_batch=64, visited_cap=1 << 10,
        frontier_cap=1 << 10, append_chunk=96, flush_factor=1,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_device_engine_flush_factor_matches_oracle():
    """Accumulating several expand windows per flush (the round-3
    amortization) must not change counts, diameter, or verdicts."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=128,
        visited_cap=1 << 10, frontier_cap=1 << 10, flush_factor=4,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_device_engine_full_cfg_published_count():
    """The second published oracle (compaction.tla:23): producer
    modeled, RetainNullKey=FALSE — 253,361 distinct states, diameter 23
    — pinned on the TPU device engine itself (VERDICT r2 #7; round 2
    pinned it only on the Python oracle)."""
    import dataclasses

    c = dataclasses.replace(
        pe.SHIPPED_CFG, model_producer=True, retain_null_key=False
    )
    r = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=4096,
        visited_cap=1 << 18, frontier_cap=1 << 17, flush_factor=2,
    ).run()
    assert r.distinct_states == 253361
    assert r.diameter == 23
    assert r.violation is None and not r.deadlock


def test_device_engine_max_states_truncation():
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    r = DeviceChecker(
        m, invariants=(), sub_batch=64, visited_cap=1 << 10,
        frontier_cap=1 << 10, max_states=40,
    ).run()
    assert r.truncated
    assert r.distinct_states <= 40 + 64 * m.A


# ---- frontier-window row store (round 5, VERDICT r4 #2) --------------


def test_frontier_window_matches_oracle():
    """rows_window="frontier" with a window far smaller than the state
    space: every level boundary slides the frontier to offset 0 and
    drops older rows; counts/diameter must still be exact."""
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, sub_batch=256, visited_cap=1 << 16,
        rows_window="frontier", row_cap_states=1 << 13,
    ).run()
    assert r.distinct_states == 45198
    assert r.diameter == 20
    assert r.violation is None and not r.deadlock and not r.truncated


def test_frontier_window_violation_trace():
    """Counterexample traces never needed rows: a violation found many
    shifts deep must still replay exactly from the parent/lane logs."""
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, invariants=("CompactedLedgerLeak",), sub_batch=256,
        visited_cap=1 << 16,
        rows_window="frontier", row_cap_states=1 << 13,
    ).run()
    assert r.violation == "CompactedLedgerLeak"
    assert r.diameter == 12
    assert len(r.trace) == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )


def test_frontier_window_host_seeded_matches_oracle():
    """Seed prefix + frontier window: the first boundary shift drops the
    seed levels' rows; counts must be unchanged."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    m = CompactionModel(c)
    seed = m.host_seed(max_level_states=40, max_total=120)
    got = DeviceChecker(
        m, invariants=(), sub_batch=64, visited_cap=1 << 10,
        rows_window="frontier", row_cap_states=1 << 11,
    ).run(seed=seed)
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_frontier_window_exhaustion_stops_honestly():
    """A window too small for a mid-BFS level: the run keeps deduping/
    counting to the level boundary, then stops with stop_reason
    "row_window" instead of corrupting rows or crashing."""
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, sub_batch=64, visited_cap=1 << 16,
        rows_window="frontier", row_cap_states=1 << 10,
    ).run()
    assert r.truncated
    assert r.stop_reason == "row_window"
    assert 0 < r.distinct_states < 45198


@pytest.mark.parametrize(
    "engine,param",
    [("device", p) for p in (
        "visited_impl", "compact_impl", "probe_impl", "expand_impl",
        "sieve_impl",
    )] + [("sharded", p) for p in ("visited_impl", "compact_impl")],
)
def test_kernel_selectors_are_not_constructor_parameters(engine, param):
    """One implementation per kernel stage: the constructors take no
    selector, and the benchmark's surface (``seed_cap`` among it) is
    still accepted."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    if engine == "device":
        DeviceChecker(m, seed_cap=1 << 21, rows_window="frontier")
        with pytest.raises(TypeError, match=param):
            DeviceChecker(m, **{param: "fpset"})
    else:
        with pytest.raises(TypeError, match=param):
            ShardedDeviceChecker(m, n_devices=2, **{param: "fpset"})
