"""Property and differential tests for the fpset visited-set subsystem
(round 6 tentpole): the table must behave as an exact set (insert/
lookup round-trips, adversarial same-key batches, growth-preserving
rehash, loud failure on overload), and the fpset-backed device engine
must match the legacy sort-merge flush STATE FOR STATE — same counts,
same levels, same gid assignment, same trace logs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS


# ---- table properties ------------------------------------------------


@pytest.mark.parametrize("ncols", [2, 3])
def test_insert_lookup_roundtrip(ncols):
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 2**32 - 2, size=(4000, ncols), dtype=np.uint32)
    n_unique = len(np.unique(keys, axis=0))
    s = fpset.FPSet(ncols, cap=1 << 10)
    kcols = tuple(keys[:, i] for i in range(ncols))
    is_new = np.asarray(s.insert(kcols))
    assert int(is_new.sum()) == n_unique == s.n
    # every inserted key is a member; a re-insert finds only duplicates
    assert np.asarray(s.contains(kcols)).all()
    assert int(np.asarray(s.insert(kcols)).sum()) == 0
    # disjoint fresh keys are not members
    other = rng.integers(2**32 - 2, 2**32 - 1, size=(500, ncols),
                         dtype=np.uint32)
    assert not np.asarray(s.contains(tuple(other[:, i]
                                           for i in range(ncols)))).any()


def test_adversarial_same_key_batches():
    """Batches dominated by equal-key groups: exactly one winner per
    distinct key, and it is the FIRST (minimum-lane) occurrence — the
    sort-merge flush's discovery order, which the engine's gid
    assignment depends on."""
    rng = np.random.default_rng(11)
    # draw from a tiny pool so most lanes are in-batch duplicates
    pool = rng.integers(0, 2**31, size=(37, 3), dtype=np.uint32)
    idx = rng.integers(0, len(pool), size=2048)
    keys = pool[idx]
    expected = np.zeros(len(keys), bool)
    seen = set()
    for i, j in enumerate(idx):
        if int(j) not in seen:
            seen.add(int(j))
            expected[i] = True
    s = fpset.FPSet(3, cap=1 << 12)
    got = np.asarray(s.insert(tuple(keys[:, i] for i in range(3))))
    assert np.array_equal(got, expected)
    assert s.n == len(pool)


def test_growth_preserves_membership():
    """Inserting far past the initial capacity forces repeated
    double-and-rehash; membership and uniqueness counts must be exact
    across every growth step."""
    rng = np.random.default_rng(3)
    s = fpset.FPSet(2, cap=1 << 6)
    all_keys = []
    total_new = 0
    for _ in range(6):
        batch = rng.integers(0, 2**31, size=(700, 2), dtype=np.uint32)
        all_keys.append(batch)
        total_new += int(np.asarray(
            s.insert((batch[:, 0], batch[:, 1]))
        ).sum())
    stacked = np.concatenate(all_keys)
    assert s.n == total_new == len(np.unique(stacked, axis=0))
    assert s.cap >= 2 * s.n  # load-factor contract held through growth
    assert np.asarray(s.contains((stacked[:, 0], stacked[:, 1]))).all()


def test_failure_count_on_overload():
    """More distinct keys than the table can hold: the unresolved lanes
    MUST surface in n_failed (and the wrapper must raise) — never a
    silent drop."""
    cap = 1 << 6
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2**31, size=(4 * cap, 2), dtype=np.uint32)
    cols = fpset.empty_cols(cap, 2)
    is_new, cols, n_failed, _rounds = fpset.lookup_or_insert(
        cols, (keys[:, 0], keys[:, 1]),
        jnp.ones((len(keys),), jnp.bool_),
    )
    assert int(n_failed) > 0
    assert int(np.asarray(is_new).sum()) + int(n_failed) >= len(keys) - cap

    class NoGrow(fpset.FPSet):
        def reserve(self, n):  # defeat auto-growth to hit the overload
            return self

    s = NoGrow(2, cap=cap)
    with pytest.raises(RuntimeError, match="probe overflow"):
        s.insert((keys[:, 0], keys[:, 1]))


def test_staged_compaction_matches_single_loop():
    """The staged (dense -> compacted) probe schedule must make exactly
    the decisions of the plain single-loop probe: same winners, same
    final table — the stages are a cost optimization, not a semantics
    change."""
    rng = np.random.default_rng(13)
    cap = 1 << 12
    pool = rng.integers(0, 2**31, size=(1500, 2), dtype=np.uint32)
    keys = pool[rng.integers(0, len(pool), size=4096)]
    kcols = (jnp.asarray(keys[:, 0]), jnp.asarray(keys[:, 1]))
    valid = jnp.ones((len(keys),), jnp.bool_)
    staged_new, staged_cols, nf, _ = fpset.lookup_or_insert(
        fpset.empty_cols(cap, 2), kcols, valid
    )
    simple_new, simple_cols, _, pending, _ = fpset.probe_insert(
        fpset.empty_cols(cap, 2), kcols, valid
    )
    assert int(nf) == 0 and not bool(np.asarray(pending).any())
    assert np.array_equal(np.asarray(staged_new), np.asarray(simple_new))
    for a, b in zip(staged_cols, simple_cols):
        assert np.array_equal(np.asarray(a)[:cap], np.asarray(b)[:cap])


# ---- engine differential: fpset vs the legacy sort-merge flush -------


def test_fpset_engine_matches_sort_engine_state_for_state():
    """Same model, both visited implementations: identical counts,
    levels, AND identical row stores / parent / lane logs — the fpset
    flush must assign every gid exactly like the sort-merge flush."""
    c = SMALL_CONFIGS["producer_on"]
    m = CompactionModel(c)
    results = {}
    for impl in ("fpset", "sort"):
        ck = DeviceChecker(
            CompactionModel(c), invariants=(), sub_batch=64,
            visited_cap=1 << 10, frontier_cap=1 << 10, group=2,
            visited_impl=impl,
        )
        r = ck.run()
        n = r.distinct_states
        results[impl] = (
            r,
            np.asarray(ck.last_bufs["rows"][: n * m.layout.W]).copy(),
            np.asarray(ck.last_bufs["parent"][:n]).copy(),
            np.asarray(ck.last_bufs["lane"][:n]).copy(),
        )
    rf, rows_f, par_f, lane_f = results["fpset"]
    rs, rows_s, par_s, lane_s = results["sort"]
    assert rf.distinct_states == rs.distinct_states
    assert rf.diameter == rs.diameter
    assert rf.level_sizes == rs.level_sizes
    assert np.array_equal(rows_f, rows_s)
    assert np.array_equal(par_f, par_s)
    assert np.array_equal(lane_f, lane_s)


@pytest.mark.parametrize("impl", ["fpset", "sort"])
def test_engine_shipped_oracle_both_impls(impl):
    """45,198 / diameter 20 (compaction.tla:23) pinned on BOTH visited
    implementations explicitly (the rest of the suite exercises the
    default; this stays meaningful if the default ever flips back)."""
    r = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15, visited_impl=impl,
    ).run()
    assert r.distinct_states == 45198
    assert r.diameter == 20
    assert r.violation is None and not r.deadlock


def test_fpset_full_cfg_published_count():
    """The second published oracle (253,361 / diameter 23) on the
    fpset-backed engine explicitly, with growth forced from a small
    initial table (ISSUE r6 acceptance)."""
    import dataclasses

    c = dataclasses.replace(
        pe.SHIPPED_CFG, model_producer=True, retain_null_key=False
    )
    r = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=4096,
        visited_cap=1 << 12, frontier_cap=1 << 17, flush_factor=2,
        visited_impl="fpset",
    ).run()
    assert r.distinct_states == 253361
    assert r.diameter == 23
    assert r.violation is None and not r.deadlock


# ---- _load_seed frontier-window guard (ADVICE r5 medium) -------------


def test_load_seed_frontier_window_guard():
    """A seed whose LAST LEVEL leaves no room for one blind APAD append
    window must be rejected up front (it used to flip rows_ok on the
    first flush and overwrite live frontier rows with scratch writes —
    silent corruption)."""
    m = CompactionModel(pe.SHIPPED_CFG)
    ck = DeviceChecker(
        m, sub_batch=8192, visited_cap=1 << 16,
        rows_window="frontier", row_cap_states=1 << 10,
    )
    # the guard fires before any seed-content validation, so the seed
    # can be fabricated to land exactly on the edge: a last level too
    # big for window + append scratch, under a total the OLD guard
    # (n + SEED_CHUNK <= LCAP) accepts
    last = ck.LCAP - ck.APAD + 1
    n = min(ck.LCAP - ck.SEED_CHUNK, last + 1024)
    assert n >= last, "edge needs SEED_CHUNK < APAD at this tier"
    W = m.layout.W
    seed = (
        np.zeros((n, W), np.uint32),
        np.zeros((n,), np.int32),
        np.zeros((n,), np.int32),
        [n - last, last],
    )
    assert n + ck.SEED_CHUNK <= ck.LCAP, "edge precondition (old guard)"
    assert last + ck.APAD > ck.LCAP, "edge precondition (new guard)"
    with pytest.raises(ValueError, match="frontier rows window"):
        ck.run(seed=seed)


# ---- sharded engine differential (virtual CPU mesh) ------------------


@pytest.mark.parametrize("impl", ["fpset", "sort"])
def test_sharded_fpset_counts_match_oracle(impl):
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        CompactionModel(c), n_devices=4, invariants=(), sub_batch=64,
        visited_cap=1 << 6, group=2, visited_impl=impl,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
