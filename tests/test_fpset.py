"""Property tests for the fpset visited-set subsystem: the table must
behave as an exact set (insert/lookup round-trips, adversarial
same-key batches, growth-preserving rehash, loud failure on overload);
the flush is held to a Python set and the keys to a numpy murmur3; the
engines reach the published counts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS


@pytest.fixture(scope="module", autouse=True)
def _release_the_modules_programs():
    """This module loads more executables than any other (every table
    size is a program of its own), and a loaded XLA:CPU executable
    holds its memory mappings for as long as ``jax.jit``'s cache keeps
    it: a worker that went on to ``tests/test_codegen.py`` with them
    met the kernel's ``vm.max_map_count`` (65,530) inside a compile and
    died of it (PR 38).  Let them go when the module is done."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()


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


def _table_at_load(cap, load, ncols, seed):
    """``(keys [n, ncols], table columns)``: ``cap * load`` distinct
    random keys inserted into an empty table of ``cap`` slots."""
    rng = np.random.default_rng(seed)
    keys = np.unique(
        rng.integers(
            0, 2**32 - 2, size=(int(cap * load), ncols), dtype=np.uint32
        ),
        axis=0,
    )
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(ncols))
    _new, tcols, n_failed, _r, _l, _s, _ = fpset.lookup_or_insert(
        fpset.empty_cols(cap, ncols), kcols,
        jnp.ones((len(keys),), jnp.bool_),
    )
    assert int(n_failed) == 0
    return keys, tcols


def _assert_holds_exactly(tcols, keys):
    """The table's occupied slots hold ``keys`` (sorted rows), each
    once, and ``lookup`` finds every one of them."""
    occ = np.asarray(fpset.occupied_mask(tcols))
    held = np.stack([np.asarray(c)[:-1][occ] for c in tcols], axis=1)
    assert int(occ.sum()) == len(keys)
    assert np.array_equal(np.unique(held, axis=0), keys)
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(keys.shape[1]))
    found = fpset.lookup(tcols, kcols, jnp.ones((len(keys),), jnp.bool_))
    assert np.asarray(found).all()


REHASH_TEST_CHUNK = 1 << 13


@pytest.mark.parametrize("ncols", [2, 3])
@pytest.mark.parametrize("load", [0.1, 0.3, 0.5])
@pytest.mark.parametrize(
    "ocap", [1 << 11, REHASH_TEST_CHUNK, 4 * REHASH_TEST_CHUNK],
    ids=["below_chunk", "one_chunk", "four_chunks"],
)
def test_rehash_doubling_moves_exactly_the_old_keys(ocap, load, ncols):
    keys, old = _table_at_load(ocap, load, ncols, seed=ocap + ncols)
    new, rhm = fpset.rehash_cols(
        old, fpset.empty_cols(2 * ocap, ncols), chunk=REHASH_TEST_CHUNK
    )
    failed, moved, lane_rounds = fpset.rhm_logical(rhm)
    assert failed == 0 and moved == len(keys)
    assert lane_rounds >= moved
    _assert_holds_exactly(new, keys)


@pytest.mark.parametrize("ncols", [2, 3])
@pytest.mark.parametrize(
    "ocap", [1 << 11, 4 * REHASH_TEST_CHUNK],
    ids=["below_chunk", "four_chunks"],
)
def test_rehash_same_capacity_rebuild_at_half_load(ocap, ncols):
    """The tiered store's rebuild after an eviction: the new table is
    as large as the old one and ends as full (load 1/2, where the
    ladder's margins are stated)."""
    keys, old = _table_at_load(ocap, 0.5, ncols, seed=11 * ncols)
    new, rhm = fpset.rehash_cols(
        old, fpset.empty_cols(ocap, ncols), chunk=REHASH_TEST_CHUNK
    )
    assert fpset.rhm_logical(rhm)[:2] == (0, len(keys))
    _assert_holds_exactly(new, keys)


def test_rehash_with_the_shift_passes_the_chip_compacts_with():
    """``materialize="shift"`` (what a TPU process picks; the rehash
    runs the passes as one loop) packs and hands over as the CPU's
    gather does."""
    ocap = 4 * REHASH_TEST_CHUNK
    keys, old = _table_at_load(ocap, 0.4, 2, seed=23)
    new, rhm = fpset.rehash_cols(
        old, fpset.empty_cols(2 * ocap, 2), chunk=REHASH_TEST_CHUNK,
        materialize="shift",
    )
    ref, rhm_ref = fpset.rehash_cols(
        old, fpset.empty_cols(2 * ocap, 2), chunk=REHASH_TEST_CHUNK
    )
    assert fpset.rhm_logical(rhm) == fpset.rhm_logical(rhm_ref)
    assert fpset.rhm_logical(rhm)[:2] == (0, len(keys))
    for c, r in zip(new, ref):  # slot for slot; the last is the trash row
        assert np.array_equal(np.asarray(c)[:-1], np.asarray(r)[:-1])


def test_rehash_into_a_table_too_small_counts_its_failures():
    keys, old = _table_at_load(1 << 12, 0.5, 2, seed=13)
    new, rhm = fpset.rehash_cols(old, fpset.empty_cols(1 << 10, 2))
    failed, moved, _lanes = fpset.rhm_logical(rhm)
    assert moved == len(keys) == 2048
    # never a silent drop: what did not land is counted
    landed = int(np.asarray(fpset.occupied_mask(new)).sum())
    assert failed > 0 and landed + failed == moved


def test_rehash_of_a_chunk_past_the_load_contract_counts_what_it_leaves():
    """A chunk fuller than the packed buffer (5/8 of it; the contract
    is 1/2): the keys that do not fit are failures, not silent drops."""
    cap = 1 << 12
    keys, old = _table_at_load(cap, 0.7, 2, seed=19)
    new, rhm = fpset.rehash_cols(old, fpset.empty_cols(2 * cap, 2))
    failed, held, _lanes = fpset.rhm_logical(rhm)
    width = cap * fpset.REHASH_PACK_NUM // fpset.REHASH_PACK_DEN
    assert held == len(keys) > width
    assert failed == held - width
    assert int(np.asarray(fpset.occupied_mask(new)).sum()) == width


def test_rehash_presents_lanes_by_the_pending_count():
    """The mechanism (ISSUE 35): a chunk narrows with what is pending,
    where one full-width loop pays its whole tail at the chunk's
    width."""
    cap = 1 << 16
    keys, old = _table_at_load(cap, 0.5, 2, seed=17)
    _new, rhm = fpset.rehash_cols(old, fpset.empty_cols(2 * cap, 2))
    failed, moved, lane_rounds = fpset.rhm_logical(rhm)
    assert failed == 0 and moved == len(keys)
    assert lane_rounds / moved < 5
    ks = tuple(c[:cap] for c in old)
    _f, _t, _o, pending, rounds, _ = fpset.probe_insert(
        fpset.empty_cols(2 * cap, 2), ks, ~fpset.all_sentinel(ks)
    )
    assert not np.asarray(pending).any()
    assert cap * int(rounds) / moved > 8


def test_rhm_logical_sums_shards_and_reads_lane_rounds_as_64_bits():
    one = np.array([0, 5, -1, 2], np.int32)  # lo word 2^32 - 1, hi 2
    assert fpset.rhm_logical(one) == (0, 5, (2 << 32) + 2**32 - 1)
    two = np.stack([one, np.array([3, 7, 10, 0], np.int32)])
    assert fpset.rhm_logical(two) == (3, 12, (2 << 32) + 2**32 + 9)


def test_failure_count_on_overload():
    """More distinct keys than the table can hold: the unresolved lanes
    MUST surface in n_failed (and the wrapper must raise) — never a
    silent drop."""
    cap = 1 << 6
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2**31, size=(4 * cap, 2), dtype=np.uint32)
    cols = fpset.empty_cols(cap, 2)
    is_new, cols, n_failed, _rounds, _lanes, _, _ = fpset.lookup_or_insert(
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
    staged_new, staged_cols, nf, _, _, _, _ = fpset.lookup_or_insert(
        fpset.empty_cols(cap, 2), kcols, valid
    )
    simple_new, simple_cols, _, pending, _, _ = fpset.probe_insert(
        fpset.empty_cols(cap, 2), kcols, valid
    )
    assert int(nf) == 0 and not bool(np.asarray(pending).any())
    assert np.array_equal(np.asarray(staged_new), np.asarray(simple_new))
    for a, b in zip(staged_cols, simple_cols):
        assert np.array_equal(np.asarray(a)[:cap], np.asarray(b)[:cap])


# ---- the pending-driven schedule (PR 28) -----------------------------
#
# A stage of ``lookup_or_insert`` ends as soon as what is pending fits
# the next, narrower one.  The single loop below is the reference for
# every decision; the replay gives the pending count before each round,
# from which both schedules' presented lanes follow by arithmetic.


def _batch(seed, nq, cap, load, dup, K, valid_share=0.95):
    """A table pre-filled so that it ends near ``load`` once the batch
    is in, and ``nq`` lanes of which a ``dup`` share repeat an earlier
    lane or a key the table already holds; ~5% of the lanes invalid
    (a ``cli check`` flush: nine in ten)."""
    rng = np.random.default_rng(seed)
    n_fresh = max(int(nq * (1.0 - dup)), 1)
    n_pre = max(int(load * cap) - n_fresh, 0)
    pool = np.unique(
        rng.integers(0, 2**32 - 2, size=(n_fresh + n_pre + 64, K),
                     dtype=np.uint32),
        axis=0,
    )
    rng.shuffle(pool)
    pre, fresh = pool[:n_pre], pool[n_pre:n_pre + n_fresh]
    known = pool[:n_pre + n_fresh]
    keys = np.concatenate(
        [fresh, known[rng.integers(0, len(known), size=nq - len(fresh))]]
    )[:nq]
    rng.shuffle(keys)
    tcols = fpset.empty_cols(cap, K)
    if n_pre:
        _, tcols, _, pending, _, _ = fpset.probe_insert(
            tcols, tuple(jnp.asarray(pre[:, i]) for i in range(K)),
            jnp.ones((n_pre,), jnp.bool_),
        )
        assert not bool(np.asarray(pending).any())
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(K))
    valid = jnp.asarray(rng.random(nq) < valid_share)
    return tcols, kcols, valid


def _ladder(nq, dense, stages):
    """[(width, ceiling)] as the module builds it: written out again so
    the arithmetic below does not lean on the code under test."""
    steps = [(nq, min(dense, fpset.MAX_PROBES))]
    for div, limit in stages:
        limit = min(limit, fpset.MAX_PROBES)
        capi = max(nq // div, min(nq, fpset.MIN_STAGE))
        width = steps[-1][0]
        if capi >= width or limit <= dense or (
            width > fpset.QUARTER_ABOVE and 4 * capi > width
        ):
            steps[-1] = (width, max(steps[-1][1], limit))
        else:
            steps.append((capi, limit))
    return steps


def _pending_by_round(tcols, kcols, valid, ceiling):
    """Lanes pending BEFORE round r of the single loop, r = 0.. until
    nothing is pending or the ceiling is reached."""
    counts, r, pending = [], 0, valid
    while True:
        counts.append(int(np.asarray(pending).sum()))
        if counts[-1] == 0 or r >= ceiling:
            return counts
        _, tcols, _, pending, r2, _ = fpset.probe_insert(
            tcols, kcols, pending, max_probes=r + 1, start_round=r
        )
        assert int(r2) == r + 1
        r += 1


def _lane_rounds(ladder, pending, follow):
    """Presented lanes of a schedule over the replayed pending counts:
    ``follow`` hands a step over once what is pending fits the next
    (this PR); without it each step runs to its ceiling (the parent)."""
    total = r = 0
    for i, (width, ceiling) in enumerate(ladder):
        fits = ladder[i + 1][0] if follow and i + 1 < len(ladder) else 0
        while r < ceiling and r < len(pending) and pending[r] > fits:
            total += width
            r += 1
    return total


SCHEDULE_CASES = [
    # nq, cap, load, dup, K, dense_rounds, stages
    pytest.param(512, 1 << 12, 0.1, 0.0, 2, None, None, id="below-min-stage"),
    pytest.param(1024, 1 << 12, 0.25, 0.5, 3, None, None, id="min-stage-k3"),
    pytest.param(4096, 1 << 13, 0.5, 0.0, 2, None, None, id="4k-half-load"),
    pytest.param(1 << 14, 1 << 16, 0.25, 0.9, 2, None, None, id="16k-dups"),
    pytest.param(1 << 15, 1 << 22, 0.01, 0.3, 3, None, None, id="32k-sparse"),
    pytest.param(1 << 17, 1 << 18, 0.5, 0.0, 2, None, None, id="128k-expansion"),
    pytest.param(1 << 17, 1 << 19, 0.3, 0.6, 3, None, None, id="128k-k3-dups"),
    pytest.param(1 << 16, 1 << 17, 0.5, 0.3, 3, 2, ((2, 8), (8, 64)),
                 id="custom-halving"),
    pytest.param(1 << 15, 1 << 16, 0.4, 0.1, 2, 1,
                 ((4, 16), (16, 32), (256, 64)), id="custom-deep-ladder"),
    pytest.param(1 << 13, 1 << 14, 0.45, 0.2, 2, 6, (), id="dense-only"),
    pytest.param(1 << 14, 1 << 15, 0.4, 0.0, 2, 8, ((4, 4), (16, 64)),
                 id="stage-under-dense-ceiling"),
]

# the default ladder's halving steps (PR 37) at the shapes it was made
# for, each also held to the two-step ladder it replaced:
# nq, cap, load, dup, K, share of the lanes that is valid
HALVING_CASES = [
    # a ``cli check`` flush: sub_batch 4096 x 16 actions, one lane in
    # ten valid, the table at load 0.3
    pytest.param(1 << 16, 1 << 19, 0.3, 0.45, 2, 0.10, id="cli-flush"),
    pytest.param(1 << 18, 1 << 20, 0.3, 0.1, 2, 0.7, id="wide"),
    pytest.param(512, 1 << 12, 0.3, 0.2, 2, 0.5, id="below-min-stage"),
]


@pytest.mark.parametrize(
    "nq,cap,load,dup,K,dense,stages", SCHEDULE_CASES
)
def test_pending_driven_schedule_matches_single_loop(
    nq, cap, load, dup, K, dense, stages
):
    """``is_new``, the table columns, ``n_failed`` and ``rounds`` equal
    the single loop's: every pending lane still probes the same slot at
    the same global round with its original lane id, whichever buffer
    holds it."""
    tcols, kcols, valid = _batch(nq + K, nq, cap, load, dup, K)
    d, st = fpset.resolve_schedule(dense, stages)
    ceiling = _ladder(nq, d, st)[-1][1]
    got_new, got_cols, n_failed, rounds, _, _, _ = fpset.lookup_or_insert(
        tcols, kcols, valid, dense_rounds=dense, stages=stages
    )
    want_new, want_cols, _, pending, want_rounds, _ = fpset.probe_insert(
        tcols, kcols, valid, max_probes=ceiling
    )
    assert np.array_equal(np.asarray(got_new), np.asarray(want_new))
    for a, b in zip(got_cols, want_cols):
        assert np.array_equal(np.asarray(a)[:cap], np.asarray(b)[:cap])
    assert int(n_failed) == int(np.asarray(pending).sum())
    assert int(rounds) == int(want_rounds)


@pytest.mark.parametrize(
    "nq,cap,load,dup,K,dense,stages", SCHEDULE_CASES
)
def test_lane_rounds_is_width_times_rounds_and_never_above_fixed(
    nq, cap, load, dup, K, dense, stages
):
    """``lane_rounds`` is each step's width times the rounds run at it,
    never more than the parent's fixed schedule presents for the same
    batch, and strictly less where the batch is mostly new keys and the
    ladder has somewhere narrower to go."""
    tcols, kcols, valid = _batch(nq + K, nq, cap, load, dup, K)
    d, st = fpset.resolve_schedule(dense, stages)
    ladder = _ladder(nq, d, st)
    _, _, n_failed, rounds, lane_rounds, _, _ = fpset.lookup_or_insert(
        tcols, kcols, valid, dense_rounds=dense, stages=stages
    )
    pending = _pending_by_round(tcols, kcols, valid, ladder[-1][1])
    assert int(rounds) == len(pending) - 1
    followed = _lane_rounds(ladder, pending, follow=True)
    fixed = _lane_rounds(ladder, pending, follow=False)
    assert int(lane_rounds) == followed <= fixed
    if len(ladder) > 1 and int(rounds) > 1 and dup <= 0.3:
        assert followed < fixed
    if len(ladder) == 1:
        assert followed == fixed == nq * int(rounds)


@pytest.mark.parametrize("nq,cap,load,dup,K,share", HALVING_CASES)
def test_halving_ladder_matches_single_loop_and_two_step_ladder(
    nq, cap, load, dup, K, share
):
    """The default ladder against the single loop AND against the
    two-step ladder of PR 28-36: ``is_new``, the columns, ``n_failed``
    and ``rounds`` bit for bit; ``step_rounds`` sums to ``rounds``
    with a 0 for every stage that was not built; and it never presents
    more lanes, and far fewer for a flush with one valid lane in ten."""
    assert fpset.resolve_schedule() == (fpset.DENSE_ROUNDS, fpset.STAGES)
    assert [d for d, _ in fpset.STAGES] == [4, 8, 16, 32, 64, 256]
    tcols, kcols, valid = _batch(nq + K, nq, cap, load, dup, K, share)
    got = fpset.lookup_or_insert(tcols, kcols, valid)
    two = fpset.lookup_or_insert(
        tcols, kcols, valid, stages=fpset.STAGES_TWO_STEP
    )
    one_new, one_cols, _, pending, one_rounds, _ = fpset.probe_insert(
        tcols, kcols, valid
    )
    assert not bool(np.asarray(pending).any())
    for new, cols, n_failed, rounds, _, steps, _ in (got, two):
        assert np.array_equal(np.asarray(new), np.asarray(one_new))
        for a, b in zip(cols, one_cols):
            assert np.array_equal(np.asarray(a)[:cap], np.asarray(b)[:cap])
        assert int(n_failed) == 0
        assert int(rounds) == int(one_rounds)
        assert sum(int(x) for x in steps) == int(rounds)
    built = {0} | {
        j for j, (d, _) in enumerate(fpset.STAGES, 1)
        if nq // d >= fpset.MIN_STAGE and nq // d < nq
    }
    steps = got[5]
    assert len(steps) == 1 + len(fpset.STAGES)
    assert all(
        isinstance(x, int) and x == 0
        for j, x in enumerate(steps) if j not in built
    )
    halves, two_step = int(got[4]), int(two[4])
    assert halves <= two_step
    if share <= 0.1:
        # the chip's materialization: the batch's and its quarter's
        # compactions unrolled, the narrower steps' as one loop each
        chip = fpset.lookup_or_insert(
            tcols, kcols, valid, materialize="shift"
        )
        assert np.array_equal(np.asarray(chip[0]), np.asarray(one_new))
        for a, b in zip(chip[1], one_cols):
            assert np.array_equal(np.asarray(a)[:cap], np.asarray(b)[:cap])
        assert [int(x) for x in chip[2:5]] == [0, int(one_rounds), halves]
    if share <= 0.1:
        # 9-11% valid: the pending lanes skip the 1/4 buffer's rounds
        assert halves < 0.7 * two_step
    if nq < fpset.MIN_STAGE:
        assert halves == two_step == nq * int(one_rounds)


def test_handover_at_a_ceiling_counts_every_lane_it_cannot_carry():
    """A stage left at its ceiling with more survivors than the next
    can hold drops none of them silently: the excess is exactly
    ``n_failed``, the lanes that were carried resolve as in the single
    loop, and the wrapper still raises ``probe overflow``."""
    nq, cap, K = 1 << 14, 1 << 15, 2
    tcols, kcols, valid = _batch(99, nq, cap, 0.5, 0.0, K)
    # one dense round, then a 1/16 stage: far more than 1,024 lanes
    # lose their first bid at this load
    sched = dict(dense_rounds=1, stages=((16, 64),))
    carried = max(nq // 16, fpset.MIN_STAGE)
    after_one = _pending_by_round(tcols, kcols, valid, 1)[1]
    assert after_one > carried
    is_new, cols, n_failed, _, lane_rounds, _, _ = fpset.lookup_or_insert(
        tcols, kcols, valid, **sched
    )
    assert int(n_failed) == after_one - carried
    # every valid lane whose key is not in the table afterwards is one
    # of the counted ones
    member = np.asarray(fpset.lookup(cols, kcols, valid))
    assert int((np.asarray(valid) & ~member).sum()) <= int(n_failed)
    assert int(lane_rounds) >= nq + carried
    # the same batch with room in the next stage: nothing fails
    _, _, none_failed, _, _, _, _ = fpset.lookup_or_insert(
        tcols, kcols, valid, dense_rounds=1, stages=((2, 64),)
    )
    assert int(none_failed) == 0

    class NoGrow(fpset.FPSet):
        def reserve(self, n):
            return self

    s = NoGrow(K, cap=cap, **sched)
    s.cols = tcols
    with pytest.raises(RuntimeError, match="probe overflow"):
        s.insert(kcols, valid)
    assert s.stats["failures"] == after_one - carried
    assert s.stats["lane_rounds"] == int(lane_rounds)


def test_fpm_carries_lane_rounds_past_32_bits_and_pads_old_frames():
    """The widened metrics vector: ``lane_rounds`` rides as hi/lo
    uint32 words (the r12 pattern) and every narrower historical frame
    reads back zero-padded with its old counters where they were."""
    fpm = jnp.zeros((fpset.FPM_N,), jnp.int32)
    big = np.uint32(3_000_000_000)  # one flush can pass 2^31
    for _ in range(3):
        fpm = fpset.fpm_update(
            fpm, jnp.int32(4), jnp.int32(0), jnp.int32(7),
            jnp.uint32(big),
        )
    got = fpset.fpm_logical(np.asarray(fpm))
    assert list(got) == [3, 12, 0, 21, 4, 3 * int(big)]
    assert 3 * int(big) > (1 << 32)
    six = np.asarray(fpm)[:6]  # an r12-r27 frame
    assert list(fpset.fpm_logical(six)) == [3, 12, 0, 21, 4, 0]
    assert list(fpset.fpm_logical(np.asarray([2, 5, 0]))) == [
        2, 5, 0, 0, 0, 0,
    ]


def test_fpm_step_rounds_ride_the_wide_vector_only():
    """The single-chip engine's vector carries the rounds of each
    ladder step behind ``FPM_N`` (a longer schedule folds its tail into
    the last word); the sharded engine's ``FPM_N`` words and the
    logical view are what they were."""
    steps = (jnp.int32(1), 0, jnp.int32(2), jnp.int32(3))
    args = (jnp.int32(6), jnp.int32(0), jnp.int32(7), jnp.uint32(9))
    wide = jnp.zeros((fpset.FPM_WIDE_N,), jnp.int32)
    narrow = jnp.zeros((fpset.FPM_N,), jnp.int32)
    for _ in range(2):
        wide = fpset.fpm_update(wide, *args, steps)
        narrow = fpset.fpm_update(narrow, *args, steps)
    assert narrow.shape == (fpset.FPM_N,)
    assert np.array_equal(np.asarray(wide)[: fpset.FPM_N], np.asarray(narrow))
    assert list(fpset.fpm_logical(np.asarray(wide))) == [2, 12, 0, 14, 6, 18]
    assert fpset.fpm_step_rounds(np.asarray(wide), ((4, 16),) * 3) == [
        2, 0, 4, 6,
    ]
    long = tuple(jnp.int32(1) for _ in range(fpset.FPM_STEPS + 3))
    folded = fpset.fpm_update(
        jnp.zeros((fpset.FPM_WIDE_N,), jnp.int32), *args, long
    )
    got = fpset.fpm_step_rounds(
        np.asarray(folded), ((2, 8),) * (len(long) - 1)
    )
    assert got == [1] * (fpset.FPM_STEPS - 1) + [4]


# nq -> the default ladder's widths: halves between 1/4 and 1/64 of
# the batch; no step under ``MIN_STAGE``; quarters from a buffer wider
# than ``QUARTER_ABOVE`` lanes
LADDER_WIDTHS = [
    pytest.param(512, [512], id="below-min-stage"),
    pytest.param(
        1 << 16, [65536, 16384, 8192, 4096, 2048, 1024], id="cli-flush"
    ),
    pytest.param(
        1 << 18,
        [262144, 65536, 32768, 16384, 8192, 4096, 1024],
        id="true-256th-from-2^18",
    ),
    pytest.param(
        1 << 22,
        [1 << 22, 1 << 20, 1 << 19, 1 << 18, 1 << 17, 1 << 16, 1 << 14],
        id="halves-at-2^20",
    ),
    pytest.param(
        26738688,
        [26738688, 6684672, 1671168, 417792, 104448],
        id="flagship-quarters",
    ),
]


@pytest.mark.parametrize("nq,widths", LADDER_WIDTHS)
def test_default_ladder_by_batch_width(nq, widths):
    """The ladder is a function of the batch's static width alone; the
    two-step tuple is untouched by the quarter rule (its gaps are 4x and
    16x); and the traced program has one probe loop a step."""
    steps = fpset.ladder_steps(nq, fpset.DENSE_ROUNDS, fpset.STAGES)
    assert [w for w, _, _ in steps] == widths
    assert [(w, c) for w, c, _ in steps] == _ladder(
        nq, fpset.DENSE_ROUNDS, fpset.STAGES
    )
    assert steps[-1][1] == fpset.MAX_PROBES
    two = fpset.ladder_steps(nq, fpset.DENSE_ROUNDS, fpset.STAGES_TWO_STEP)
    assert [w for w, _, _ in two] == [
        w for w in dict.fromkeys(
            (nq, max(nq // 4, min(nq, fpset.MIN_STAGE)),
             max(nq // 64, min(nq, fpset.MIN_STAGE)))
        )
    ]
    lanes = jax.ShapeDtypeStruct((nq,), jnp.uint32)
    table = jax.ShapeDtypeStruct(
        ((1 << (nq.bit_length() + 1)) + 1,), jnp.uint32
    )
    jaxpr = jax.make_jaxpr(
        lambda t, k, v: fpset.lookup_or_insert(t, k, v, materialize="roll")
    )((table, table), (lanes, lanes), jax.ShapeDtypeStruct((nq,), jnp.bool_))
    names = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert names.count("while") == len(widths)


def test_sharded_engine_and_rehash_keep_the_two_step_ladder(monkeypatch):
    """One default ladder and one named two-step tuple: the single-chip
    engine resolves the first; ``ShardedDeviceChecker`` and
    ``rehash_cols`` the second, by value (ISSUE 37)."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    assert fpset.STAGES_TWO_STEP == ((4, 16), (64, 64))
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    one = DeviceChecker(m, invariants=(), sub_batch=64, visited_cap=1 << 10)
    assert (one.fps_dense, one.fps_stages) == (
        fpset.DENSE_ROUNDS, fpset.STAGES
    )
    four = ShardedDeviceChecker(
        m, n_devices=4, invariants=(), sub_batch=64, visited_cap=1 << 6,
    )
    assert (four.fps_dense, four.fps_stages) == (
        fpset.DENSE_ROUNDS, fpset.STAGES_TWO_STEP
    )
    # the two-step ladder's programs are the parent's on the chip too:
    # under the chip's materialization its compactions stay unrolled
    # (its loops are its three probe loops), while the halving ladder
    # runs the compactions of its 1/8, 1/16 and 1/32 buffers as loops
    def whiles(stages):  # (probe loops, rolled compactions)
        cols = tuple(jnp.zeros((1 << 16,), jnp.uint32) for _ in range(2))
        jaxpr = jax.make_jaxpr(
            lambda t, k, v: fpset.lookup_or_insert(
                t, k, v, stages=stages, materialize="shift"
            )[:5]
        )(fpset.empty_cols(1 << 18, 2), cols, cols[0] > 0)
        names = [e.primitive.name for e in jaxpr.jaxpr.eqns]
        return names.count("while"), names.count("scan")

    assert whiles(fpset.STAGES_TWO_STEP) == (3, 0)
    assert whiles(fpset.STAGES) == (6, 3)
    seen = []
    real = fpset.lookup_or_insert

    def spy(*a, **kw):
        seen.append((kw.get("dense_rounds"), kw.get("stages")))
        return real(*a, **kw)

    keys, old = _table_at_load(1 << 13, 0.3, 2, seed=37)
    monkeypatch.setattr(fpset, "lookup_or_insert", spy)
    # a chunk no other test asks for, so that the body is traced here
    new, rhm = fpset.rehash_cols(
        old, fpset.empty_cols(1 << 14, 2), chunk=1 << 12
    )
    assert seen == [(fpset.DENSE_ROUNDS, fpset.STAGES_TWO_STEP)]
    assert fpset.rhm_logical(rhm)[:2] == (0, len(keys))
    _assert_holds_exactly(new, keys)


# ---- the engines on the published oracles ---------------------------


def test_engine_shipped_oracle():
    """45,198 / diameter 20 (compaction.tla:23) on the device engine."""
    r = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    ).run()
    assert r.distinct_states == 45198
    assert r.diameter == 20
    assert r.violation is None and not r.deadlock


def test_fpset_full_cfg_published_count():
    """The second published oracle (253,361 / diameter 23) with
    growth forced from a small initial table (ISSUE r6 acceptance)."""
    import dataclasses

    c = dataclasses.replace(
        pe.SHIPPED_CFG, model_producer=True, retain_null_key=False
    )
    r = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=4096,
        visited_cap=1 << 12, frontier_cap=1 << 17, flush_factor=2,
    ).run()
    assert r.distinct_states == 253361
    assert r.diameter == 23
    assert r.violation is None and not r.deadlock


# the 253,361-state binding's level sizes after the initial state, as
# the parent commit's ``cli check`` prints them (PR 37, CPU)
_SIZES_253K = [
    10, 99, 990, 3267, 4860, 6642, 8595, 8748, 10935, 13131, 13293, 15633,
    20232, 15291, 17640, 22455, 20943, 21555, 27936, 4041, 6372, 10692,
]


def test_cli_check_of_the_253k_binding_presents_under_2_6_lanes(tmp_path):
    """The counter that says how the ladder engages, on a whole ``cli
    check`` at the CLI's own tiers: 3.3606 lanes presented a valid one
    under the two-step ladder (the parent, CPU: the counter is
    hardware-independent), 1.9005 with the halving steps; the search is
    the parent's, level for level, and every round is some step's."""
    from tests.test_units import VERDICT, _cli_check
    from tests.test_units_programs import CFG_253K

    rc, out, sizes, stats = _cli_check(tmp_path, 0, "-config", CFG_253K)
    assert rc == 0
    assert VERDICT.search(out).groups() == ("253361", "23")
    assert sizes == _SIZES_253K and 1 + sum(sizes) == 253361
    assert stats["fpset_failures"] == 0
    assert stats["fpset_lanes_presented_per_valid"] < 2.6
    assert stats["fpset_lane_rounds"] < 0.6 * 1414144  # the parent's
    steps = stats["fpset_step_rounds"]
    assert len(steps) == 1 + len(fpset.STAGES)
    assert sum(steps) == stats["fpset_probe_rounds"] == 316
    # no step idles on this traffic; 1/256 of 65,536 lanes is under
    # ``MIN_STAGE``, so that step is not built and its rounds are the
    # 1/64 step's
    assert all(n > 0 for n in steps[1:6]) and steps[6] == 0


# ---- fpset_slot_rounds: the table's slots summed over rounds (PR 38) --


def _run_recording_fetches(fuse, visited_cap, seed=None):
    """One run of the small binding; beside its ``last_stats``, what
    every stats fetch found: the cumulative probe rounds and the
    table's slots at that moment (a recording wrapper around
    ``_fetch``, as ``tests/test_spans.py: _recorded_jits`` records
    programs)."""
    ck = DeviceChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), sub_batch=256,
        fuse=fuse, visited_cap=visited_cap, frontier_cap=1 << 12,
    )
    seen = []
    fetch = ck._fetch

    def recording_fetch(st, vec=None):
        out = fetch(st, vec)
        seen.append((int(ck._last_fpm[1]), int(ck.TCAP)))
        return out

    ck._fetch = recording_fetch
    r = ck.run(seed=seed)
    return r, dict(ck.last_stats), seen


def _slot_rounds_of(seen):
    """The sum the counter should be, from what the fetches found, and
    the rounds it covers."""
    want, done = 0, 0
    for rounds, cap in seen:
        want += (rounds - done) * cap
        done = rounds
    return want, done


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_slot_rounds_sum_each_rounds_own_table(fuse):
    """A table that grows at least twice inside the run: the counter
    is the sum, over fetches, of the rounds since the last fetch times
    the slots of the table they ran on, made here from what each fetch
    found; so it lies strictly between all rounds at the first tier
    and all rounds at the last."""
    r, st, seen = _run_recording_fetches(fuse, 1 << 8)
    assert r.distinct_states == 1654 and st["fpset_failures"] == 0
    assert st["grow_rehashes"] >= 2
    caps = sorted({cap for _rounds, cap in seen})
    # (a crossing may double more than once: the rounds ran on fewer
    # distinct tables than there were rehashes)
    assert len(caps) >= 2 and caps[-1] == st["fpset_table_cap"]
    want, done = _slot_rounds_of(seen)
    assert done == st["fpset_probe_rounds"] > 0
    assert len(seen) == st["stats_fetches"]
    assert st["fpset_slot_rounds"] == want
    assert (
        st["fpset_probe_rounds"] * caps[0]
        < st["fpset_slot_rounds"]
        < st["fpset_probe_rounds"] * caps[-1]
    )
    assert st["fpset_slots_per_valid"] == round(
        want / st["fpset_valid_lanes"], 4
    )


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_slot_rounds_of_a_table_that_never_grows(fuse):
    """One table all through: every round walked the same slots."""
    r, st, seen = _run_recording_fetches(fuse, 1 << 16)
    assert r.distinct_states == 1654
    assert st.get("grow_rehashes", 0) == 0
    assert {cap for _rounds, cap in seen} == {st["fpset_table_cap"]}
    assert st["fpset_slot_rounds"] == (
        st["fpset_probe_rounds"] * st["fpset_table_cap"]
    )
    assert st["fpset_slots_per_valid"] == round(
        st["fpset_slot_rounds"] / st["fpset_valid_lanes"], 4
    )


def test_slot_rounds_count_a_seed_loads_rounds_on_the_table_it_grew():
    """The seed load grows the table first and merges afterwards, with
    no fetch between its merges: all their rounds ran on the table the
    next fetch finds."""
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    seed = m.host_seed(max_level_states=200, max_total=600)
    r, st, seen = _run_recording_fetches("level", 1 << 8, seed=seed)
    assert r.distinct_states == 1654
    want, done = _slot_rounds_of(seen)
    assert seen[0][0] > 0  # the merges' rounds ride the first fetch
    assert st["fpset_slot_rounds"] == want > 0


# ---- the probe's two arbitrations (PR 40) ----------------------------

ARB_WIDTHS = [1024, 2048, 4096, 8192, 16384]


def _arb_case(case, nq, cap, seed):
    """``(bid, s, lane_ids)`` of one round: which lanes bid, the slot
    each lane looks at (``cap`` for a parked one) and the ids they bid
    with."""
    rng = np.random.default_rng(seed)
    lane_ids = np.arange(nq, dtype=np.int32)
    bid = np.ones((nq,), bool)
    if case == "one_slot":
        # every lane bids for the same slot
        s = np.full((nq,), 5, np.int32)
    elif case == "own_slots":
        s = rng.permutation(cap)[:nq].astype(np.int32)
    elif case == "groups":
        # runs of 1 to 40 lanes a slot (same-key duplicates and other
        # keys alike are just lanes on one slot here), 30% bidding
        s = rng.integers(0, max(nq // 8, 1), size=nq).astype(np.int32)
        bid = rng.random(nq) < 0.3
    elif case == "parked":
        # most lanes parked on the trash row; a parked lane's id may
        # be below every bidder's and must not beat them
        s = rng.integers(0, 64, size=nq).astype(np.int32)
        bid = rng.random(nq) < 0.05
        s = np.where(rng.random(nq) < 0.5, s, cap).astype(np.int32)
        bid &= s < cap
    elif case == "compacted_ids":
        # a compacted buffer: sorted sparse original ids, garbage ids
        # (zeros) on the lanes past the pending ones
        s = rng.integers(0, nq // 4, size=nq).astype(np.int32)
        lane_ids = np.sort(
            rng.choice(1 << 24, size=nq, replace=False)
        ).astype(np.int32)
        npend = nq // 3
        bid = (np.arange(nq) < npend) & (rng.random(nq) < 0.7)
        lane_ids[npend:] = 0
        s[npend:] = cap
    elif case == "unordered_ids":
        # ids in no order: the least ID wins, not the first position
        s = rng.integers(0, nq // 16, size=nq).astype(np.int32)
        lane_ids = rng.permutation(nq).astype(np.int32)
        bid = rng.random(nq) < 0.6
    else:
        raise AssertionError(case)
    return bid, s, lane_ids


@pytest.mark.parametrize("nq", ARB_WIDTHS)
@pytest.mark.parametrize(
    "case",
    ["one_slot", "own_slots", "groups", "parked", "compacted_ids",
     "unordered_ids"],
)
def test_the_two_arbitrations_pick_the_same_winners(case, nq):
    """``win_among_lanes`` against ``win_by_claims`` on the same round,
    and both against the definition: the bidder with the least id of
    its slot."""
    cap = 1 << 15
    bid, s, lane_ids = _arb_case(case, nq, cap, seed=nq + len(case))
    args = (jnp.asarray(bid), jnp.asarray(s), jnp.asarray(lane_ids), cap)
    # jitted, as a round runs them: the pairwise compare fuses, and no
    # [nq, nq] value (1 GiB at 16,384 lanes) is built
    by_claims, among = (
        np.asarray(jax.jit(form, static_argnums=3)(*args))
        for form in (fpset.win_by_claims, fpset.win_among_lanes)
    )
    best = {}
    for i in np.flatnonzero(bid):
        best[s[i]] = min(best.get(s[i], 1 << 31), lane_ids[i])
    want = bid & np.array(
        [best.get(s[i]) == lane_ids[i] for i in range(nq)]
    )
    assert np.array_equal(by_claims, want)
    assert np.array_equal(among, want)
    assert want.sum() == len(best)


def _held_to(monkeypatch, among_lanes):
    """Hold the rule to one side for what is traced next."""
    monkeypatch.setattr(
        fpset, "arbitrates_among_lanes", lambda nq, cap: among_lanes
    )


def _colliding_batch(nq, ncols, cap, seed):
    """A batch that meets every case of a round at once on a table of
    ``cap`` slots at load 0.3: keys the table holds, new keys, each new
    key up to five times in the batch, far more lanes than slots (so
    different keys bid for one slot), and lanes that are not valid."""
    held, tcols = _table_at_load(cap, 0.3, ncols, seed)
    rng = np.random.default_rng(seed + 1)
    fresh = rng.integers(0, 2**32 - 2, size=(nq // 4, ncols),
                         dtype=np.uint32)
    pool = np.concatenate([held[: nq // 8], fresh])
    keys = pool[rng.integers(0, len(pool), size=nq)]
    valid = rng.random(nq) < 0.8
    return held, tcols, keys, valid


def _first_lanes_of_new_keys(held, keys, valid):
    """A Python set's answer: the first valid lane of every key that
    ``held`` does not hold."""
    seen = {tuple(k) for k in held}
    want = np.zeros((len(keys),), bool)
    for i in np.flatnonzero(valid):
        if tuple(keys[i]) not in seen:
            seen.add(tuple(keys[i]))
            want[i] = True
    return want


@pytest.mark.parametrize("layout", ["sentinel", "occ"])
@pytest.mark.parametrize("nq", [1024, 4096])
def test_probe_insert_is_the_same_by_either_arbitration(
    monkeypatch, nq, layout
):
    """One ``probe_insert`` loop by both arbitrations: ``is_new``, the
    table, ``occ``, ``pending`` and ``rounds`` bit for bit, and
    ``is_new`` the first valid lane of every key the table did not
    hold (a Python set's answer)."""
    ncols, cap = 2, 1 << 11
    held, tcols, keys, valid = _colliding_batch(nq, ncols, cap, seed=nq)
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(ncols))
    occ = None
    if layout == "occ":
        occ = fpset.occupied_mask(tcols).astype(jnp.int32)
        occ = jnp.concatenate([occ, jnp.zeros((1,), jnp.int32)])
        # the occ layout tells an empty slot by the column alone: put
        # a key no lane carries where the SENTINEL marker was
        tcols = tuple(
            jnp.where(occ == 1, c, jnp.uint32(7)) for c in tcols
        )
    got = {}
    for among_lanes in (False, True):
        _held_to(monkeypatch, among_lanes)
        got[among_lanes] = fpset.probe_insert(
            tcols, kcols, jnp.asarray(valid), occ=occ, max_probes=3,
        )
    (new_c, cols_c, occ_c, pend_c, r_c, _) = got[False]
    (new_l, cols_l, occ_l, pend_l, r_l, _) = got[True]
    assert np.array_equal(np.asarray(new_c), np.asarray(new_l))
    assert np.array_equal(np.asarray(pend_c), np.asarray(pend_l))
    assert int(r_c) == int(r_l) == 3  # cut short: lanes still pending
    assert int(np.asarray(pend_l).sum()) > 0
    for a, b in zip(cols_c, cols_l):
        assert np.array_equal(np.asarray(a)[:cap], np.asarray(b)[:cap])
    if occ is not None:
        assert np.array_equal(
            np.asarray(occ_c)[:cap], np.asarray(occ_l)[:cap]
        )
    # to the end, against a Python set
    _held_to(monkeypatch, True)
    is_new, _cols, _occ, pending, _r, _ = fpset.probe_insert(
        tcols, kcols, jnp.asarray(valid), occ=occ,
    )
    assert int(np.asarray(pending).sum()) == 0
    assert np.array_equal(
        np.asarray(is_new), _first_lanes_of_new_keys(held, keys, valid)
    )


def test_a_resumed_narrow_stage_bids_with_its_original_lane_ids(
    monkeypatch,
):
    """A compacted buffer resumes at round 2 with sparse original ids
    in DESCENDING position order: the least id wins by both
    arbitrations, not the first position."""
    nq, cap = 1024, 1 << 12
    rng = np.random.default_rng(5)
    pool = rng.integers(0, 2**31, size=(200, 2), dtype=np.uint32)
    keys = pool[rng.integers(0, len(pool), size=nq)]
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(2))
    ids = np.sort(rng.choice(1 << 20, nq, replace=False))[::-1].astype(
        np.int32
    )
    valid = jnp.asarray(rng.random(nq) < 0.9)
    out = {}
    for among_lanes in (False, True):
        _held_to(monkeypatch, among_lanes)
        out[among_lanes] = fpset.probe_insert(
            fpset.empty_cols(cap, 2), kcols, valid, start_round=2,
            lane_ids=jnp.asarray(ids),
        )
    for a, b in zip(jax.tree.leaves(out[False]), jax.tree.leaves(out[True])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    is_new = np.asarray(out[True][0])
    first = {}
    for i in np.flatnonzero(np.asarray(valid)):
        k = tuple(keys[i])
        if k not in first or ids[i] < ids[first[k]]:
            first[k] = i
    assert sorted(np.flatnonzero(is_new)) == sorted(first.values())


@pytest.mark.parametrize("nq", [4096, 20000])
def test_lookup_or_insert_is_the_same_by_either_arbitration(
    monkeypatch, nq
):
    """The whole ladder (its narrow steps resume on compacted buffers
    with original lane ids) by both arbitrations and against a Python
    set: every output bit for bit."""
    ncols, cap = 2, 1 << 16
    held, tcols, keys, valid = _colliding_batch(nq, ncols, cap, seed=nq)
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(ncols))
    got = {}
    for among_lanes in (False, True):
        _held_to(monkeypatch, among_lanes)
        got[among_lanes] = fpset.lookup_or_insert(
            tcols, kcols, jnp.asarray(valid)
        )
    for a, b in zip(jax.tree.leaves(got[False]), jax.tree.leaves(got[True])):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape == (cap + 1,):  # the trash row holds any loser
            a, b = a[:cap], b[:cap]
        assert np.array_equal(a, b)
    is_new, _cols, n_failed, rounds, _lanes, steps, _ = got[True]
    assert int(n_failed) == 0 and int(rounds) == sum(int(x) for x in steps)
    assert np.array_equal(
        np.asarray(is_new), _first_lanes_of_new_keys(held, keys, valid)
    )


def _jaxpr_values(jaxpr):
    """Every ``(primitive name, output shape, output dtype)`` of a
    jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, v.aval.shape, v.aval.dtype
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_values(sub)


def _probe_round_values(nq, cap):
    u32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.uint32)  # noqa: E731
    jaxpr = jax.make_jaxpr(fpset.probe_insert)(
        (u32(cap + 1), u32(cap + 1)), (u32(nq), u32(nq)),
        jax.ShapeDtypeStruct((nq,), jnp.bool_),
    )
    return list(_jaxpr_values(jaxpr.jaxpr))


def test_a_narrow_round_builds_nothing_of_the_tables_size_to_arbitrate():
    """The jaxpr of a narrow ``probe_insert`` (the CLI's 1,024-lane
    step on the 9m binding's 2^24-slot table) holds no int32 value of
    ``cap + 1`` elements and no scatter-min; a wide one (the flagship
    level's flush) still holds both."""
    cap = 1 << 24
    assert fpset.arbitrates_among_lanes(1024, cap)
    narrow = _probe_round_values(1024, cap)
    assert not [v for v in narrow if v[0] == "scatter-min"]
    assert not [
        v for v in narrow if v[1] == (cap + 1,) and v[2] == jnp.int32
    ]
    # the two key columns are still written at the table's size
    assert len([
        v for v in narrow if v[0] == "scatter" and v[1] == (cap + 1,)
    ]) == 2
    assert not fpset.arbitrates_among_lanes(65536, cap)
    wide = _probe_round_values(65536, cap)
    assert [v for v in wide if v[0] == "scatter-min"] == [
        ("scatter-min", (cap + 1,), jnp.int32)
    ]


def test_the_rule_reads_the_two_static_shapes_and_nothing_else():
    """``arbitrates_among_lanes(nq, cap)``: two parameters; narrower
    or on a larger table never turns it off; the CLI's ladder on the
    9m binding engages it at its 1,024-lane step at 2^24 slots, and
    the wide flushes (the CLI's 65,536 lanes, the flagship level's
    26,738,688 on 2^27 slots, the sharded engine's 98,304) never."""
    import inspect

    rule = fpset.arbitrates_among_lanes
    assert list(inspect.signature(rule).parameters) == ["nq", "cap"]
    widths = [
        w for w, _c, _e in fpset.ladder_steps(
            65536, fpset.DENSE_ROUNDS, fpset.STAGES
        )
    ]
    assert widths == [65536, 16384, 8192, 4096, 2048, 1024]
    assert rule(1024, 1 << 24) and rule(1024, 1 << 25)
    assert not rule(65536, 1 << 25)
    assert not rule(26738688, 1 << 27) and not rule(98304, 1 << 23)
    # the measured crossovers (PR 40): 16,384 lanes from 2^24 slots,
    # up to 8,192 from 2^22, nothing wider than was measured and
    # nothing on a smaller table (tier-1's, the two small CLI cells')
    assert rule(16384, 1 << 24) and not rule(16384, 1 << 23)
    assert rule(8192, 1 << 22) and rule(64, 1 << 22)
    assert not rule(1024, 1 << 21) and not rule(64, 1 << 21)
    assert not rule(32768, 1 << 27)
    for log_cap in range(6, 28):
        engaged = [
            nq for nq in (64, 256, 1024, 1536, 2048, 2560, 4096, 8192,
                          16384, 65536)
            if rule(nq, 1 << log_cap)
        ]
        # a prefix of the widths: narrower never turns it off
        assert engaged == [
            nq for nq in (64, 256, 1024, 1536, 2048, 2560, 4096, 8192,
                          16384, 65536)
            if nq <= max(engaged, default=0)
        ]
        # nor does a larger table
        assert all(rule(nq, 2 << log_cap) for nq in engaged)


def test_lane_arb_rounds_folds_step_rounds_by_the_rule(monkeypatch):
    """The host's fold: of the rounds by schedule entry, those at a
    step whose width the rule arbitrates among the lanes."""
    monkeypatch.setattr(
        fpset, "arbitrates_among_lanes",
        lambda nq, cap: nq <= 2048 and cap >= 1 << 20,
    )
    steps = [2, 853, 1265, 1694, 1462, 17857, 0, 0]
    fold = lambda nq, cap: fpset.lane_arb_rounds(  # noqa: E731
        steps, nq, cap, fpset.DENSE_ROUNDS, fpset.STAGES
    )
    assert fold(65536, 1 << 24) == 1462 + 17857
    assert fold(65536, 1 << 19) == 0
    # a seed merge's 32,768 lanes walk 8,192 / 4,096 / 2,048 / 1,024
    # at the entries 1 to 4 (1/32 and under have no shrink to offer)
    assert fold(32768, 1 << 24) == 1694 + 1462
    # a batch no wider than MIN_STAGE runs every round at entry 0
    assert fold(1024, 1 << 24) == steps[0]


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_lane_arb_rounds_counter_is_the_rule_over_the_fetches(
    monkeypatch, fuse
):
    """``fpset_lane_arb_rounds`` in ``last_stats``: folded at every
    fetch from the step rounds since the last one and the table as it
    is then.  Under a rule that turns on with the table's size the
    counter is the rounds of the narrow steps from that tier on, made
    here from what each fetch found."""
    rule = lambda nq, cap: nq <= 1024 and cap >= 1 << 14  # noqa: E731
    monkeypatch.setattr(fpset, "arbitrates_among_lanes", rule)
    ck = DeviceChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), sub_batch=256,
        fuse=fuse, visited_cap=1 << 8, frontier_cap=1 << 12,
    )
    seen = []
    fetch = ck._fetch

    def recording_fetch(st, vec=None):
        out = fetch(st, vec)
        seen.append((
            np.asarray(ck._last_fpm, np.int64)[
                fpset.FPM_N: fpset.FPM_WRITE_SAVED
            ], ck.TCAP
        ))
        return out

    ck._fetch = recording_fetch
    r = ck.run()
    st = ck.last_stats
    assert r.distinct_states == 1654 and st["grow_rehashes"] >= 2
    lad = fpset.ladder_steps(ck.ACAP, ck.fps_dense, ck.fps_stages)
    assert lad[0][0] > 1024 >= lad[-1][0]  # a wide step and a narrow one
    assert min(cap for _s, cap in seen) < 1 << 14 <= st["fpset_table_cap"]
    want, prev = 0, np.zeros((fpset.FPM_STEPS,), np.int64)
    for steps, cap in seen:
        want += sum(
            int((steps - prev)[e]) for w, _c, e in lad if rule(w, cap)
        )
        prev = steps
    assert int(prev.sum()) == st["fpset_probe_rounds"]
    assert 0 < want < st["fpset_probe_rounds"]
    assert st["fpset_lane_arb_rounds"] == want
    assert st["fpset_lane_arb_rounds_pct"] == round(
        100.0 * want / st["fpset_probe_rounds"], 4
    )


def test_lane_arb_rounds_fold_a_seed_loads_merges_at_their_own_ladder(
    monkeypatch,
):
    """The seed merges run ``SEED_CHUNK`` lanes, not a flush's: their
    rounds are folded by their own ladder before the first fetch."""
    rule = lambda nq, cap: nq <= 1024  # noqa: E731
    monkeypatch.setattr(fpset, "arbitrates_among_lanes", rule)
    monkeypatch.setattr(DeviceChecker, "SEED_CHUNK", 1024)
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    seed = m.host_seed(max_level_states=200, max_total=600)
    ck = DeviceChecker(
        m, sub_batch=256, visited_cap=1 << 8, frontier_cap=1 << 12,
    )
    folds = []
    fold = ck._fold_lane_arb

    def recording_fold(fpm, nq):
        before = ck._arb_rounds, ck._arb_of
        fold(fpm, nq)
        folds.append(
            (nq, ck._arb_rounds - before[0], ck._arb_of - before[1])
        )

    ck._fold_lane_arb = recording_fold
    r = ck.run(seed=seed)
    assert r.distinct_states == 1654
    assert folds[0][0] == ck.SEED_CHUNK == 1024 < ck.ACAP
    # the merges' rounds, folded before a fetch: all run in place at
    # 1,024 lanes, where a flush's first step is wider than the rule
    assert folds[0][1] == folds[0][2] > 0
    assert {nq for nq, _a, _o in folds[1:]} == {ck.ACAP}
    st = ck.last_stats
    assert st["fpset_lane_arb_rounds"] == sum(a for _n, a, _o in folds)
    assert st["fpset_probe_rounds"] == sum(o for _n, _a, o in folds)


# ---- a narrow round writes only its winners (PR 42) ------------------


def _write_held_to(monkeypatch, winners):
    """Hold the write's rule to one side for what is traced next."""
    monkeypatch.setattr(fpset, "writes_winners", lambda nq, cap: winners)


def _round_of_known_winners(nq, cap, n_win, seed):
    """``(table columns, keys, valid)`` of a round whose winners are
    known: every key of the pool has a round-0 slot of its own; 20 of
    them are in the table already and 20 valid lanes carry them
    (duplicates, found in round 0); ``n_win`` more valid lanes each
    win their empty slot in round 0; and up to 50 lanes repeat a
    winner's key from a higher lane: same-key losers, resolved by the
    round's reread."""
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 2**32 - 2, size=(16 * cap, 2), dtype=np.uint32)
    slots = np.asarray(
        fpset.slot_hash((jnp.asarray(pool[:, 0]), jnp.asarray(pool[:, 1])))
    ) & np.uint32(cap - 1)
    _, one_a_slot = np.unique(slots, return_index=True)
    assert len(one_a_slot) >= nq + 20
    picked = rng.permutation(one_a_slot)[: nq + 20]
    keys, held = pool[picked[:nq]].copy(), picked[nq:]
    table = np.full((cap + 1, 2), 0xFFFFFFFF, np.uint32)
    table[slots[held]] = pool[held]
    lanes = rng.permutation(nq)
    winners = np.sort(lanes[:n_win])
    valid = np.zeros((nq,), bool)
    valid[winners] = True
    others = lanes[n_win:]
    keys[others[:20]] = pool[held][: len(others[:20])]
    valid[others[:20]] = True
    for lane in others[20:70]:
        below = winners[winners < lane]
        if len(below):
            keys[lane] = keys[rng.choice(below)]
            valid[lane] = True
    tcols = tuple(jnp.asarray(table[:, i]) for i in range(2))
    return tcols, keys, valid


_W1024 = fpset.write_chunk(1024)


@pytest.mark.parametrize("layout", ["sentinel", "occ"])
@pytest.mark.parametrize(
    "n_win", [0, 1, _W1024 - 1, _W1024, _W1024 + 1, 1024]
)
def test_a_narrow_round_writes_the_same_table_by_its_winners(
    monkeypatch, n_win, layout
):
    """One round of ``probe_insert`` (and the loop to its end) by the
    winners' write against the full-width write, jitted as a round runs
    them: ``is_new``, the table under ``cap``, ``occ``, ``pending`` and
    ``rounds`` bit for bit, and the lanes the table was not handed:
    all but ``chunk`` a trip."""
    nq, cap = 1024, 1 << 12
    assert _W1024 == 128
    tcols, keys, valid = _round_of_known_winners(nq, cap, n_win, seed=n_win)
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(2))
    occ = None
    if layout == "occ":
        occ = jnp.concatenate([
            fpset.occupied_mask(tcols).astype(jnp.int32),
            jnp.zeros((1,), jnp.int32),
        ])
    got = {}
    for winners in (False, True):
        _write_held_to(monkeypatch, winners)
        for max_probes in (1, fpset.MAX_PROBES):
            got[winners, max_probes] = jax.jit(
                lambda t, k, v, o: fpset.probe_insert(
                    t, k, v, occ=o, max_probes=max_probes
                )
            )(tcols, kcols, jnp.asarray(valid), occ)
    for max_probes in (1, fpset.MAX_PROBES):
        full = got[False, max_probes]
        mine = got[True, max_probes]
        for a, b in zip(jax.tree.leaves(full[:5]), jax.tree.leaves(mine[:5])):
            a, b = np.asarray(a), np.asarray(b)
            if a.shape == (cap + 1,):  # the trash row is write-only
                a, b = a[:cap], b[:cap]
            assert np.array_equal(a, b)
        assert int(full[5]) == 0  # the plain 0, through the jit
    is_new, cols, _occ, pending, rounds, saved = got[True, 1]
    assert int(rounds) == 1 and int(np.asarray(is_new).sum()) == n_win
    assert not np.asarray(pending).any()
    assert int(np.asarray(fpset.occupied_mask(cols)).sum()) == n_win + 20
    assert saved.dtype == jnp.uint32
    assert int(saved) == nq - -(-n_win // _W1024) * _W1024


def test_write_winners_clamps_a_last_chunk_that_passes_the_buffer():
    """A buffer that the chunk does not divide: the last trip's slice
    is clamped back over winners already written, the same words to
    the same slots, and every winner lands."""
    nq, cap, chunk = 300, 1 << 10, 128
    rng = np.random.default_rng(42)
    s = rng.permutation(cap)[:nq].astype(np.int32)
    keys = rng.integers(0, 2**32 - 2, size=(nq, 2), dtype=np.uint32)
    for n_win in (0, 129, 257, 300):
        win = np.zeros((nq,), bool)
        win[rng.choice(nq, n_win, replace=False)] = True
        tc, oc, lanes = jax.jit(fpset.write_winners, static_argnums=5)(
            fpset.empty_cols(cap, 2), jnp.zeros((cap + 1,), jnp.int32),
            jnp.asarray(win), jnp.asarray(s),
            tuple(jnp.asarray(keys[:, i]) for i in range(2)), chunk,
        )
        want = np.full((cap, 2), 0xFFFFFFFF, np.uint32)
        want[s[win]] = keys[win]
        assert np.array_equal(
            np.stack([np.asarray(c)[:cap] for c in tc], 1), want
        )
        assert np.array_equal(
            np.flatnonzero(np.asarray(oc)[:cap]), np.sort(s[win])
        )
        assert int(lanes) == -(-n_win // chunk) * chunk


@pytest.mark.parametrize("among_lanes", [False, True])
def test_a_resumed_narrow_stage_writes_the_same_by_its_winners(
    monkeypatch, among_lanes
):
    """A compacted buffer resumes at round 2 with sparse original ids
    in DESCENDING position order: the winners are packed in POSITION
    order whatever their ids, and every output is the full-width
    write's, by either arbitration."""
    nq, cap = 1024, 1 << 12
    rng = np.random.default_rng(6)
    pool = rng.integers(0, 2**31, size=(200, 2), dtype=np.uint32)
    keys = pool[rng.integers(0, len(pool), size=nq)]
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(2))
    ids = np.sort(rng.choice(1 << 20, nq, replace=False))[::-1].astype(
        np.int32
    )
    valid = jnp.asarray(rng.random(nq) < 0.9)
    _held_to(monkeypatch, among_lanes)
    out = {}
    for winners in (False, True):
        _write_held_to(monkeypatch, winners)
        out[winners] = jax.jit(
            lambda t, k, v, i: fpset.probe_insert(
                t, k, v, start_round=2, lane_ids=i
            )
        )(fpset.empty_cols(cap, 2), kcols, valid, jnp.asarray(ids))
    for a, b in zip(
        jax.tree.leaves(out[False][:5]), jax.tree.leaves(out[True][:5])
    ):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape == (cap + 1,):
            a, b = a[:cap], b[:cap]
        assert np.array_equal(a, b)
    first = {}
    for i in np.flatnonzero(np.asarray(valid)):
        k = tuple(keys[i])
        if k not in first or ids[i] < ids[first[k]]:
            first[k] = i
    assert sorted(np.flatnonzero(np.asarray(out[True][0]))) == sorted(
        first.values()
    )
    lanes = nq * (int(out[True][4]) - 2)
    assert 0 < lanes - int(out[True][5]) < lanes


@pytest.mark.parametrize("nq", [4096, 20000])
def test_lookup_or_insert_is_the_same_by_either_write(monkeypatch, nq):
    """The whole ladder by both writes and against a Python set: every
    output bit for bit but the trash row and the lanes kept from the
    table, which the full-width write reports as the plain 0."""
    ncols, cap = 2, 1 << 16
    held, tcols, keys, valid = _colliding_batch(nq, ncols, cap, seed=nq + 1)
    kcols = tuple(jnp.asarray(keys[:, i]) for i in range(ncols))
    got = {}
    for winners in (False, True):
        _write_held_to(monkeypatch, winners)
        # a function of its own a side: ``jax.jit`` of one function
        # twice would hand the second side the first side's trace
        got[winners] = jax.jit(
            lambda t, k, v: fpset.lookup_or_insert(t, k, v)
        )(tcols, kcols, jnp.asarray(valid))
    for a, b in zip(
        jax.tree.leaves(got[False][:6]), jax.tree.leaves(got[True][:6])
    ):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape == (cap + 1,):
            a, b = a[:cap], b[:cap]
        assert np.array_equal(a, b)
    is_new, _cols, n_failed, _r, lane_rounds, _steps, saved = got[True]
    assert int(n_failed) == 0
    assert np.array_equal(
        np.asarray(is_new), _first_lanes_of_new_keys(held, keys, valid)
    )
    assert int(got[False][6]) == 0
    # the table was handed its winners in chunks: fewer lanes than were
    # presented, no fewer than won
    handed = int(lane_rounds) - int(saved)
    assert int(np.asarray(is_new).sum()) <= handed < int(lane_rounds)


def test_rehash_cols_is_the_same_by_either_write(monkeypatch):
    """A rehash (chunks packed, then the two-step ladder) by both
    writes: the same keys in the same slots, the same counters."""
    keys, old = _table_at_load(1 << 13, 0.4, 2, seed=42)
    got = {}
    for winners in (False, True):
        _write_held_to(monkeypatch, winners)
        # a jit of its own: the module's would hand the second side the
        # first side's trace
        got[winners] = jax.jit(
            lambda old, new: fpset._rehash_cols.__wrapped__(
                old, new, chunk=1 << 12, max_probes=fpset.MAX_PROBES,
                materialize="roll",
            )
        )(old, fpset.empty_cols(1 << 14, 2))
    (full, rhm_full), (mine, rhm_mine) = got[False], got[True]
    for a, b in zip(full, mine):
        assert np.array_equal(np.asarray(a)[:-1], np.asarray(b)[:-1])
    assert np.array_equal(np.asarray(rhm_full), np.asarray(rhm_mine))
    assert fpset.rhm_logical(rhm_mine)[:2] == (0, len(keys))
    _assert_holds_exactly(mine, keys)


def _scatters_into(nq, cap):
    """The update widths of every scatter into a table column (a
    ``u32[cap + 1]`` operand) in the jaxpr of a ``probe_insert`` of ``nq`` lanes, and the names
    of all its primitives."""
    u32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.uint32)  # noqa: E731
    jaxpr = jax.make_jaxpr(fpset.probe_insert)(
        (u32(cap + 1), u32(cap + 1)), (u32(nq), u32(nq)),
        jax.ShapeDtypeStruct((nq,), jnp.bool_),
    )
    widths, names = [], []

    def walk(j):
        for eqn in j.eqns:
            names.append(eqn.primitive.name)
            if eqn.primitive.name.startswith("scatter") and (
                eqn.invars[0].aval.shape == (cap + 1,)
                and eqn.invars[0].aval.dtype == jnp.uint32
            ):
                widths.append(eqn.invars[2].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return widths, names


def test_a_narrow_round_hands_the_table_no_scatter_as_wide_as_itself():
    """The jaxpr of a narrow ``probe_insert`` (the CLI's 1,024- and
    16,384-lane steps on the 9m binding's tables): every scatter into
    a table column is a chunk wide, inside a loop of its own; a wide
    round (the CLI's flush, the flagship's) and any round on a small
    table scatter their full width in the one loop, as they did."""
    for nq, cap in ((1024, 1 << 24), (16384, 1 << 25), (2560, 1 << 23)):
        widths, names = _scatters_into(nq, cap)
        assert widths == [(fpset.write_chunk(nq),)] * 2
        assert names.count("while") == 2 and "cumsum" in names
    for nq, cap in (
        (65536, 1 << 25), (1024, 1 << 21), (163840, 1 << 24),
        (16384, 1 << 24), (4096, 1 << 22),
    ):
        widths, names = _scatters_into(nq, cap)
        assert widths == [(nq,)] * 2
        assert names.count("while") == 1 and "cumsum" not in names
        assert "dynamic_slice" not in names


def test_the_writes_rule_reads_the_two_static_shapes_and_nothing_else():
    """``writes_winners(nq, cap)``: two parameters; narrower or on a
    larger table never turns it off; false under 2^22 slots, over
    16,384 lanes, and from a lane to 1,024 slots on, where the chip's
    full-width scatter is cheap by the lane (the measured crossovers);
    the chunk is one width for every round."""
    import inspect

    rule = fpset.writes_winners
    assert list(inspect.signature(rule).parameters) == ["nq", "cap"]
    assert (fpset.NARROW_MAX_LANES, fpset.NARROW_MIN_SLOTS) == (
        1 << 14, 1 << 22
    )
    widths = (64, 256, 1024, 1536, 2048, 2560, 4096, 8192, 16384, 32768,
              65536, 163840)
    widest = {22: 2560, 23: 4096, 24: 8192, 25: 16384, 26: 16384, 27: 16384}
    for log_cap in range(6, 28):
        engaged = [nq for nq in widths if rule(nq, 1 << log_cap)]
        assert engaged == [
            nq for nq in widths if nq <= widest.get(log_cap, 0)
        ]
        assert all(rule(nq, 2 << log_cap) for nq in engaged)
    # the CLI's ladder on the 9m binding, the rehash's and the sharded
    # flush's narrow steps; not the wide flushes
    assert rule(1024, 1 << 22) and rule(1024, 1 << 25)
    assert rule(2560, 1 << 22) and rule(1536, 1 << 23)
    assert rule(8192, 1 << 24) and not rule(16384, 1 << 24)
    assert not rule(4096, 1 << 22) and not rule(1024, 1 << 21)
    assert not rule(26738688, 1 << 27) and not rule(98304, 1 << 23)
    assert not rule(40960, 1 << 25) and not rule(65536, 1 << 27)
    assert [fpset.write_chunk(n) for n in (64, 1024, 2560, 16384)] == [
        64, 128, 128, 128
    ]


# a schedule that no other test runs, so that the programs of the runs
# below are traced under the rule they hold
_OWN_STAGES = ((4, 16), (8, 24), (16, 42))


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_write_lanes_are_the_lanes_presented_where_no_round_is_narrow(
    fuse,
):
    """Tier-1's tables are under 2^22 slots: every lane presented is
    handed to the scatters, the device's word stays 0 and the two
    ratios are one."""
    ck = DeviceChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), sub_batch=256,
        fuse=fuse, visited_cap=1 << 8, frontier_cap=1 << 12,
    )
    r = ck.run()
    st = ck.last_stats
    assert r.distinct_states == 1654
    assert int(ck._last_fpm[fpset.FPM_WRITE_SAVED]) == 0
    assert st["fpset_write_lanes"] == st["fpset_lane_rounds"] > 0
    assert (
        st["fpset_write_lanes_per_valid"]
        == st["fpset_lanes_presented_per_valid"]
    )


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_write_lanes_fall_where_narrow_rounds_write_their_winners(
    monkeypatch, fuse
):
    """With the rule held on, the same run hands the scatters fewer
    lanes than it presents, and no fewer than won: the counter is the
    lanes presented less the device's word, over the valid lanes."""
    _write_held_to(monkeypatch, True)
    ck = DeviceChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), sub_batch=256,
        fuse=fuse, visited_cap=1 << 8, frontier_cap=1 << 12,
        fpset_stages=_OWN_STAGES,
    )
    r = ck.run()
    st = ck.last_stats
    assert r.distinct_states == 1654 and st["fpset_failures"] == 0
    saved = int(ck._last_fpm[fpset.FPM_WRITE_SAVED])
    assert 0 < saved < st["fpset_lane_rounds"]
    assert st["fpset_write_lanes"] == st["fpset_lane_rounds"] - saved
    assert r.distinct_states <= st["fpset_write_lanes"]
    assert st["fpset_write_lanes_per_valid"] == round(
        st["fpset_write_lanes"] / st["fpset_valid_lanes"], 4
    ) < st["fpset_lanes_presented_per_valid"]
    # the rounds by step and the lanes presented are the schedule's,
    # whatever the write
    assert sum(st["fpset_step_rounds"]) == st["fpset_probe_rounds"]


@pytest.mark.parametrize("winners", [False, True])
def test_write_lanes_restart_at_a_resume(monkeypatch, tmp_path, winners):
    """A resumed run counts the lanes of its own flushes: the frame's
    lanes presented and the frame's device word are where the fold
    starts, as the slot rounds and the lane arbitration's do."""
    _write_held_to(monkeypatch, winners)
    frame = str(tmp_path / "run.npz")
    kw = dict(
        sub_batch=256, visited_cap=1 << 8, frontier_cap=1 << 12,
        fpset_stages=_OWN_STAGES if winners else None,
        checkpoint_path=frame,
    )
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    first = DeviceChecker(m, checkpoint_every=3, **kw)
    assert first.run().distinct_states == 1654
    whole = dict(first.last_stats)
    with np.load(frame) as d:
        at = np.asarray(d["fpm"], np.int64)
    lanes_at = int(fpset.fpm_logical(at)[5])
    valid_at = int(fpset.fpm_logical(at)[3])
    assert 0 < lanes_at < whole["fpset_lane_rounds"]
    ck = DeviceChecker(m, **kw)
    assert ck.run(resume=True).distinct_states == 1654
    st = ck.last_stats
    assert st["fpset_lane_rounds"] == whole["fpset_lane_rounds"]
    saved = int(ck._last_fpm[fpset.FPM_WRITE_SAVED]) - int(
        at[fpset.FPM_WRITE_SAVED]
    )
    assert (saved > 0) == winners
    assert st["fpset_write_lanes"] == (
        whole["fpset_lane_rounds"] - lanes_at - saved
    ) < whole["fpset_write_lanes"]
    assert st["fpset_write_lanes_per_valid"] == round(
        st["fpset_write_lanes"] / (st["fpset_valid_lanes"] - valid_at), 4
    )


def test_fpm_update_keeps_the_word_of_a_full_width_flush_as_it_was():
    """``write_saved`` rides the word behind the step rounds: a uint32
    that wraps, added as its bit pattern; the plain 0 of a flush that
    writes every lane adds nothing, and a vector with no step words
    (the sharded engine's) has no such word."""
    args = (jnp.int32(3), jnp.int32(0), jnp.int32(7), jnp.uint32(9))
    steps = (jnp.int32(1), 0, jnp.int32(2))
    wide = jnp.zeros((fpset.FPM_WIDE_N,), jnp.int32)
    assert fpset.FPM_WIDE_N == 16 and fpset.FPM_WRITE_SAVED == 15
    one = fpset.fpm_update(wide, *args, steps, jnp.uint32(5))
    two = fpset.fpm_update(one, *args, steps, jnp.uint32(2**32 - 2))
    assert int(one[15]) == 5 and (int(two[15]) & 0xFFFFFFFF) == 3
    assert int(fpset.fpm_update(two, *args, steps)[15]) == int(two[15])
    assert fpset.fpm_step_rounds(np.asarray(two), ((4, 16),) * 2) == [
        2, 0, 4
    ]
    narrow = fpset.fpm_update(
        jnp.zeros((fpset.FPM_N,), jnp.int32), *args, steps, jnp.uint32(5)
    )
    assert narrow.shape == (fpset.FPM_N,)
    # the equations of a flush that writes every lane: none for the word
    # but the one add the vector's last word always had
    with_word = jax.make_jaxpr(
        lambda f: fpset.fpm_update(f, *args, steps, 0)
    )(wide)
    before = jax.make_jaxpr(lambda f: fpset.fpm_update(f, *args, steps))(
        wide
    )
    assert str(with_word) == str(before)


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


def test_sharded_fpset_counts_match_oracle():
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        CompactionModel(c), n_devices=4, invariants=(), sub_batch=64,
        visited_cap=1 << 6, group=2,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


# ---- the flush and the keys against plain references ------------------


def _rand_cols(key, n, K):
    cols = []
    for _ in range(K):
        key, sub = jax.random.split(key)
        cols.append(jax.random.bits(sub, (n,), jnp.uint32))
    return key, tuple(cols)


def _flush_against_a_set(tcols, kcols, n_acc, members):
    """One ``flush_acc`` held to a Python set: the lanes flagged new
    are exactly the first occurrence of each key that is valid (inside
    ``n_acc``, not all-SENTINEL) and not yet a member."""
    fpm = jnp.zeros((fpset.FPM_N,), jnp.int32)
    t2, n_new, flags, fpm2 = fpset.flush_acc(
        tcols, kcols, jnp.int32(n_acc), fpm
    )
    host = np.stack([np.asarray(c) for c in kcols], axis=1)
    seen = set(members)
    want = np.zeros((host.shape[0],), np.uint32)
    for lane in range(n_acc):
        k = tuple(int(x) for x in host[lane])
        if all(x == 0xFFFFFFFF for x in k) or k in seen:
            continue
        seen.add(k)
        want[lane] = 1
    assert np.array_equal(np.asarray(flags), want)
    assert int(n_new) == int(want.sum())
    assert int(np.asarray(fpm2)[2]) == 0  # no lane failed
    # the table now holds exactly the set
    cap = t2[0].shape[0] - 1
    occ = np.asarray(fpset.occupied_mask(t2))[:cap]
    held = {
        tuple(int(np.asarray(c)[i]) for c in t2)
        for i in np.flatnonzero(occ)
    }
    assert held == seen
    return want


# (cap_log2, nq, dup_frac, n_acc_frac, fill_frac): ragged lane counts,
# dup-heavy batches, stale tails past n_acc, and a growth-boundary
# load; fill_frac keeps the post-flush load under the engines' growth
# threshold (they rehash BEFORE a flush could overload the table)
FLUSH_SHAPES = [
    (12, 1000, 0.0, 1.0, 0.375),
    (12, 1024, 0.6, 1.0, 0.5),
    (11, 777, 0.5, 0.61, 0.375),
    (11, 2048, 0.9, 0.83, 0.25),
    (13, 3000, 0.3, 1.0, 0.375),
]


@pytest.mark.parametrize(
    "cap_log2,nq,dup_frac,n_acc_frac,fill_frac", FLUSH_SHAPES
)
def test_flush_acc_against_a_python_set(
    cap_log2, nq, dup_frac, n_acc_frac, fill_frac
):
    cap, K = 1 << cap_log2, 2
    key = jax.random.PRNGKey(cap_log2 * 1000 + nq)
    key, fill_cols = _rand_cols(key, int(cap * fill_frac), K)
    nfill = fill_cols[0].shape[0]
    _flush_against_a_set(
        fpset.empty_cols(cap, K), fill_cols, nfill, set()
    )
    tcols, _, _, _ = fpset.flush_acc(
        fpset.empty_cols(cap, K), fill_cols, jnp.int32(nfill),
        jnp.zeros((fpset.FPM_N,), jnp.int32),
    )
    members = set(
        zip(*(np.asarray(c).tolist() for c in fill_cols))
    )
    ndup = int(nq * dup_frac)
    key, fresh = _rand_cols(key, nq - ndup, K)
    dup_ix = jnp.arange(ndup) % nfill
    kcols = tuple(
        jnp.concatenate([f[dup_ix], g])
        for f, g in zip(fill_cols, fresh)
    )
    # a few SENTINEL (masked-expand) lanes sprinkled in
    sent = jnp.arange(nq) % 97 == 3
    kcols = tuple(
        jnp.where(sent, jnp.uint32(0xFFFFFFFF), c) for c in kcols
    )
    _flush_against_a_set(tcols, kcols, int(nq * n_acc_frac), members)


def test_flush_acc_within_batch_duplicates_first_lane_wins():
    """Lanes presenting the SAME new key in one batch: exactly one
    winner, the minimum lane id — what gives every state its gid."""
    cap, K, nq = 1 << 10, 2, 512
    _key, cols = _rand_cols(jax.random.PRNGKey(7), nq, K)
    # groups of 4 consecutive lanes share a key
    kcols = tuple(c[::4].repeat(4)[:nq] for c in cols)
    want = _flush_against_a_set(
        fpset.empty_cols(cap, K), kcols, nq, set()
    )
    assert (np.flatnonzero(want) % 4 == 0).all()


def _np_murmur3_words(words, seed):
    """murmur3_32 over the trailing word axis, in 64-bit numpy integers
    masked to 32 bits (no reliance on wrap-around)."""
    m = np.uint64(0xFFFFFFFF)
    w64 = words.astype(np.uint64)

    def rotl(x, r):
        return ((x << np.uint64(r)) | (x >> np.uint64(32 - r))) & m

    h = np.full(words.shape[:-1], seed, np.uint64)
    for i in range(words.shape[-1]):
        k = (w64[..., i] * np.uint64(0xCC9E2D51)) & m
        k = (rotl(k, 15) * np.uint64(0x1B873593)) & m
        h = h ^ k
        h = (rotl(h, 13) * np.uint64(5) + np.uint64(0xE6546B64)) & m
    h = h ^ np.uint64(4 * words.shape[-1])
    h = h ^ (h >> np.uint64(16))
    h = (h * np.uint64(0x85EBCA6B)) & m
    h = h ^ (h >> np.uint64(13))
    h = (h * np.uint64(0xC2B2AE35)) & m
    return (h ^ (h >> np.uint64(16))).astype(np.uint32)


@pytest.mark.parametrize(
    "total_bits,W,fp_bits",
    [(60, 2, None), (90, 3, None), (160, 5, 64), (160, 5, 96)],
)
def test_keyspec_make_against_numpy(total_bits, W, fp_bits):
    """Exact layouts: the key IS the packed words (zero-padded to the
    column count).  Hashed layouts: one murmur3 per column, and the
    all-SENTINEL tuple (the table's empty marker) never comes out."""
    from pulsar_tlaplus_tpu.ops.dedup import KeySpec

    ks = KeySpec(total_bits, W, fp_bits)
    for nc in (257, 4096, 5000):
        packed = np.asarray(
            jax.random.bits(jax.random.PRNGKey(nc), (nc, W), jnp.uint32)
        )
        got = [np.asarray(c) for c in ks.make(jnp.asarray(packed))]
        if ks.exact:
            want = [packed[:, i] for i in range(W)]
            want += [np.zeros((nc,), np.uint32)] * (ks.ncols - W)
        else:
            assert ks.ncols == fp_bits // 32
            seeds = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35)[: ks.ncols]
            want = [_np_murmur3_words(packed, s) for s in seeds]
            sent = np.all(
                np.stack(want) == np.uint32(0xFFFFFFFF), axis=0
            )
            want[-1] = np.where(sent, want[-1] ^ np.uint32(1), want[-1])
        assert len(got) == len(want) == ks.ncols
        for a, b in zip(got, want):
            assert np.array_equal(a, b)
