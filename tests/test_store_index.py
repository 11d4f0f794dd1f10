"""The cold tier's ONE lookup index (PR 51): ``TieredStore.lookup_keys``
against the per-run walk it replaced, kept HERE as the reference —
masks and miss accounting batch for batch; the runs' durable format
(``spill_v`` 1, one ``key_runs`` entry and one file an eviction) read
and written as the parent's; a degraded store; and a tiered check
whose every answer comes from the walk instead."""

import os

import numpy as np
import pytest

from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.store import compress as codec
from pulsar_tlaplus_tpu.store import tiers
from pulsar_tlaplus_tpu.store.tiers import TieredStore
from pulsar_tlaplus_tpu.utils import faults
from tests.helpers import SMALL_CONFIGS, tight_hbm_budget

U32 = (1 << 32) - 1
# the stats and the run entries a frame of the parent commit carries
PARENT_FIELDS = (
    "evictions", "keys_evicted", "rows_evicted", "logs_evicted",
    "bytes_raw", "bytes_comp", "transfer_s", "blocked_s",
    "misses_resolved", "miss_hits", "miss_batches", "lookup_s", "joins",
)
RUN_ENTRY = {"n", "file", "digest", "raw", "comp"}


class WalkedRuns:
    """The parent's cold key tier: the runs kept apart, never merged,
    and every one of them searched twice for every batch."""

    def __init__(self):
        self.runs, self.cols = [], []
        self.misses_resolved = self.miss_hits = 0

    def evict(self, kcols):
        hi, lo = codec.pack_keys(kcols)
        if len(hi):
            self.runs.append((hi, lo))
            self.cols.append(kcols)

    def lookup(self, kcols):
        qhi, qlo = codec.pack_keys(kcols)
        member = np.zeros(qhi.shape, bool)
        for hi, lo in self.runs:
            sel = (qhi >= hi[0]) & (qhi <= hi[-1]) & ~member
            if not sel.any():
                continue
            qh = qhi[sel]
            left = np.searchsorted(hi, qh, "left")
            right = np.searchsorted(hi, qh, "right")
            hit = np.zeros(qh.shape, bool)
            simple = right - left == 1
            idx = np.clip(left, 0, len(hi) - 1)
            hit[simple] = lo[idx[simple]] == qlo[sel][simple]
            for t in np.nonzero(right - left > 1)[0]:
                seg = lo[left[t]: right[t]]
                p = np.searchsorted(seg, qlo[sel][t])
                hit[t] = p < len(seg) and seg[p] == qlo[sel][t]
            member[np.nonzero(sel)[0][hit]] = True
        self.misses_resolved += len(qhi)
        self.miss_hits += int(member.sum())
        return member


def _run(rows):
    """Distinct key rows ``[n, K]`` as one sorted run's K columns."""
    rows = np.unique(np.asarray(rows, np.uint64).astype(np.uint32), axis=0)
    return _cols(rows, rows.shape[1])


def _cols(rows, K):
    rows = np.asarray(rows, np.uint64).astype(np.uint32).reshape(-1, K)
    return tuple(np.ascontiguousarray(rows[:, j]) for j in range(K))


def _rand(rng, n, K, bits=32):
    return rng.integers(0, 1 << bits, (n, K), np.uint64)


def _batch(rng, runs, K, n=600, bits=32):
    """Lane-order queries: some evicted keys, some near misses (a key
    of a run with one column changed), some fresh."""
    have = np.concatenate([np.stack(r, 1) for r in runs if len(r[0])] or
                          [np.zeros((0, K), np.uint32)]).astype(np.uint64)
    q = [_rand(rng, n // 3, K, bits)]
    if len(have):
        pick = have[rng.integers(0, len(have), n // 3)]
        near = have[rng.integers(0, len(have), n // 3)].copy()
        near[:, K - 1] ^= 1
        q += [pick, near]
    q = np.concatenate(q)
    return _cols(q[rng.permutation(len(q))], K)


def _case_random(K):
    def build(rng):
        runs = [_run(_rand(rng, 3000, K)) for _ in range(4)]
        return K, runs, [_batch(rng, runs, K) for _ in range(3)]
    return build


def _case_equal_hi_blocks(rng):
    # 3-column keys that share their first two columns sixty at a time
    def run():
        head = np.repeat(_rand(rng, 40, 2, bits=6), 60, axis=0)
        return _run(np.concatenate([head, _rand(rng, 2400, 1, 12)], 1))
    runs = [run() for _ in range(3)]
    return 3, runs, [_batch(rng, runs, 3, bits=6) for _ in range(3)]


def _case_key_in_two_runs(K):
    def build(rng):
        a = _rand(rng, 2000, K)
        b = np.concatenate([a[:700], _rand(rng, 1500, K)])
        runs = [_run(a), _run(b), _run(a[300:900])]
        return K, runs, [_batch(rng, runs, K), _cols(a[:900], K)]
    return build


def _case_repeated_queries(rng):
    runs = [_run(_rand(rng, 1000, 2)) for _ in range(2)]
    q = np.stack(runs[0], 1)[rng.integers(0, 50, 400)]
    q[::7] ^= 5
    return 2, runs, [_cols(q, 2)]


def _case_empty_batch(rng):
    return 2, [_run(_rand(rng, 500, 2))], [_cols(np.zeros((0, 2)), 2)]


def _case_empty_store(rng):
    return 3, [], [_batch(rng, [], 3), _cols(np.zeros((0, 3)), 3)]


def _case_empty_run_evicted(rng):
    runs = [_run(_rand(rng, 300, 2)), _cols(np.zeros((0, 2)), 2)]
    return 2, runs, [_batch(rng, runs, 2)]


def _case_one_run(K):
    def build(rng):
        runs = [_run(_rand(rng, 5000, K))]
        return K, runs, [_batch(rng, runs, K)]
    return build


def _case_twelve_runs(K):
    def build(rng):
        runs = [_run(_rand(rng, 1500, K, bits=14)) for _ in range(12)]
        return K, runs, [_batch(rng, runs, K, bits=14) for _ in range(2)]
    return build


def _case_extreme_columns(K):
    def build(rng):
        ends = np.array(np.meshgrid(*[[0, U32]] * K)).reshape(K, -1).T
        runs = [
            _run(np.concatenate([ends[::2], _rand(rng, 200, K)])),
            _run(np.concatenate([ends[1::2], _rand(rng, 200, K)])),
        ]
        mid = ends.copy()
        mid[:, K - 1] ^= 1
        return K, runs, [_cols(np.concatenate([ends, mid]), K),
                         _batch(rng, runs, K)]
    return build


def _case_one_key_a_run(rng):
    runs = [_run([[7, 9, k]]) for k in (5, 1, 3, 1)]
    return 3, runs, [_cols([[7, 9, k] for k in range(7)], 3)]


CASES = {
    "k2": _case_random(2),
    "k3": _case_random(3),
    "k3_equal_hi_blocks": _case_equal_hi_blocks,
    "k2_key_in_two_runs": _case_key_in_two_runs(2),
    "k3_key_in_two_runs": _case_key_in_two_runs(3),
    "repeated_queries": _case_repeated_queries,
    "empty_batch": _case_empty_batch,
    "empty_store": _case_empty_store,
    "empty_run_evicted": _case_empty_run_evicted,
    "k2_one_run": _case_one_run(2),
    "k3_one_run": _case_one_run(3),
    "k2_twelve_runs": _case_twelve_runs(2),
    "k3_twelve_runs": _case_twelve_runs(3),
    "k2_extreme_columns": _case_extreme_columns(2),
    "k3_extreme_columns": _case_extreme_columns(3),
    "k3_one_key_a_run": _case_one_key_a_run,
}


@pytest.mark.parametrize("case", list(CASES))
def test_indexed_lookup_is_the_per_run_walk(case):
    """Every batch after every eviction: the mask, in lane order, and
    the miss accounting are the walk's; the index holds each evicted
    key once, sorted; the runs stay one record an eviction."""
    K, runs, batches = CASES[case](np.random.default_rng(51))
    ts, ref = TieredStore(K), WalkedRuns()
    try:
        for run in [None, *runs]:
            if run is not None:
                assert ts.evict_keys(run) == len(run[0])
                ref.evict(run)
            for b in batches:
                got = ts.lookup_keys(b)
                assert got.dtype == bool and got.shape == b[0].shape
                assert (got == ref.lookup(b)).all()
        ts.flush()
        st = ts.stats
        assert st.misses_resolved == ref.misses_resolved
        assert st.miss_hits == ref.miss_hits
        assert st.miss_batches == len(batches) * (len(runs) + 1)
        assert ts.cold_runs == st.evictions == st.merges == len(ref.runs)
        assert ts.has_cold_keys == bool(ref.runs)
        assert ts.cold_keys == st.keys_evicted == sum(
            len(hi) for hi, _ in ref.runs)
        keys = {
            (int(h), int(x)) for hi, lo in ref.runs for h, x in zip(hi, lo)
        }
        index = list(zip(*(plane.tolist() for plane in ts._index)))
        assert index == sorted(keys)
        assert st.index_keys == len(keys) <= st.keys_evicted
        assert all("hi" not in r and "lo" not in r for r in ts._runs)
    finally:
        ts.close()


# ---- durability ---------------------------------------------------------


def _filled(ts, ref, rng, K=3):
    base = _rand(rng, 900, K, bits=10)
    for rows in (base, np.concatenate([base[:200], _rand(rng, 700, K, 10)]),
                 _rand(rng, 500, K, 10)):
        run = _run(rows)
        ts.evict_keys(run)
        ref.evict(run)
    return [_batch(rng, ref.cols, K, bits=10) for _ in range(3)]


def test_manifest_and_restore_keep_the_parents_format(tmp_path):
    sdir = str(tmp_path / "spill")
    rng = np.random.default_rng(7)
    ts, ref = TieredStore(3, spill_dir=sdir, durable=True), WalkedRuns()
    batches = _filled(ts, ref, rng)
    want = [ts.lookup_keys(b) for b in batches]
    man = ts.manifest()
    ts.close()
    assert man["spill_v"] == 1 and len(man["key_runs"]) == 3
    for e, (hi, lo) in zip(man["key_runs"], ref.runs):
        assert set(e) == RUN_ENTRY and e["n"] == len(hi)
        with open(os.path.join(sdir, e["file"]), "rb") as f:
            dhi, dlo = codec.decode_key_run(f.read())
        assert (dhi == hi).all() and (dlo == lo).all()
    # no new kind of file: one .ptsk a run
    assert sorted(os.listdir(sdir)) == sorted(
        e["file"] for e in man["key_runs"])
    # what the parent's restore reads of the stats is all there
    assert set(PARENT_FIELDS) <= set(man["stats"])
    assert man["stats"]["merges"] == 3
    assert man["stats"]["index_keys"] < man["stats"]["keys_evicted"]
    ts2 = TieredStore(3, spill_dir=sdir, durable=True)
    ts2.restore(man)
    assert [ts2.lookup_keys(b).tolist() for b in batches] == [
        w.tolist() for w in want]
    assert all((ts2.lookup_keys(b) == ref.lookup(b)).all() for b in batches)
    assert all((a == b).all() for a, b in zip(ts2._index, ts._index))
    assert ts2.cold_runs == 3 and ts2.stats.merges == 3
    assert ts2.stats.index_keys == len(ts._index[0])
    man2 = ts2.manifest()
    assert man2["key_runs"] == man["key_runs"]
    ts2.close()


def test_a_frame_the_parent_wrote_restores_and_answers_the_same(tmp_path):
    """The parent's writer, spelt out: one encoded ``.ptsk`` a run, a
    ``key_runs`` entry each, the thirteen stats it knew."""
    sdir = str(tmp_path / "spill")
    os.makedirs(sdir)
    rng = np.random.default_rng(8)
    ref, entries = WalkedRuns(), []
    base = _rand(rng, 800, 2)
    for i, rows in enumerate((base, np.concatenate([base[:300],
                                                    _rand(rng, 400, 2)]))):
        run = _run(rows)
        ref.evict(run)
        blob, raw, comp = codec.encode_key_run(*ref.runs[-1], True)
        name = f"keys_{i + 1}.ptsk"
        with open(os.path.join(sdir, name), "wb") as f:
            f.write(blob)
        entries.append({"n": len(run[0]), "file": name,
                        "digest": tiers._digest(blob), "raw": raw,
                        "comp": comp})
    stats = dict.fromkeys(PARENT_FIELDS, 0)
    stats.update(evictions=2, keys_evicted=sum(e["n"] for e in entries))
    man = {"spill_v": 1, "ncols": 2, "compress": True, "durable": True,
           "stats": stats, "key_runs": entries, "rows": [], "logs": []}
    ts = TieredStore(2, spill_dir=sdir, durable=True)
    ts.restore(man)
    for _ in range(3):
        b = _batch(rng, ref.cols, 2)
        assert (ts.lookup_keys(b) == ref.lookup(b)).all()
    assert ts.cold_runs == 2 and ts.cold_keys == stats["keys_evicted"]
    assert ts.stats.merges == 0  # the parent merged nothing
    assert ts.stats.index_keys == stats["keys_evicted"] - 300
    # and goes on: a third run lands beside the two
    run = _run(_rand(rng, 100, 2))
    ts.evict_keys(run)
    assert [e["file"] for e in ts.manifest()["key_runs"]] == [
        "keys_1.ptsk", "keys_2.ptsk", "keys_3.ptsk"]
    ts.close()


def test_a_degraded_store_stays_queryable_through_the_index(
    tmp_path, monkeypatch
):
    """The ``enospc@spill`` drill on the second run's write: nothing
    more is durable, every evicted key still answers."""
    monkeypatch.setenv("PTT_FAULT", "enospc@spill:2")
    faults.reset()
    try:
        rng = np.random.default_rng(9)
        ts = TieredStore(3, spill_dir=str(tmp_path / "s"), durable=True)
        ref = WalkedRuns()
        batches = _filled(ts, ref, rng)
        ts.flush()
        assert ts.degraded and ts.cold_runs == 3
        assert [r["file"] is None for r in ts._runs] == [False, True, True]
        for b in batches:
            assert (ts.lookup_keys(b) == ref.lookup(b)).all()
        assert ts.stats.miss_hits == ref.miss_hits > 0
        with pytest.raises(ValueError, match="degraded"):
            ts.manifest()
        ts.close()
    finally:
        faults.reset()


def test_wipe_empties_the_index():
    ts = TieredStore(2)
    run = _run(_rand(np.random.default_rng(3), 100, 2))
    ts.evict_keys(run)
    assert ts.lookup_keys(run).all()
    ts.wipe()
    assert not ts.has_cold_keys and not ts.lookup_keys(run).any()
    assert ts.stats.index_keys == 0 and len(ts._index[0]) == 0
    ts.close()


# ---- the engine ---------------------------------------------------------

PARITY = (
    "spill_fetches", "spill_fetch_planes", "spill_d2h_bytes",
    "spill_d2h_padded_bytes", "spill_misses_resolved", "spill_miss_hits",
    "spill_evictions", "spill_keys_evicted", "spill_rows_evicted",
    "spill_cold_runs", "spill_syncs", "spill_hot_keys_max",
    "stage_sieve_n", "stage_unflag_n", "stage_evict_n",
)


def _tiered_run():
    def mk(budget):
        return DeviceChecker(
            CompactionModel(SMALL_CONFIGS["producer_on"]),
            invariants=(), check_deadlock=False, sub_batch=64,
            visited_cap=1 << 9, frontier_cap=1 << 9, hbm_budget=budget,
        )

    ck = mk(tight_hbm_budget(mk))
    return ck, ck.run()


def test_a_tiered_check_is_the_same_answered_by_the_walk(monkeypatch):
    """The 1,654-state binding under a budget that evicts three times
    and more: every fetch, every cold lookup and its hits, the level
    sizes and the tiered line to the digit, whether a batch is
    answered from the index or by the walk over the runs."""
    ck, r = _tiered_run()
    st = ck.last_stats
    assert r.distinct_states == 1654
    assert st["spill_merges"] == st["spill_evictions"] >= 3
    assert st["spill_cold_runs"] == st["spill_evictions"]
    assert 0 < st["spill_index_keys"] <= st["spill_keys_evicted"]
    assert st["spill_index_keys"] == len(ck.tstore._index[0])
    assert st["spill_merge_s"] > 0 and st["spill_miss_hits"] > 0

    walks = {}
    real_evict = TieredStore.evict_keys

    def evict(self, kcols):
        walks.setdefault(id(self), WalkedRuns()).evict(kcols)
        return real_evict(self, kcols)

    def lookup(self, kcols):
        member = walks[id(self)].lookup(kcols)
        self.stats.misses_resolved += len(member)
        self.stats.miss_hits += int(member.sum())
        self.stats.miss_batches += 1
        return member

    monkeypatch.setattr(TieredStore, "evict_keys", evict)
    monkeypatch.setattr(TieredStore, "lookup_keys", lookup)
    ck2, r2 = _tiered_run()
    st2 = ck2.last_stats
    assert len(walks) == 1 and st2["spill_lookup_s"] == 0
    assert list(r2.level_sizes) == list(r.level_sizes)
    assert r2.distinct_states == r.distinct_states
    assert {k: st2[k] for k in PARITY} == {k: st[k] for k in PARITY}
    assert cli.tiered_line(st2, 1654) == cli.tiered_line(st, 1654)
