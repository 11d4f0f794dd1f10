"""Tiered state store (round 16): codec/budget/sieve units, the
tiered-vs-untiered state-for-state differentials on the pinned
compaction oracles, the 45,198-state acceptance run with the hot tier
pinned under 25% of the reachable set, crash/suspend resume through
the spill manifest, schema-v9 validation, and the spill ledger gate."""

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import ledger, report
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.store import budget as store_budget
from pulsar_tlaplus_tpu.store import compress as codec
from pulsar_tlaplus_tpu.store import sieve as store_sieve
from pulsar_tlaplus_tpu.store.tiers import (
    TieredStore,
    cleanup_stale_spill,
)
from tests.helpers import (
    SMALL_CONFIGS,
    assert_valid_counterexample,
    tight_hbm_budget,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPILL_PINNED = os.path.join(
    ROOT, "tests", "data", "mini_bench_spill_producer_on.jsonl"
)


def _checker_mod():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(ROOT, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(c, **kw):
    kw.setdefault("invariants", ())
    kw.setdefault("check_deadlock", False)
    kw.setdefault("sub_batch", 64)
    kw.setdefault("visited_cap", 1 << 9)
    kw.setdefault("frontier_cap", 1 << 9)
    return DeviceChecker(CompactionModel(c), **kw)


def _tight_budget(c, slack=4096, **kw):
    """A budget just above the engine's initial-tier minimum — tiers
    pinned at their smallest, so the run MUST spill (the shared
    helpers.tight_hbm_budget recipe at this file's shapes)."""
    return tight_hbm_budget(
        lambda b: _mk(c, hbm_budget=b, **kw), slack=slack
    )


def _merged_logs(ck, nv):
    """(parent, lane) over [0, nv) — cold segments + device window."""
    base = ck._last_rb["row_base"]
    cp, cl = ck.tstore.fetch_logs(0, base)
    par = np.concatenate(
        [cp, np.asarray(ck.last_bufs["parent"][: nv - base])]
    )
    lan = np.concatenate(
        [cl, np.asarray(ck.last_bufs["lane"][: nv - base])]
    )
    return par, lan


def _merged_rows(ck, nv):
    base = ck._last_rb["row_base"]
    W = ck.W
    cold = ck.tstore.fetch_rows(0, base, W)
    return np.concatenate(
        [cold, np.asarray(ck.last_bufs["rows"][: (nv - base) * W])]
    )


# ---------------------------------------------------- budget / codec


def test_parse_budget():
    assert store_budget.parse_budget("512M") == 512 << 20
    assert store_budget.parse_budget("7.5G") == int(7.5 * (1 << 30))
    assert store_budget.parse_budget("65536") == 65536
    assert store_budget.parse_budget(1 << 20) == 1 << 20
    for bad in ("", "12X", "-1", 0, "0M"):
        with pytest.raises(ValueError):
            store_budget.parse_budget(bad)


def test_resolve_budget_env(monkeypatch):
    monkeypatch.delenv(store_budget.ENV_VAR, raising=False)
    assert store_budget.resolve_budget(None) is None
    monkeypatch.setenv(store_budget.ENV_VAR, "2M")
    assert store_budget.resolve_budget(None) == 2 << 20
    assert store_budget.resolve_budget("1M") == 1 << 20  # explicit wins


@pytest.mark.parametrize("compress", [True, False])
def test_key_run_codec_roundtrip(compress):
    rng = np.random.default_rng(7)
    hi = np.sort(rng.integers(0, 1 << 60, 5000).astype(np.uint64))
    lo = rng.integers(0, 1 << 32, 5000).astype(np.uint32)
    blob, raw, comp = codec.encode_key_run(hi, lo, compress=compress)
    assert raw == hi.nbytes + lo.nbytes
    if compress:
        assert comp < raw  # sorted deltas must actually compress
    hi2, lo2 = codec.decode_key_run(blob)
    assert (hi2 == hi).all() and (lo2 == lo).all()
    # empty run round-trips too
    b2, _, _ = codec.encode_key_run(
        np.zeros(0, np.uint64), np.zeros(0, np.uint32)
    )
    h, l = codec.decode_key_run(b2)
    assert len(h) == 0 and len(l) == 0


def test_plane_codec_roundtrip_and_magic():
    arr = np.arange(1000, dtype=np.int32) - 500
    blob, raw, comp = codec.encode_plane(arr)
    assert (codec.decode_plane(blob) == arr).all()
    with pytest.raises(ValueError, match="magic"):
        codec.decode_plane(b"XXXX" + blob[4:])
    with pytest.raises(ValueError, match="magic"):
        codec.decode_key_run(blob)  # wrong blob kind


def test_pack_keys_order_matches_column_sort():
    rng = np.random.default_rng(3)
    cols = tuple(
        rng.integers(0, 1 << 32, 300).astype(np.uint32)
        for _ in range(2)
    )
    hi, lo = codec.pack_keys(cols)
    order = np.lexsort((lo, hi))
    # unsigned lexicographic column order == (hi, lo) order
    order2 = np.lexsort((cols[1], cols[0]))
    assert (order == order2).all()
    back = codec.unpack_keys(hi, lo, 2)
    assert all((a == b).all() for a, b in zip(back, cols))


# ------------------------------------------------------- TieredStore


def test_store_evict_lookup_and_miss_accounting():
    ts = TieredStore(2)
    rng = np.random.default_rng(11)
    keys = np.unique(rng.integers(0, 1 << 48, 4000).astype(np.uint64))
    c0 = (keys >> np.uint64(32)).astype(np.uint32)
    c1 = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    assert ts.evict_keys((c0, c1)) == len(keys)
    assert ts.has_cold_keys and ts.cold_keys == len(keys)
    # members hit, fresh keys miss
    q0 = np.concatenate([c0[:100], c0[:100] ^ np.uint32(0x5A5A5A5A)])
    q1 = np.concatenate([c1[:100], c1[:100]])
    mask = ts.lookup_keys((q0, q1))
    assert mask[:100].all()
    assert not mask[100:].any() or (
        # astronomically unlikely collision with the xor'd keys
        mask[100:].sum() == 0
    )
    assert ts.stats.misses_resolved == 200
    ts.flush()
    assert ts.stats.bytes_comp > 0
    ts.close()


@pytest.mark.parametrize("K", [2, 3])
def test_lookup_reads_the_rows_of_a_packed_fetch(K):
    """A batch's key columns come to the host as rows of ONE flat word
    array (``DeviceChecker._spill_fetch_cols``): ``lookup_keys`` on the
    trimmed rows, views or copies, is the lookup on columns fetched one
    by one, and the lanes ride as the last row."""
    ts = TieredStore(K)
    rng = np.random.default_rng(47)
    cols = np.unique(
        rng.integers(0, 1 << 32, (4000, K), np.uint64).astype(np.uint32),
        axis=0,
    )
    cols = cols[np.lexsort(cols.T[::-1])]
    assert ts.evict_keys(tuple(cols[:, j].copy() for j in range(K)))
    size, n, lo = 1 << 10, 700, 13
    q = np.concatenate([cols[:400], cols[400:1024] ^ np.uint32(0xA5A5A5A5)])
    lanes = rng.permutation(size).astype(np.int32)
    flat = np.concatenate(
        [q[:, j] for j in range(K)] + [lanes.view(np.uint32)]
    )
    *kq, lq = (row[lo: lo + n] for row in flat.reshape(K + 1, size))
    assert all(k.flags["C_CONTIGUOUS"] for k in kq)
    want = ts.lookup_keys(
        [np.ascontiguousarray(q[lo: lo + n, j]) for j in range(K)]
    )
    assert want[: 400 - lo].all() and not want[400 - lo:].any()
    assert (ts.lookup_keys(kq) == want).all()
    assert (ts.lookup_keys([k.copy() for k in kq]) == want).all()
    assert (lq.view(np.int32) == lanes[lo: lo + n]).all()
    ts.close()


def test_store_rows_logs_gather_and_gap_detection():
    ts = TieredStore(2)
    W = 3
    ts.spill_rows(0, 10, np.arange(30, dtype=np.uint32))
    ts.spill_rows(10, 25, np.arange(30, 75, dtype=np.uint32))
    got = ts.fetch_rows(5, 20, W)
    assert (got == np.arange(15, 60, dtype=np.uint32)).all()
    assert ts.rows_spilled_hi == 25
    ts.spill_logs(0, 4, np.arange(4), np.arange(4) * 2)
    par, lan = ts.fetch_logs(1, 3)
    assert (par == [1, 2]).all() and (lan == [2, 4]).all()
    with pytest.raises(ValueError, match="gap"):
        ts.fetch_rows(20, 40, W)
    with pytest.raises(ValueError, match="gap"):
        ts.fetch_logs(2, 9)
    ts.close()


def test_store_manifest_restore_and_digest_tamper(tmp_path):
    sdir = str(tmp_path / "spill")
    ts = TieredStore(2, spill_dir=sdir, durable=True)
    c0 = np.sort(np.arange(100, dtype=np.uint32) * 7)
    c1 = np.arange(100, dtype=np.uint32)
    ts.evict_keys((c0, c1))
    ts.spill_rows(0, 8, np.arange(16, dtype=np.uint32))
    ts.spill_logs(0, 8, np.arange(8), np.arange(8))
    man = ts.manifest()
    ts.close()
    # restore in a fresh store: identical lookups and gathers
    ts2 = TieredStore(2, spill_dir=sdir, durable=True)
    ts2.restore(man)
    assert ts2.cold_keys == 100
    assert ts2.lookup_keys((c0[:5], c1[:5])).all()
    assert (ts2.fetch_rows(0, 8, 2) == np.arange(16)).all()
    # cumulative stats continue (the monotone telemetry contract)
    assert ts2.stats.keys_evicted == 100
    ts2.close()
    # a tampered spill file must fail the digest check loudly
    victim = os.path.join(sdir, man["key_runs"][0]["file"])
    with open(victim, "r+b") as f:
        f.seek(10)
        f.write(b"\xff")
    ts3 = TieredStore(2, spill_dir=sdir, durable=True)
    with pytest.raises(ValueError, match="digest mismatch"):
        ts3.restore(man)
    ts3.close()


def test_store_wipe_and_stale_tmp_hygiene(tmp_path):
    sdir = str(tmp_path / "spill")
    os.makedirs(sdir)
    # a crashed writer's temp and a dead run's spill files
    open(os.path.join(sdir, f"keys_1.ptsk.tmp.{os.getpid()}.1"), "w").close()
    open(os.path.join(sdir, "keys_9.ptsk"), "w").close()
    assert cleanup_stale_spill(sdir) == 1
    assert os.path.exists(os.path.join(sdir, "keys_9.ptsk"))
    ts = TieredStore(2, spill_dir=sdir, durable=True)
    ts.wipe()  # a FRESH run owns the dir: dead files must not leak
    assert os.listdir(sdir) == []
    ts.close()


# -------------------------------------------------- sieve device ops


def test_sieve_tag_evict_unflag_roundtrip():
    from pulsar_tlaplus_tpu.ops import fpset as fps
    from pulsar_tlaplus_tpu.ops.dedup import SENTINEL

    cap = 64
    tc = fps.empty_cols(cap, 2)
    keys = (
        jnp.asarray(np.arange(10, dtype=np.uint32) + 1),
        jnp.asarray(np.arange(10, dtype=np.uint32) * 3 + 1),
    )
    is_new, tc, nf, _, _, _, _ = fps.lookup_or_insert(
        tc, keys, jnp.ones((10,), bool)
    )
    assert int(nf) == 0 and bool(np.asarray(is_new).all())
    gen = jnp.zeros((cap + 1,), jnp.int32)
    gen = store_sieve.tag_generation(tc, gen, 1)
    assert int(np.asarray(gen).sum()) == 10  # 10 slots tagged epoch 1
    # second insert wave tags epoch 2
    keys2 = (
        jnp.asarray(np.arange(5, dtype=np.uint32) + 100),
        jnp.asarray(np.arange(5, dtype=np.uint32) + 200),
    )
    _, tc, nf2, _, _, _, _ = fps.lookup_or_insert(
        tc, keys2, jnp.ones((5,), bool)
    )
    assert int(nf2) == 0
    gen = store_sieve.tag_generation(tc, gen, 2)
    holed, gen2, ev, n_ev = store_sieve.extract_cold(tc, gen, 1)
    assert int(n_ev) == 10
    ev_np = [np.asarray(c[:10]) for c in ev]
    # sorted + exactly the epoch-1 keys
    hi, lo = codec.pack_keys(ev_np)
    assert (np.diff(hi.astype(np.int64)) >= 0).all()
    want_hi, _ = codec.pack_keys([np.asarray(k) for k in keys])
    assert set(hi.tolist()) == set(want_hi.tolist())
    # cleared slots: only the 5 epoch-2 keys remain occupied
    occ = ~np.asarray(fps.all_sentinel(holed))[:-1]
    assert occ.sum() == 5
    # unflag merges verdicts back
    flag = jnp.ones((16,), jnp.uint32)
    out = store_sieve.unflag_lanes(
        flag, jnp.asarray([3, 7, 0, 0], jnp.int32), jnp.int32(2)
    )
    out = np.asarray(out)
    assert out[3] == 0 and out[7] == 0 and out.sum() == 14
    # sieve_new packs exactly the flagged lanes with original ids
    ak = tuple(
        jnp.asarray(np.arange(16, dtype=np.uint32) + 10 * (i + 1))
        for i in range(2)
    )
    flags = np.zeros((16,), np.uint32)
    flags[[2, 5, 11]] = 1
    out = store_sieve.sieve_new(ak, jnp.asarray(flags))
    n = int(out[-1])
    assert n == 3
    lanes = np.asarray(out[-2][:n])
    assert (lanes == [2, 5, 11]).all()
    assert (np.asarray(out[0][:n]) == np.asarray(ak[0])[[2, 5, 11]]).all()


def test_extract_cold_against_numpy():
    """``extract_cold`` on a three-quarters-full 2^11-slot table with
    five generations: the holed table, the cleared generations, the
    eviction run (sorted, SENTINEL-padded) and the count are what numpy
    computes from the same arrays."""
    import jax

    from pulsar_tlaplus_tpu.ops import fpset as fps
    from pulsar_tlaplus_tpu.store import sieve as store_sieve

    cap, K = 1 << 11, 3
    key = jax.random.PRNGKey(3)
    cols = []
    for _ in range(K):
        key, sub = jax.random.split(key)
        cols.append(jax.random.bits(sub, ((cap * 3) // 4,), jnp.uint32))
    tcols, _, _, _ = fps.flush_acc(
        fps.empty_cols(cap, K), tuple(cols),
        jnp.int32(cols[0].shape[0]),
        jnp.zeros((fps.FPM_N,), jnp.int32),
    )
    occ = fps.occupied_mask(tcols)
    gen = jnp.where(occ, (jnp.arange(cap, dtype=jnp.int32) % 5) + 1, 0)
    gen = jnp.concatenate([gen, jnp.zeros((1,), jnp.int32)])
    t_np = [np.asarray(c) for c in tcols]
    g_np = np.asarray(gen)
    sent = np.uint32(0xFFFFFFFF)
    live = ~np.all(np.stack(t_np) == sent, axis=0)
    live[cap] = False  # the trash row is never occupied
    for cutoff in (1, 3):
        holed, gen2, ev, n_ev = store_sieve.extract_cold(
            tcols, gen, cutoff
        )
        cold = live & (g_np >= 1) & (g_np <= cutoff)
        assert int(n_ev) == int(cold.sum()) > 0
        for got, c in zip(holed, t_np):
            assert np.array_equal(np.asarray(got), np.where(cold, sent, c))
        assert np.array_equal(np.asarray(gen2), np.where(cold, 0, g_np))
        order = np.lexsort([c[cold] for c in reversed(t_np)])
        for got, c in zip(ev, t_np):
            want = np.full((cap + 1,), sent)
            want[: int(n_ev)] = c[cold][order]
            assert np.array_equal(np.asarray(got), want)


# --------------------------- tiered-vs-untiered exactness (the hinge)


@pytest.mark.parametrize(
    "name",
    [
        "producer_on",
        # the second config exercises the same machinery at deeper
        # duplicate rates; slow-marked for the tier-1 time budget
        # (producer_on + the subscription spill-parity differential
        # keep two specs' worth of coverage in tier-1)
        pytest.param("two_crashes", marks=pytest.mark.slow),
    ],
)
def test_tiered_vs_untiered_state_for_state(name):
    """Same states in the same order under a budget that forces key
    eviction, row/log spill, and cold-miss resolution: level sizes,
    packed rows, and parent/lane logs bit-identical (rows/logs via
    the merged cold+device view)."""
    c = SMALL_CONFIGS[name]
    ck_u = _mk(c)
    r_u = ck_u.run()
    ck_t = _mk(c, hbm_budget=_tight_budget(c))
    r_t = ck_t.run()
    assert r_t.distinct_states == r_u.distinct_states
    assert r_t.level_sizes == r_u.level_sizes
    st = ck_t.last_stats
    assert st["spill_evictions"] >= 1, "budget never forced an eviction"
    assert st["spill_rows_evicted"] > 0
    assert st["spill_misses_resolved"] > 0
    nv = r_u.distinct_states
    pu = np.asarray(ck_u.last_bufs["parent"][:nv])
    lu = np.asarray(ck_u.last_bufs["lane"][:nv])
    pt, lt = _merged_logs(ck_t, nv)
    assert (pu == pt).all() and (lu == lt).all()
    ru = np.asarray(ck_u.last_bufs["rows"][: nv * ck_u.W])
    assert (_merged_rows(ck_t, nv) == ru).all()


# the untiered device engine's deterministic verdicts at these exact
# shapes (sub_batch 512, visited_cap 2^11) — re-derivable with
# _mk(pe.SHIPPED_CFG, invariants=(inv,), ...); pinned so the tiered
# oracle test pays 2 runs instead of 4 (the untiered side of this
# differential is already exercised by tests/test_fuse.py)
BUG_ORACLE_PINS = {
    "CompactedLedgerLeak": (23329, 12),
    "DuplicateNullKeyMessage": (3645, 4),
}


@pytest.mark.parametrize(
    "invariant", sorted(BUG_ORACLE_PINS),
)
def test_tiered_bug_oracles_identical(invariant):
    """Both published counterexamples through the tiered store: the
    violation gid, diameter, and state count equal the pinned
    untiered-engine verdicts, and the replayed trace (through the
    merged cold+device logs) validates step-by-step on the Python
    oracle semantics."""
    gid, depth = BUG_ORACLE_PINS[invariant]
    kw = dict(
        invariants=(invariant,), check_deadlock=True,
        sub_batch=512, visited_cap=1 << 11, frontier_cap=1 << 11,
    )
    ck_t = _mk(
        pe.SHIPPED_CFG, hbm_budget=_tight_budget(pe.SHIPPED_CFG, **kw),
        **kw,
    )
    r_t = ck_t.run()
    assert r_t.violation == invariant
    assert r_t.violation_gid == gid
    assert r_t.diameter == depth
    # (distinct_states at a violation stop is dispatch-pipeline-
    # dependent — the tiered group-ahead clamp stops sooner after the
    # find; gid/diameter/trace are the order-exactness pins)
    assert len(r_t.trace) == depth
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r_t.trace, r_t.trace_actions, invariant
    )


def test_tiered_shipped_45k_hot_under_quarter(tmp_path):
    """THE acceptance run: the 45,198-state compaction oracle with the
    hot tier pinned under 25% of the reachable set completes
    untruncated with the pinned count/diameter, a validator-clean v9
    stream, and monotone-cumulative spill records."""
    stream = str(tmp_path / "spill45k.jsonl")
    kw = dict(sub_batch=512, visited_cap=1 << 12, frontier_cap=1 << 12)
    ck = _mk(
        pe.SHIPPED_CFG,
        hbm_budget=_tight_budget(pe.SHIPPED_CFG, slack=65536, **kw),
        telemetry=stream, **kw,
    )
    r = ck.run()
    assert (r.distinct_states, r.diameter) == (45198, 20)
    assert not r.truncated and r.violation is None
    st = ck.last_stats
    assert st["spill_hot_keys"] / r.distinct_states < 0.25
    assert st["spill_keys_evicted"] > 0
    assert st["spill_bytes_comp"] < st["spill_bytes_raw"]
    assert st["spill_bytes_per_state"] > 0
    mod = _checker_mod()
    assert mod.validate_stream(stream) == []
    evs = [json.loads(x) for x in open(stream)]
    spills = [e for e in evs if e["event"] == "spill"]
    assert spills, "tiered run emitted no spill records"
    hdr = next(e for e in evs if e["event"] == "run_header")
    assert hdr["hbm_budget"] == ck.hbm_budget
    # the round trips and the columns they brought (PR 47): cumulative
    # in every spill record, the run's in the result's stats
    for k in ("fetches", "fetch_planes"):
        seen = [e[k] for e in spills]
        assert seen == sorted(seen) and seen[-1] == st[f"spill_{k}"] > 0
    res = next(e for e in evs if e["event"] == "result")
    assert res["stats"]["spill_fetches"] == st["spill_fetches"]
    assert res["stats"]["spill_fetch_planes"] == st["spill_fetch_planes"]
    assert st["spill_fetch_planes"] > 2 * st["spill_fetches"]


def test_spill_monotone_validator_negative(tmp_path):
    """A spill record whose cumulative bytes go BACKWARDS fails the
    v9 cross-check."""
    mod = _checker_mod()
    path = str(tmp_path / "bad.jsonl")
    base = dict(
        v=9, run_id="r1", tier="ram", keys_evicted=10,
        rows_evicted=0, transfer_s=0.1, misses_resolved=5,
        event="spill",
    )
    with open(path, "w") as f:
        f.write(json.dumps(dict(
            base, t=0.1, seq=0, bytes_raw=100, bytes_comp=50,
        )) + "\n")
        f.write(json.dumps(dict(
            base, t=0.2, seq=1, bytes_raw=90, bytes_comp=60,
        )) + "\n")
    errs = mod.validate_stream(path)
    assert any("bytes_raw went backwards" in e for e in errs)


@pytest.mark.parametrize("key", ["fetches", "fetch_planes"])
def test_spill_fetch_counters_are_cumulative_where_present(tmp_path, key):
    """``fetches`` / ``fetch_planes`` (PR 47) are optional in a spill
    record and cumulative where a record has them."""
    mod = _checker_mod()
    base = dict(
        v=9, run_id="r1", tier="ram", keys_evicted=10, rows_evicted=0,
        transfer_s=0.1, misses_resolved=5, bytes_raw=100, bytes_comp=50,
        event="spill",
    )
    for name, vals, clean in (
        ("old", (None, None), True), ("up", (3, 7), True),
        ("down", (7, 3), False),
    ):
        path = str(tmp_path / f"{name}.jsonl")
        with open(path, "w") as f:
            for seq, v in enumerate(vals):
                rec = dict(base, t=0.1 * (seq + 1), seq=seq)
                if v is not None:
                    rec[key] = v
                f.write(json.dumps(rec) + "\n")
        errs = mod.validate_stream(path)
        assert (errs == []) is clean, errs
        assert clean or any(f"spill.{key} went backwards" in e for e in errs)


# ------------------------------------------- survive + resume drills


def test_tiered_suspend_resume_through_manifest(tmp_path):
    """The daemon's suspend path: a cooperative mid-run suspend writes
    a frame embedding the spill manifest; a fresh checker resumes
    through it to the identical result (the scheduler's exact
    mechanism — suspend_hook + run(resume=True))."""
    c = SMALL_CONFIGS["producer_on"]
    ck_ref = _mk(c)
    r_ref = ck_ref.run()
    frame = str(tmp_path / "job.npz")
    budget = _tight_budget(c)
    polls = {"n": 0}

    def hook():
        polls["n"] += 1
        return "suspended" if polls["n"] >= 4 else None

    ck1 = _mk(
        c, hbm_budget=budget, checkpoint_path=frame,
        checkpoint_every=2, suspend_hook=hook,
    )
    r1 = ck1.run()
    assert r1.truncated and r1.stop_reason == "suspended"
    assert r1.distinct_states < r_ref.distinct_states
    assert os.path.exists(frame)
    # the suspended frame references durable spill files
    ck2 = _mk(
        c, hbm_budget=budget, checkpoint_path=frame,
        checkpoint_every=2,
    )
    r2 = ck2.run(resume=True)
    assert r2.distinct_states == r_ref.distinct_states
    assert r2.level_sizes == r_ref.level_sizes
    assert not r2.truncated
    nv = r_ref.distinct_states
    pu = np.asarray(ck_ref.last_bufs["parent"][:nv])
    pt, _lt = _merged_logs(ck2, nv)
    assert (pu == pt).all()


@pytest.mark.slow
def test_tiered_kill_drill_resumes_to_pinned_result(tmp_path):
    """kill@level mid-way through the tiered 45,198 run (hard exit
    137, only frames + spill files survive), then resume through the
    spill manifest to the exact pinned result — the crash half of the
    acceptance criteria, as a real subprocess.  Slow-marked (the r10/
    r14 precedent for subprocess differentials): the in-process
    suspend/resume test above drills the same manifest-restore path
    in tier-1."""
    frame = str(tmp_path / "drill.npz")
    stream = str(tmp_path / "drill.jsonl")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PTT_FAULT="kill@level:12"
    )
    args = [
        sys.executable, "-m", "tests._survivable_run",
        "--engine", "device", "--checkpoint", frame,
        "--telemetry", stream, "--every", "3",
        "--sub-batch", "512", "--visited-cap", "4096",
        "--hbm-budget", "min+65536",
    ]
    p1 = subprocess.run(
        args, env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=560,
    )
    assert p1.returncode == 137, (p1.returncode, p1.stderr[-800:])
    assert os.path.exists(frame)
    spill_dir = f"{frame}.spill"
    assert os.listdir(spill_dir), "no durable spill files at the kill"
    env2 = dict(os.environ, JAX_PLATFORMS="cpu")
    p2 = subprocess.run(
        args + ["--resume"], env=env2, cwd=ROOT,
        capture_output=True, text=True, timeout=560,
    )
    assert p2.returncode == 0, p2.stderr[-800:]
    out = json.loads(p2.stdout.strip().splitlines()[-1])
    assert out["distinct_states"] == 45198
    assert out["diameter"] == 20
    assert not out["truncated"]
    # the crashed + resumed streams both validate at v9
    mod = _checker_mod()
    assert mod.validate_stream(stream) == []


# the untiered liveness verdict at these exact knobs (re-derivable
# by dropping hbm_budget below): the published consumer_on lasso
LASSO_PREFIX = [0, 1, 6, 30, 86, 162, 270, 394, 522, 678, 834, 995, 1187]


def test_tiered_liveness_lasso_verdict_from_cold_rows():
    """The consumer_on lasso oracle through a tiered inner explorer:
    the sweep streams the aged rows back from the cold tiers and
    reaches the SAME verdict (lasso included) as the pinned untiered
    run — retiring the sweep's rows_window='all' HBM requirement."""
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    cc = dataclasses.replace(
        SMALL_CONFIGS["producer_on"], model_consumer=True
    )
    budget = _tight_budget(
        cc, sub_batch=256, visited_cap=1 << 9, frontier_cap=1 << 9,
    )
    lt = LivenessChecker(
        CompactionModel(cc), hbm_budget=budget, goal="Termination",
        fairness="wf_next", frontier_chunk=256, visited_cap=1 << 9,
        sweep_chunk=1 << 10,
    )
    r_t = lt.run()
    assert not r_t.holds  # the published lasso oracle
    assert "no var-changing successor" in r_t.reason
    assert r_t.distinct_states == 1654
    assert r_t.lasso_prefix == LASSO_PREFIX
    assert r_t.lasso_cycle == [1187]
    inner = lt._checker
    assert inner.last_stats.get("spill_rows_evicted", 0) > 0, (
        "the inner explorer never spilled — the sweep read nothing "
        "from the cold tier"
    )


# ------------------------------------------------------- ledger ties


def test_ledger_gate_spill_keys_pinned_baseline(tmp_path):
    """The spill tier-1 gate: a fresh tiered producer_on run gates
    clean against the committed spill baseline on the deterministic
    keys + spill_bytes_per_state; an injected spill-bytes regression
    fails."""
    from pulsar_tlaplus_tpu import cli

    path = str(tmp_path / "spill_ledger.jsonl")
    shutil.copy(SPILL_PINNED, path)
    assert ledger.validate_ledger(path) == []
    stream = str(tmp_path / "run.jsonl")
    c = SMALL_CONFIGS["producer_on"]
    _mk(c, hbm_budget=_tight_budget(c), telemetry=stream).run()
    assert cli.main(["ledger", "--ledger", path, "add", stream]) == 0
    keys = [
        "dispatches_per_level", "work_units_per_state",
        "spill_bytes_per_state",
    ]
    rc = cli.main(
        ["ledger", "--ledger", path, "gate", "--threshold", "0.1",
         "--keys"] + keys
    )
    assert rc == 0
    cur = ledger.load(path)[-1]
    bad = dict(cur, values=dict(cur["values"]))
    bad["values"]["spill_bytes_per_state"] = (
        cur["values"]["spill_bytes_per_state"] * 2
    )
    bad["digest"] = ledger._digest(bad["values"])
    ledger.append(path, [bad])
    rc = cli.main(
        ["ledger", "--ledger", path, "gate", "--threshold", "0.1",
         "--keys"] + keys
    )
    assert rc == 1
    v = ledger.gate(cur, bad, threshold=0.1, keys=tuple(keys))
    assert {x["key"] for x in v} == {"spill_bytes_per_state"}
