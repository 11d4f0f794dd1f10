"""Dense-tile kernel layer tests (round 23, ``ops/tiles.py``).

The acceptance bar (ISSUE 20):

- kernel-level parity properties: the tiled/Pallas probe, key-plane,
  and sieve formulations bit-identical to the legacy ops on
  randomized shapes — ragged (non-tile-multiple) lane counts, dup-
  heavy batches, SENTINEL lanes, partial ``n_acc``, growth-boundary
  load factors;
- engine state-for-state differentials: identical level sizes, rows,
  parent/lane logs on producer_on under EVERY ``*_impl`` setting,
  with the r14 work-counter totals key-for-key equal and the r13
  fused dispatch economy unchanged;
- both published bug oracles replay identically (violation gid +
  full trace) through the tile kernels;
- knob plumbing: ctor validation, tuned-profile resolution with
  explicit-wins, profile validator enum, search-space membership,
  predict pricing, v16 headers, bench_schema-12 artifacts;
- the tiles ledger gate: a tile-impl run gates CLEAN against the
  committed legacy-comparable mini baseline on the deterministic
  economy keys (the impls share one comparability class by design),
  and a tampered baseline fails loudly.
"""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import ledger
from pulsar_tlaplus_tpu.ops import fpset, tiles
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL, KeySpec
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.store import sieve as store_sieve
from pulsar_tlaplus_tpu.tune import predict, profiles
from pulsar_tlaplus_tpu.tune import space as tune_space
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TILES_PINNED = os.path.join(
    ROOT, "tests", "data", "mini_bench_tiles_producer_on.jsonl"
)


def _checker_mod():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(ROOT, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(c, sub_batch=256, **kw):
    kw.setdefault("visited_cap", 1 << 12)
    kw.setdefault("frontier_cap", 1 << 12)
    return DeviceChecker(
        CompactionModel(c), invariants=kw.pop("invariants", ()),
        sub_batch=sub_batch, **kw,
    )


def _rand_cols(key, n, K):
    cols = []
    for _ in range(K):
        key, sub = random.split(key)
        cols.append(random.bits(sub, (n,), jnp.uint32))
    return key, tuple(cols)


# ---- kernel parity: probe ------------------------------------------


# (cap_log2, nq, dup_frac, n_acc_frac, fill_frac) — ragged lane
# counts that are NOT chunk multiples, dup-heavy batches, stale tails,
# and a growth-boundary load factor; fill_frac keeps the post-flush
# load under the engine's growth threshold (the engine rehashes BEFORE
# a flush could overload the table, so an overloaded flush is outside
# the parity contract — probe-failure resolution under impossible
# load is schedule-dependent in every impl)
PROBE_SHAPES = [
    (12, 1000, 0.0, 1.0, 0.375),
    (12, 1024, 0.6, 1.0, 0.5),
    (11, 777, 0.5, 0.61, 0.375),
    (11, 2048, 0.9, 0.83, 0.25),
    (13, 3000, 0.3, 1.0, 0.375),
]


@pytest.mark.parametrize("impl", ["tile"])
@pytest.mark.parametrize(
    "cap_log2,nq,dup_frac,n_acc_frac,fill_frac", PROBE_SHAPES
)
def test_flush_probe_parity(
    impl, cap_log2, nq, dup_frac, n_acc_frac, fill_frac
):
    """flush_acc under the tile probe: bit-identical ``is_new``/``n_new``
    and the same resulting table KEY SET as legacy (slot placement may
    differ — the tiled insert probes in chunks — but membership, the
    only observable the engine reads, may not)."""
    cap = 1 << cap_log2
    K = 2
    key = random.PRNGKey(cap_log2 * 1000 + nq)
    key, fill_cols = _rand_cols(key, int(cap * fill_frac), K)
    tcols = fpset.empty_cols(cap, K)
    fpm = jnp.zeros((fpset.FPM_N,), jnp.int32)
    tcols, _, _, _ = fpset.flush_acc(
        tcols, fill_cols, jnp.int32(fill_cols[0].shape[0]), fpm
    )
    ndup = int(nq * dup_frac)
    key, fresh = _rand_cols(key, nq - ndup, K)
    dup_ix = jnp.arange(ndup) % fill_cols[0].shape[0]
    kcols = tuple(
        jnp.concatenate([f[dup_ix], g])
        for f, g in zip(fill_cols, fresh)
    )
    # a few SENTINEL (masked-expand) lanes sprinkled in
    sent = jnp.arange(nq) % 97 == 3
    kcols = tuple(jnp.where(sent, SENTINEL, c) for c in kcols)
    n_acc = jnp.int32(int(nq * n_acc_frac))
    t_l, n_l, f_l, m_l = fpset.flush_acc(tcols, kcols, n_acc, fpm)
    t_i, n_i, f_i, m_i = fpset.flush_acc(
        tcols, kcols, n_acc, fpm, probe_impl=impl
    )
    assert int(n_l) == int(n_i)
    assert np.array_equal(np.asarray(f_l), np.asarray(f_i))
    # same key multiset in both tables (sorted column compare);
    # slot `cap` is the write-only trash row — parked/duplicate lanes
    # scatter into it, so its residue is last-writer scheduling noise
    # in EVERY impl and is never read back
    def keyset(tc):
        cols = tuple(np.asarray(c)[:cap] for c in tc)
        order = np.lexsort(cols)
        return tuple(c[order] for c in cols)

    for a, b in zip(keyset(t_l), keyset(t_i)):
        assert np.array_equal(a, b)
    # the duplicate/valid accounting rides the same metrics vector
    # (probe-round totals legitimately differ per impl — the schedule
    # is reformulated — but failure count and presented lanes may not)
    assert int(m_l[2]) == int(m_i[2])  # n_failed accumulator


@pytest.mark.parametrize("impl", ["tile"])
def test_flush_probe_within_batch_duplicates(impl):
    """Lanes presenting the SAME new key in one batch: exactly one
    winner, and it is the minimum lane id (the discovery-order
    invariant every engine path leans on)."""
    cap, K, nq = 1 << 10, 2, 512
    tcols = fpset.empty_cols(cap, K)
    fpm = jnp.zeros((fpset.FPM_N,), jnp.int32)
    key, cols = _rand_cols(random.PRNGKey(7), nq, K)
    # force groups of 4 consecutive lanes to share a key
    kcols = tuple(c[::4].repeat(4)[:nq] for c in cols)
    _, n_l, f_l, _ = fpset.flush_acc(tcols, kcols, jnp.int32(nq), fpm)
    _, n_i, f_i, _ = fpset.flush_acc(
        tcols, kcols, jnp.int32(nq), fpm, probe_impl=impl
    )
    assert int(n_l) == int(n_i)
    assert np.array_equal(np.asarray(f_l), np.asarray(f_i))
    w = np.flatnonzero(np.asarray(f_i))
    assert (w % 4 == 0).all()  # min-lane wins every group


# ---- kernel parity: expand key plane --------------------------------


@pytest.mark.parametrize("impl", ["tile", "pallas"])
@pytest.mark.parametrize(
    "total_bits,W,fp_bits",
    [(60, 2, None), (90, 3, None), (160, 5, 64), (160, 5, 96)],
)
def test_key_plane_parity(impl, total_bits, W, fp_bits):
    """key_plane vs KeySpec.make + SENTINEL masking: bit-identical on
    exact and hashed layouts, ragged row counts included."""
    ks = KeySpec(total_bits, W, fp_bits)
    for nc in (257, 4096, 5000):
        key = random.PRNGKey(nc)
        packedf = random.bits(key, (nc, W), jnp.uint32)
        vflat = (jnp.arange(nc) % 11) != 5
        want = tuple(
            jnp.where(vflat, c, SENTINEL) for c in ks.make(packedf)
        )
        got = tiles.key_plane(ks, packedf, vflat, impl=impl)
        assert len(want) == len(got) == ks.ncols
        for a, b in zip(want, got):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- kernel parity: sieve ------------------------------------------


@pytest.mark.parametrize("impl", ["tile", "pallas"])
def test_extract_cold_parity(impl):
    """extract_cold under tile/pallas: array-identical holed table,
    cleared generations, sorted eviction run, and count."""
    cap, K = 1 << 11, 3
    key, cols = _rand_cols(random.PRNGKey(3), (cap * 3) // 4, K)
    tcols = fpset.empty_cols(cap, K)
    fpm = jnp.zeros((fpset.FPM_N,), jnp.int32)
    tcols, _, _, _ = fpset.flush_acc(
        tcols, cols, jnp.int32(cols[0].shape[0]), fpm
    )
    occ = fpset.occupied_mask(tcols)
    gen = jnp.where(occ, (jnp.arange(cap, dtype=jnp.int32) % 5) + 1, 0)
    gen = jnp.concatenate([gen, jnp.zeros((1,), jnp.int32)])
    for cutoff in (1, 3):
        legacy = store_sieve.extract_cold(tcols, gen, cutoff)
        tiled = store_sieve.extract_cold(
            tcols, gen, cutoff, sieve_impl=impl
        )
        for a, b in zip(legacy[0], tiled[0]):  # holed planes
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(
            np.asarray(legacy[1]), np.asarray(tiled[1])
        )
        for a, b in zip(legacy[2], tiled[2]):  # sorted eviction run
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert int(legacy[3]) == int(tiled[3])


# ---- engine state-for-state differentials ---------------------------


# work-counter keys the impls may NOT move (ACAP-presented lanes per
# flush are schedule-invariant); fpset_probe_rounds is deliberately
# NOT here — the tiled schedule legitimately reports different round
# totals (docs/kernels.md)
def _work(ck):
    return {
        k: v for k, v in ck.last_stats.items() if k.startswith("work_")
    }


IMPL_COMBOS = [
    dict(probe_impl="tile"),
    dict(expand_impl="tile"),
    dict(probe_impl="tile", expand_impl="tile"),
    dict(probe_impl="tile", expand_impl="pallas"),
]


def test_engine_state_for_state_under_every_impl():
    """producer_on under every impl combo: identical level sizes,
    packed rows, parent/lane logs, work-counter totals, and the r13
    fused dispatch economy."""
    c = SMALL_CONFIGS["producer_on"]
    ck0 = _mk(c)
    r0 = ck0.run()
    nv, W = r0.distinct_states, ck0.W
    rows0 = np.asarray(ck0.last_bufs["rows"][: nv * W])
    p0 = np.asarray(ck0.last_bufs["parent"][:nv])
    l0 = np.asarray(ck0.last_bufs["lane"][:nv])
    wk0 = _work(ck0)
    disp0 = ck0.last_stats["dispatches_per_level"]
    for kw in IMPL_COMBOS:
        ck = _mk(c, **kw)
        r = ck.run()
        assert r.distinct_states == nv, kw
        assert r.level_sizes == r0.level_sizes, kw
        assert np.array_equal(
            np.asarray(ck.last_bufs["rows"][: nv * W]), rows0
        ), kw
        assert np.array_equal(
            np.asarray(ck.last_bufs["parent"][:nv]), p0
        ), kw
        assert np.array_equal(
            np.asarray(ck.last_bufs["lane"][:nv]), l0
        ), kw
        assert _work(ck) == wk0, kw
        assert ck.last_stats["dispatches_per_level"] == disp0, kw


def test_tiered_sieve_impl_state_for_state():
    """A budgeted producer_on run with the tiled cold-extract: same
    discovery and the same spill economy as the legacy sieve."""
    from tests.helpers import tight_hbm_budget

    c = SMALL_CONFIGS["producer_on"]
    # test_store's spill shape: caps well under the 1654-state
    # reachable set so the pinned-tier budget MUST evict
    kw = dict(
        sub_batch=64, visited_cap=1 << 9, frontier_cap=1 << 9,
        check_deadlock=False,
    )
    budget = tight_hbm_budget(lambda b: _mk(c, hbm_budget=b, **kw))
    ck_l = _mk(c, hbm_budget=budget, **kw)
    r_l = ck_l.run()
    assert ck_l.last_stats["spill_evictions"] >= 1
    for impl in ("tile", "pallas"):
        ck_t = _mk(c, hbm_budget=budget, sieve_impl=impl, **kw)
        r_t = ck_t.run()
        assert r_t.distinct_states == r_l.distinct_states, impl
        assert r_t.level_sizes == r_l.level_sizes, impl
        for k in (
            "spill_evictions", "spill_keys_evicted",
            "spill_rows_evicted", "spill_misses_resolved",
        ):
            assert ck_t.last_stats[k] == ck_l.last_stats[k], (impl, k)


# the untiered device engine's deterministic verdicts at these exact
# shapes (sub_batch 512, visited_cap 2^11) — the same pins
# tests/test_store.py replays the tiered store against
BUG_ORACLE_PINS = {
    "CompactedLedgerLeak": (23329, 12),
    "DuplicateNullKeyMessage": (3645, 4),
}


@pytest.mark.parametrize("invariant", sorted(BUG_ORACLE_PINS))
def test_bug_oracles_identical_under_tile_impls(invariant):
    """Both published counterexamples through the tile kernels: the
    pinned violation gid + diameter, and a replayed trace the oracle
    validates step by step."""
    gid, depth = BUG_ORACLE_PINS[invariant]
    ck = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), invariants=(invariant,),
        sub_batch=512, visited_cap=1 << 11, frontier_cap=1 << 11,
        probe_impl="tile", expand_impl="tile",
    )
    r = ck.run()
    assert r.violation == invariant
    assert r.violation_gid == gid
    assert r.diameter == depth
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, invariant
    )


# ---- knob plumbing --------------------------------------------------


def test_ctor_validates_impls():
    c = SMALL_CONFIGS["producer_on"]
    for knob in ("probe_impl", "expand_impl", "sieve_impl"):
        with pytest.raises(ValueError, match=knob):
            _mk(c, **{knob: "warp"})
    # the probe's gather does not lower through Mosaic: no such value
    with pytest.raises(ValueError, match="probe_impl"):
        _mk(c, probe_impl="pallas")


def test_impls_resolve_from_profile_with_explicit_wins(tmp_path):
    """A tuned profile's impl knobs land on the engine; an explicit
    ctor value still wins; prewarm compiles the TUNED programs (zero
    post-run compiles)."""
    os.environ["PTT_TUNE_DIR"] = str(tmp_path)
    try:
        c = SMALL_CONFIGS["producer_on"]
        m = CompactionModel(c)
        sig = profiles.profile_key(
            model=m, invariants=(), engine="device_bfs"
        )
        profiles.save(
            profiles.build(
                sig=sig, engine="device_bfs", backend="cpu",
                knobs={"probe_impl": "tile", "expand_impl": "tile"},
                spec="compaction",
            )
        )
        ck = _mk(c, profile="auto")
        assert ck.profile_sig == sig
        assert ck.probe_impl == "tile"
        assert ck.expand_impl == "tile"
        assert ck.sieve_impl == "legacy"
        # explicit ctor value beats the profile
        ck2 = _mk(c, profile="auto", probe_impl="legacy")
        assert ck2.probe_impl == "legacy"
        assert ck2.expand_impl == "tile"
        # prewarm covers the tuned impl programs: zero new jit keys
        # after a real run (tiers=True = every reachable capacity
        # tier, the r10 contract)
        ck.warmup(tiers=True)
        keys = set(ck._jits)
        ck.run()
        assert set(ck._jits) == keys
    finally:
        del os.environ["PTT_TUNE_DIR"]


def test_profile_validator_rejects_bad_impl(tmp_path):
    p = tmp_path / "prof.json"
    prof = profiles.build(
        sig="cafecafecafecafe", engine="device_bfs", backend="cpu",
        knobs={"probe_impl": "warp"}, spec="compaction",
    )
    p.write_text(json.dumps(prof))
    errs = profiles.validate(prof, str(p))
    assert any("probe_impl" in e for e in errs)
    bad = dict(prof, knobs={"probe_impl": "pallas"})
    assert any("probe_impl" in e for e in profiles.validate(bad, str(p)))
    ok = dict(prof, knobs={"probe_impl": "tile", "expand_impl": "pallas"})
    assert not [
        e for e in profiles.validate(ok, str(p)) if "_impl" in e
    ]


def test_impls_in_search_space():
    """probe/expand are searched in the device space; sieve rides the
    budgeted (spill) product only; all three are PROFILE_KNOBS."""
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    cands = tune_space.candidates(m, limit=None)
    assert any(c.get("probe_impl") == "tile" for c in cands)
    assert any(c.get("expand_impl") == "pallas" for c in cands)
    assert not any("sieve_impl" in c for c in cands)
    spill = tune_space.candidates(m, spill=True, limit=None)
    assert any(c.get("sieve_impl") == "tile" for c in spill)
    for k in ("probe_impl", "expand_impl", "sieve_impl"):
        assert k in tune_space.PROFILE_KNOBS["device_bfs"]


def test_predict_prices_impls():
    """The cost model separates the impls: on the CPU ratio table the
    tile probe is priced above legacy (the measured r23 prefilter
    overhead) and the tile expand below; a calibrated per-impl unit
    overrides the table."""
    ref = {
        "backend": "cpu",
        "work": {"probe_lanes": 10_000_000, "expand_rows": 1_000_000},
        "level_sizes": [10, 100, 1000],
        "avg_probe_rounds": 2.0,
        "probe_impl": "legacy", "expand_impl": "legacy",
    }
    base = predict.predict_candidate({}, ref)["est_s"]
    tile_p = predict.predict_candidate({"probe_impl": "tile"}, ref)
    tile_e = predict.predict_candidate({"expand_impl": "tile"}, ref)
    assert tile_p["est_s"] > base
    assert tile_e["est_s"] < base
    cal = {
        "units": {
            "probe_lane_ns": 100.0, "expand_row_ns": 10.0,
            "probe_lane_tile_ns": 50.0,
        },
        "rtt_s": 2e-4,
    }
    fast = predict.predict_candidate({"probe_impl": "tile"}, ref, cal)
    slow = predict.predict_candidate({}, ref, cal)
    assert fast["est_s"] < slow["est_s"]


def test_reference_of_carries_impls():
    c = SMALL_CONFIGS["producer_on"]
    ck = _mk(c, probe_impl="tile")
    r = ck.run()
    ref = predict.reference_of(ck, r)
    assert ref["probe_impl"] == "tile"
    assert ref["expand_impl"] == "legacy"
    assert ref["sieve_impl"] == "legacy"


# ---- telemetry v16 + bench_schema 12 --------------------------------


def test_run_header_carries_impls(tmp_path):
    stream = str(tmp_path / "s.jsonl")
    _mk(
        SMALL_CONFIGS["producer_on"], telemetry=stream,
        probe_impl="tile", sieve_impl="tile",
    ).run()
    ckr = _checker_mod()
    assert ckr.validate_stream(stream) == []
    with open(stream) as f:
        hd = next(
            json.loads(ln) for ln in f
            if json.loads(ln).get("event") == "run_header"
        )
    assert hd["v"] == 16
    assert hd["probe_impl"] == "tile"
    assert hd["expand_impl"] == "legacy"
    assert hd["sieve_impl"] == "tile"


def test_bench_schema_v12_keys():
    """bench_schema 12 artifacts must carry the impl keys +
    probe_lanes_per_sec; a v12 artifact missing them fails; a v11
    artifact without them stays clean (additive versioning)."""
    ckr = _checker_mod()
    base = {k: 1 for k in ckr.BENCH_KEYS_V12}
    base.update(bench_schema=12, value=1.0)
    assert ckr.validate_bench_artifact(dict(base), "good") == []
    bad = dict(base)
    del bad["probe_impl"], bad["probe_lanes_per_sec"]
    errs = ckr.validate_bench_artifact(bad, "bad")
    assert any("probe_impl" in e for e in errs)
    assert any("probe_lanes_per_sec" in e for e in errs)
    v11 = {k: 1 for k in ckr.BENCH_KEYS_V11}
    v11.update(bench_schema=11, value=1.0)
    assert ckr.validate_bench_artifact(v11, "v11") == []


# ---- the tiles ledger gate ------------------------------------------


def test_tiles_ledger_gate_against_committed_baseline(tmp_path):
    """THE r23 gate: a fresh tile-impl producer_on run shares the
    legacy runs' comparability class (impls are NOT in the config
    key) and gates clean against the committed tile mini baseline on
    the deterministic economy keys; a tampered (better-than-
    reality) baseline fails loudly — wall-clock never enters."""
    baseline = ledger.load(TILES_PINNED)[-1]
    assert ledger.validate_ledger(TILES_PINNED) == []
    assert "visited=fpset|compact=logshift|fuse=level" in baseline["key"]
    stream = str(tmp_path / "run.jsonl")
    _mk(
        SMALL_CONFIGS["producer_on"], telemetry=stream,
        probe_impl="tile", expand_impl="tile",
    ).run()
    cur = ledger.record_from_file(stream)
    assert cur["key"] == baseline["key"]  # same comparability class
    assert (
        ledger.gate(
            baseline, cur, threshold=0.1, keys=ledger.TILES_GATE_KEYS
        )
        == []
    )
    # negative: shrink the baseline's economy so the identical fresh
    # run reads as a regression — deterministic, no timing flake
    tampered = dict(baseline, values=dict(baseline["values"]))
    for k in ledger.TILES_GATE_KEYS:
        tampered["values"][k] = tampered["values"][k] / 2
    tampered["digest"] = ledger._digest(tampered["values"])
    violations = ledger.gate(
        tampered, cur, threshold=0.1, keys=ledger.TILES_GATE_KEYS
    )
    assert {v["key"] for v in violations} == set(ledger.TILES_GATE_KEYS)


def test_tiles_record_derives_probe_lanes_per_sec(tmp_path):
    """Stream-ingested records derive the r23 throughput signal from
    the work counters + wall clock."""
    stream = str(tmp_path / "run.jsonl")
    _mk(
        SMALL_CONFIGS["producer_on"], telemetry=stream,
        probe_impl="tile",
    ).run()
    rec = ledger.record_from_file(stream)
    v = rec["values"]
    assert v["probe_lanes_per_sec"] == round(
        v["work_probe_lanes"] / v["wall_s"], 1
    )
    assert v["probe_impl"] == "tile"
