"""The sort-free log-shift stream compaction (`ops/compact.py`) —
kernel properties against a numpy reference, the TPU materialisation
held to the CPU's through the engine, the fused+grouped liveness sweep
parity, the capacity-tier
prewarm (zero post-run() compiles), and the fpset probe-schedule
exposure."""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ops import compact, fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS

CONSUMER_CFG = dataclasses.replace(
    SMALL_CONFIGS["producer_on"], model_consumer=True
)


# ---- kernel properties ----------------------------------------------


def _ref_compact(drop, cols):
    kept = np.nonzero(drop == 0)[0]
    return [c[kept] for c in cols], kept


def _materialize(mat, monkeypatch):
    """``compact_by_flag``'s keywords for ``mat``: the process's two
    through the environment, as a process picks them; ``roll`` (the
    shift passes as one loop, the rehash's) only as an argument."""
    if mat == "roll":
        return {"materialize": "roll"}
    monkeypatch.setenv("PTT_COMPACT_MATERIALIZE", mat)
    return {}


@pytest.mark.parametrize("mat", ["shift", "gather", "roll"])
def test_logshift_matches_sort_random_masks_and_widths(
    mat, monkeypatch
):
    """Random masks, drop rates, lengths (incl. non-powers-of-two) and
    column counts, under every materialization (the TPU doubling-shift
    passes, unrolled and as one loop, and the CPU prefix+gather): the
    kept prefix must equal the numpy reference element-for-element,
    idx included."""
    kw = _materialize(mat, monkeypatch)
    rng = np.random.default_rng(0)
    for trial in range(10):
        n = int(rng.integers(1, 200))
        p = rng.uniform(0, 1)
        drop = (rng.random(n) < p).astype(np.uint32)
        ncols = int(rng.integers(1, 4))
        cols = [
            rng.integers(0, 2**32, size=n, dtype=np.uint32)
            for _ in range(ncols)
        ]
        jcols = tuple(jnp.asarray(c) for c in cols)
        out, idx = compact.compact_by_flag(jnp.asarray(drop), jcols, **kw)
        ref_cols, kept = _ref_compact(drop, cols)
        k = len(kept)
        for got, want in zip(out, ref_cols):
            assert np.array_equal(np.asarray(got)[:k], want), trial
        assert np.array_equal(np.asarray(idx)[:k], kept), trial
        # need_idx=False skips the index column, not the values
        out2, idx2 = compact.compact_by_flag(
            jnp.asarray(drop), jcols, need_idx=False, **kw
        )
        assert idx2 is None
        for got, want in zip(out2, ref_cols):
            assert np.array_equal(np.asarray(got)[:k], want), trial


@pytest.mark.parametrize("mat", ["shift", "gather", "roll"])
@pytest.mark.parametrize("n", [1, 2, 129])
@pytest.mark.parametrize("all_drop", [False, True])
def test_logshift_all_keep_all_drop_edges(n, all_drop, mat, monkeypatch):
    kw = _materialize(mat, monkeypatch)
    drop = np.full(n, 1 if all_drop else 0, np.uint32)
    c = np.arange(n, dtype=np.uint32) * 3
    out, idx = compact.compact_by_flag(
        jnp.asarray(drop), (jnp.asarray(c),), **kw
    )
    k = 0 if all_drop else n
    assert np.array_equal(np.asarray(out[0])[:k], c[:k])
    assert np.array_equal(np.asarray(idx)[:k], np.arange(k))


def test_device_engine_shift_materialization_state_for_state(
    monkeypatch,
):
    """The TPU materialization (doubling shifts) forced end-to-end
    through the device engine on the CPU backend: identical rows and
    logs to the gather materialization the CPU picks."""
    c = SMALL_CONFIGS["producer_on"]
    logs = {}
    for mat in ("shift", "gather"):
        monkeypatch.setenv("PTT_COMPACT_MATERIALIZE", mat)
        ck = DeviceChecker(
            CompactionModel(c), invariants=(), sub_batch=64,
            visited_cap=1 << 8, frontier_cap=1 << 8, group=2,
        )
        r = ck.run()
        n = r.distinct_states
        logs[mat] = (
            n,
            np.asarray(ck.last_bufs["rows"][: n * ck.W]).copy(),
            np.asarray(ck.last_bufs["parent"][:n]).copy(),
            np.asarray(ck.last_bufs["lane"][:n]).copy(),
        )
    assert logs["shift"][0] == logs["gather"][0]
    for a, b in zip(logs["shift"][1:], logs["gather"][1:]):
        assert np.array_equal(a, b)


def test_materialization_env_validation(monkeypatch):
    monkeypatch.setenv("PTT_COMPACT_MATERIALIZE", "bogus")
    with pytest.raises(ValueError, match="shift|gather"):
        compact.compact_by_flag(
            jnp.zeros((4,), jnp.uint32),
            (jnp.arange(4, dtype=jnp.uint32),),
        )


def test_liveness_fused_sweep_parity_consumer_oracle():
    """The grouped sweep (G chunks per dispatch) must produce the same
    wf_next verdict, edge count, and out-degrees as the per-chunk
    pipeline on the consumer_on lasso oracle."""
    want_holds, _ = pe.check_eventually(CONSUMER_CFG, "wf_next")
    base = None
    for kw in (
        dict(sweep_group=1),
        dict(sweep_group=3),
        dict(sweep_group=2),
    ):
        lck = LivenessChecker(
            CompactionModel(CONSUMER_CFG), fairness="wf_next",
            frontier_chunk=256, sweep_chunk=256, visited_cap=1 << 13,
            **kw,
        )
        r = lck.run()
        assert r.holds == want_holds is False
        assert r.lasso_cycle
        src, dst, out_deg = lck._edge_cache
        sig = (
            len(src),
            int(out_deg.sum()),
            hash(tuple(np.sort(src * 10_000_000 + dst).tolist())),
        )
        if base is None:
            base = sig
        else:
            assert sig == base, kw


def test_liveness_group_exceeding_chunks_is_safe():
    """A sweep_group larger than the chunk count: overrun windows are
    masked dead and the verdict is unchanged."""
    want_holds, _ = pe.check_eventually(CONSUMER_CFG, "wf_next")
    r = LivenessChecker(
        CompactionModel(CONSUMER_CFG), fairness="wf_next",
        frontier_chunk=256, sweep_chunk=256, visited_cap=1 << 13,
        sweep_group=64,
    ).run()
    assert r.holds == want_holds


# ---- capacity-tier prewarm (VERDICT r5 #8) --------------------------


def test_prewarm_compiles_every_tier_before_run():
    """warmup(tiers=True) walks the growth schedule: a run that
    crosses capacity tiers must add ZERO new jitted programs after
    run() starts (the 317 s mid-window lazy compile, retired)."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    ck = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=64,
        visited_cap=1 << 6, frontier_cap=1 << 6, group=2,
        max_states=1 << 12,
    )
    v0 = ck.VCAP
    ck.warmup(seed=False, tiers=True)
    keys_before = set(ck._jits)
    r = ck.run()
    assert set(ck._jits) == keys_before  # zero post-run() compiles
    assert ck.VCAP > v0  # the run genuinely crossed visited tiers
    assert r.distinct_states == want.distinct_states
    assert r.diameter == want.diameter
    # control: a tiers=False warmup compiles strictly fewer programs —
    # the crossing run above genuinely needed the prewarmed tier keys
    ck2 = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=64,
        visited_cap=1 << 6, frontier_cap=1 << 6, group=2,
        max_states=1 << 12,
    )
    ck2.warmup(seed=False, tiers=False)
    assert set(ck2._jits) < keys_before


def test_sharded_prewarm_compiles_every_tier_before_run():
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    ck = ShardedDeviceChecker(
        CompactionModel(c), n_devices=2, invariants=(), sub_batch=64,
        visited_cap=1 << 6, group=2, max_states=1 << 12,
    )
    ck.warmup(tiers=True)
    keys_before = set(ck._jits)
    r = ck.run()
    assert set(ck._jits) == keys_before
    assert r.distinct_states == want.distinct_states


# ---- fpset probe-schedule exposure ----------------------------------


def test_fpset_custom_schedule_is_exact():
    """A non-default probe schedule changes cost, never semantics:
    same winners as the defaults on an adversarial duplicate batch."""
    rng = np.random.default_rng(11)
    pool = rng.integers(0, 2**31, size=(37, 2), dtype=np.uint32)
    keys = pool[rng.integers(0, len(pool), size=512)]
    kcols = (keys[:, 0], keys[:, 1])
    s_default = fpset.FPSet(2, cap=1 << 10)
    s_tuned = fpset.FPSet(
        2, cap=1 << 10, dense_rounds=2, stages=((2, 12), (8, 64)),
    )
    got_d = np.asarray(s_default.insert(kcols))
    got_t = np.asarray(s_tuned.insert(kcols))
    assert np.array_equal(got_d, got_t)
    assert s_default.n == s_tuned.n == len(pool)


# ---- compact telemetry fields ---------------------------------------


def test_compact_telemetry_events_and_validator(tmp_path):
    """The device engine emits per-fetch ``compact`` records tagged
    with the impl, the run header carries ``compact_impl``, and the
    stream passes the schema validator (v3)."""
    import json
    import sys

    stream = str(tmp_path / "c.jsonl")
    c = SMALL_CONFIGS["producer_on"]
    ck = DeviceChecker(
        CompactionModel(c), invariants=(), sub_batch=64,
        visited_cap=1 << 10, frontier_cap=1 << 10,
        telemetry=stream,
    )
    r = ck.run()
    assert r.distinct_states > 0
    evs = [json.loads(l) for l in open(stream)]
    hdr = [e for e in evs if e["event"] == "run_header"][0]
    assert hdr["compact_impl"] == "logshift"
    comps = [e for e in evs if e["event"] == "compact"]
    assert comps, "no compact records in the stream"
    assert all(e["impl"] == "logshift" for e in comps)
    assert sum(e["dispatches"] for e in comps) > 0
    res = [e for e in evs if e["event"] == "result"][-1]
    assert res["stats"]["compact_impl"] == "logshift"
    assert res["stats"]["stage_compact_n"] == sum(
        e["dispatches"] for e in comps
    )
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from check_telemetry_schema import validate_stream

    assert validate_stream(stream) == []
    # a SECOND run() on the same checker must report only ITS OWN
    # dispatches (the stage counters are lifetime-cumulative; the
    # event deltas baseline per run)
    ck.run()
    evs2 = [json.loads(l) for l in open(stream)]
    runs = {e["run_id"] for e in evs2 if e["event"] == "run_header"}
    assert len(runs) == 2
    per_run = {}
    for e in evs2:
        if e["event"] == "compact":
            per_run[e["run_id"]] = per_run.get(e["run_id"], 0) + (
                e["dispatches"]
            )
    first = sum(e["dispatches"] for e in comps)
    assert set(per_run.values()) == {first}  # identical runs, no bleed
