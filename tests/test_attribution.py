"""Fused-era cost attribution tests (round 14, ISSUE 10).

The acceptance bar:

- **work-counter parity**: the fused megakernel's in-kernel work units
  equal the ``-fuse stage`` host dispatch-chain counts EXACTLY —
  state-for-state on the small differential configs and on both
  published bug oracles (the same harness shape as tests/test_fuse.py);
- **zero extra syncs**: the counters ride the packed stats vector —
  the r13 pinned dispatch/fetch economy is unchanged (fetch-count-
  identical, as r8 asserted for the heartbeat);
- **attribution from one fused run**: ``--attribution`` prices a
  single default-mode fused run's counters with a calibration derived
  from a real ``-fuse stage`` + ``PTT_STAGE_TIMING`` run, agreeing
  with that run's RTT-corrected measured stage seconds within a stated
  tolerance (exact parity of the work counts makes the agreement
  deterministic at the calibration shape);
- **v7 schema**: validator positive/negative streams for the new
  ``fuse`` work fields and the ``attribution`` record;
- **the run ledger**: round-trips legacy-shape BENCH_r0*.json artifacts,
  renders a correct delta table between two artifacts, and ``ledger
  gate`` catches an injected dispatches/level / work-units/state
  regression against the pinned mini-bench baseline (tier-1 gate).
"""

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
from pulsar_tlaplus_tpu.obs import attribution, ledger, report
from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINNED = os.path.join(
    ROOT, "tests", "data", "mini_bench_producer_on.jsonl"
)

WORK_KEYS = (
    "work_expand_rows", "work_probe_lanes", "work_compact_elems",
    "work_append_rows", "work_groups", "work_init_lanes",
)


def _checker_mod():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(ROOT, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(c, fuse="level", sub_batch=256, **kw):
    kw.setdefault("visited_cap", 1 << 12)
    kw.setdefault("frontier_cap", 1 << 12)
    return DeviceChecker(
        CompactionModel(c), invariants=kw.pop("invariants", ()),
        sub_batch=sub_batch, fuse=fuse, **kw,
    )


def _work(ck):
    return {
        k: v for k, v in ck.last_stats.items() if k.startswith("work_")
    }


# ---- the in-kernel counter primitives -------------------------------


def test_wkm_carry_arithmetic():
    """The hi/lo uint32 carry survives past 2^32 accumulated lanes —
    the r12 fpm pattern, pinned on the work vector."""
    wkm = jnp.zeros((fpset.WKM_N,), jnp.int32)
    big = (1 << 31) - 7  # near the int32 edge, added 3x crosses 2^32
    for _ in range(3):
        wkm = fpset.wkm_update(
            wkm, jnp.int32(5), jnp.int32(big), jnp.int32(big),
            jnp.int32(2), jnp.int32(1),
        )
    logical = fpset.wkm_logical(np.asarray(wkm))
    assert logical[0] == 15
    assert logical[1] == 3 * big  # > 2^32: needs the carry word
    assert logical[2] == 3 * big
    assert logical[3] == 6
    assert logical[4] == 3
    assert 3 * big > (1 << 32)


def test_wkm_logical_short_vectors_zero_pad():
    assert list(fpset.wkm_logical(np.zeros((3,), np.int32))) == [0] * 5


# ---- fused-vs-stage work-counter parity -----------------------------


@pytest.mark.parametrize("name", ["producer_on", "two_crashes"])
def test_work_counter_parity_small_configs(name):
    """Fused in-kernel totals == stage host dispatch-chain totals,
    key for key — the differential contract the whole attribution
    model rests on."""
    c = SMALL_CONFIGS[name]
    ck_f = _mk(c)
    r_f = ck_f.run()
    ck_s = _mk(c, fuse="stage")
    r_s = ck_s.run()
    assert r_f.distinct_states == r_s.distinct_states
    wf, ws = _work(ck_f), _work(ck_s)
    assert wf == ws and wf
    # structural identities: lanes/elems are flush-count x ACAP, and
    # every distinct state is appended exactly once; expand rows sum
    # the level frontiers
    assert wf["work_probe_lanes"] == ck_f.last_stats[
        "fpset_flushes"
    ] * ck_f.ACAP
    assert wf["work_compact_elems"] == wf["work_probe_lanes"]
    assert wf["work_append_rows"] == r_f.distinct_states
    assert wf["work_expand_rows"] == sum(r_f.level_sizes)
    assert wf["work_groups"] == ck_f.last_stats["fpset_flushes"]


def test_work_counter_parity_under_growth_and_flush_factor():
    """Mid-level capacity exits (the megakernel re-enters via w_off)
    and multi-window flush groups with masked partial tails must not
    skew any counter."""
    c = SMALL_CONFIGS["producer_on"]
    a_f = _mk(c, sub_batch=64, visited_cap=1 << 6, frontier_cap=1 << 6,
              group=2)
    a_f.run()
    a_s = _mk(c, fuse="stage", sub_batch=64, visited_cap=1 << 6,
              frontier_cap=1 << 6, group=2)
    a_s.run()
    assert _work(a_f) == _work(a_s)
    b_f = _mk(c, sub_batch=128, visited_cap=1 << 10,
              frontier_cap=1 << 10, flush_factor=4)
    b_f.run()
    b_s = _mk(c, fuse="stage", sub_batch=128, visited_cap=1 << 10,
              frontier_cap=1 << 10, flush_factor=4)
    b_s.run()
    assert _work(b_f) == _work(b_s)


@pytest.mark.parametrize(
    "invariant", ["CompactedLedgerLeak", "DuplicateNullKeyMessage"]
)
def test_work_counter_parity_bug_oracles(invariant):
    """Both published counterexamples (the tests/test_fuse.py
    differential harness): identical work totals through the
    violation-stopped fused and stage paths."""
    ck_f = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), invariants=(invariant,),
        sub_batch=2048, visited_cap=1 << 16, frontier_cap=1 << 15,
    )
    r_f = ck_f.run()
    ck_s = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), invariants=(invariant,),
        sub_batch=2048, visited_cap=1 << 16, frontier_cap=1 << 15,
        fuse="stage",
    )
    r_s = ck_s.run()
    assert r_f.violation == r_s.violation == invariant
    assert _work(ck_f) == _work(ck_s)
    assert _work(ck_f)


def test_work_counters_add_zero_fetches(tmp_path):
    """The r13 pinned dispatch economy is UNCHANGED with the work
    counters on board (they ride the same packed stats vector): the
    producer_on gate numbers — 2 megakernel dispatches + 3 stats
    fetches — hold, and every fuse record carries the v7 per-dispatch
    work deltas summing to the run totals."""
    stream = str(tmp_path / "wk.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], telemetry=stream)
    r = ck.run()
    assert r.distinct_states == 1654
    assert ck._fetch_n == 3  # fetch-count-identical to the r13 gate
    assert ck.last_stats["stage_fused_n"] == 2
    evs = [json.loads(x) for x in open(stream)]
    fuse_evs = [e for e in evs if e["event"] == "fuse"]
    assert fuse_evs
    for key in ("work_expand_rows", "work_probe_lanes",
                "work_compact_elems", "work_append_rows"):
        assert all(isinstance(e[key], int) for e in fuse_evs)
    # per-dispatch deltas sum to the run totals (minus the host-side
    # init chain, which appends level 1 before any fused dispatch)
    assert sum(e["work_probe_lanes"] for e in fuse_evs) + ck.ACAP == (
        ck.last_stats["work_probe_lanes"]
    )
    assert sum(
        e["work_append_rows"] for e in fuse_evs
    ) + r.level_sizes[0] == ck.last_stats["work_append_rows"]
    # the attribution record precedes the result with the same totals
    attr = [e for e in evs if e["event"] == "attribution"]
    assert len(attr) == 1
    assert attr[0]["stages"]["probe_lanes"] == ck.last_stats[
        "work_probe_lanes"
    ]


# ---- calibration + the attribution report ---------------------------


def _stage_timed_run(c, tmp_path, monkeypatch, **kw):
    """A -fuse stage run under PTT_STAGE_TIMING=1 (the calibration
    reference).  The flag is read at ctor time, so patch first."""
    monkeypatch.setenv("PTT_STAGE_TIMING", "1")
    stream = str(tmp_path / "stage_timed.jsonl")
    ck = _mk(c, fuse="stage", telemetry=stream, **kw)
    ck.run()
    monkeypatch.delenv("PTT_STAGE_TIMING")
    events, errs = report.load_events(stream)
    assert not errs
    return ck, events


def test_attribution_single_fused_run_matches_stage_timed(
    tmp_path, monkeypatch
):
    """THE acceptance composition: calibrate from a real ``-fuse
    stage`` + ``PTT_STAGE_TIMING`` run (RTT-corrected), attribute a
    single default-mode FUSED run — the estimates must reproduce the
    measured per-stage seconds within 2% (the work counts are exactly
    equal, so the only slack is float rounding in the emitted
    stream)."""
    c = SMALL_CONFIGS["producer_on"]
    _ck, stage_events = _stage_timed_run(c, tmp_path, monkeypatch)
    cal = attribution.calibrate_from_events(stage_events, label="test")
    assert set(cal["measured_stages"]) >= {
        "expand", "flush", "compact", "append",
    }
    fused_stream = str(tmp_path / "fused.jsonl")
    ck_f = _mk(c, telemetry=fused_stream)
    ck_f.run()
    fused_events, _ = report.load_events(fused_stream)
    rows = {
        r["stage"]: r for r in attribution.attribute(fused_events, cal)
    }
    measured = report.stage_split(stage_events)
    for stage in ("expand", "flush", "compact", "append"):
        est = rows[stage]["est_s"]
        dev = measured[stage]["device_s"]
        assert est is not None and dev is not None
        assert est == pytest.approx(dev, rel=0.02), stage
        # the fused stream itself carries NO measured timings — the
        # whole point: no stage rerun was needed for the estimate
        assert rows[stage]["measured_s"] is None
    table = attribution.render_attribution([("fused", fused_events)], cal)
    assert "| flush |" in table and "est s" in table


def test_attribution_cli_front_end(tmp_path):
    """scripts/telemetry_report.py --attribution renders the table
    from a fused stream (with the default, footnoted-uncalibrated
    units when no calibration file is given)."""
    stream = str(tmp_path / "cli.jsonl")
    _mk(SMALL_CONFIGS["producer_on"], telemetry=stream).run()
    cal_path = str(tmp_path / "cal.json")
    attribution.save_calibration(
        cal_path, attribution.default_calibration("cpu")
    )
    p = subprocess.run(
        [
            sys.executable, "scripts/telemetry_report.py", stream,
            "--attribution", "--calibration", cal_path,
        ],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr[-500:]
    assert "| flush |" in p.stdout
    assert "est s" in p.stdout


def test_calibration_round_trip(tmp_path):
    path = str(tmp_path / "c.json")
    cal = attribution.default_calibration("cpu")
    attribution.save_calibration(path, cal)
    assert attribution.load_calibration(path)["units"] == cal["units"]
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"nope": 1}, f)
    with pytest.raises(ValueError, match="units"):
        attribution.load_calibration(bad)


# ---- v7 schema: validator positive/negative -------------------------


def test_v7_stream_validates_and_negatives(tmp_path):
    ckr = _checker_mod()
    stream = tmp_path / "v7.jsonl"
    _mk(SMALL_CONFIGS["producer_on"], telemetry=str(stream)).run()
    assert ckr.validate_stream(str(stream)) == []
    evs = [json.loads(x) for x in open(stream)]
    assert any(e["event"] == "attribution" for e in evs)
    # negative: a v7 fuse record missing a work field fails validation
    bad = []
    done = False
    for e in evs:
        if not done and e["event"] == "fuse":
            e = {k: v for k, v in e.items() if k != "work_probe_lanes"}
            done = True
        bad.append(e)
    p = tmp_path / "v7_bad.jsonl"
    p.write_text("".join(json.dumps(e) + "\n" for e in bad))
    errs = ckr.validate_stream(str(p))
    assert errs and any("work_probe_lanes" in e for e in errs)
    # a v6 fuse record WITHOUT work fields stays valid (FIELD_SINCE)
    old = []
    for e in evs:
        if e["event"] == "fuse":
            e = {
                k: v for k, v in e.items()
                if not k.startswith("work_")
            }
            e["v"] = 6
        old.append(e)
    p2 = tmp_path / "v6_ok.jsonl"
    p2.write_text("".join(json.dumps(e) + "\n" for e in old))
    assert ckr.validate_stream(str(p2)) == []
    # negative: an attribution record without stages fails
    noat = [
        dict(e, stages=None) if e["event"] == "attribution" else e
        for e in evs
    ]
    for e in noat:
        if e["event"] == "attribution":
            del e["stages"]
    p3 = tmp_path / "v7_noattr.jsonl"
    p3.write_text("".join(json.dumps(e) + "\n" for e in noat))
    errs3 = ckr.validate_stream(str(p3))
    assert errs3 and any("stages" in e for e in errs3)


def test_bench_schema_v7_keys():
    ckr = _checker_mod()
    base = {k: 1 for k in ckr.BENCH_KEYS_V7}
    base.update(bench_schema=7, value=1.0)
    assert ckr.validate_bench_artifact(dict(base), "good") == []
    bad = dict(base)
    del bad["work_probe_lanes"], bad["work_groups"]
    errs = ckr.validate_bench_artifact(bad, "bad")
    assert any("work_probe_lanes" in e for e in errs)
    assert any("work_groups" in e for e in errs)
    # a schema-6 artifact is NOT held to the work keys
    v6 = {k: 1 for k in ckr.BENCH_KEYS_V6}
    v6.update(bench_schema=6, value=1.0)
    assert ckr.validate_bench_artifact(v6, "v6") == []


def test_bench_schema_v12_keys():
    """bench_schema 12 artifacts must carry the kernel fields +
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


def test_stream_record_derives_probe_lanes_per_sec(tmp_path):
    """Stream-ingested records derive the flush-stage throughput from
    the work counters + wall clock, and carry the kernel fields at
    their one value."""
    stream = str(tmp_path / "run.jsonl")
    _mk(SMALL_CONFIGS["producer_on"], telemetry=stream).run()
    v = ledger.record_from_file(stream)["values"]
    assert v["probe_lanes_per_sec"] == round(
        v["work_probe_lanes"] / v["wall_s"], 1
    )
    for k, want in obs.IMPL_FIELDS.items():
        assert v[k] == want, k


# ---- liveness sweep attribution (satellite 1) -----------------------


def test_sweep_work_counters_and_attribution(tmp_path):
    """The fused+grouped sweep counts its merge-sort lanes,
    gid-propagation pass-lanes, and edge-compaction elements; the
    stream validates at v7 and the attribution layer renders a sweep
    section."""
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    ckr = _checker_mod()
    stream = str(tmp_path / "sweep.jsonl")
    c = SMALL_CONFIGS["producer_on"]
    lck = LivenessChecker(
        CompactionModel(c), goal="Termination", fairness="wf_next",
        frontier_chunk=256, visited_cap=1 << 12, telemetry=stream,
    )
    lres = lck.run()
    assert lres.distinct_states == 1654
    assert ckr.validate_stream(stream) == []
    events, _ = report.load_events(stream)
    sweeps = [e for e in events if e.get("event") == "sweep"]
    assert sweeps
    last = sweeps[-1]
    # cumulative totals match the trace-time constants: chunks x the
    # per-chunk sort/prop/compact widths
    n_chunks = last["chunk"]
    NQ = lck.SF * lck.model.A
    cap = lck._table_cap(lres.distinct_states)
    assert last["sort_lanes"] == n_chunks * 2 * (cap + NQ)
    assert last["compact_elems"] == n_chunks * NQ
    assert last["prop_lanes"] % (cap + NQ) == 0
    # monotone cumulative across records
    assert all(
        a["sort_lanes"] <= b["sort_lanes"]
        for a, b in zip(sweeps, sweeps[1:])
    )
    # the liveness result carries the totals + an attribution record
    res = [e for e in events if e.get("event") == "result"][-1]
    assert res["work_sweep_sort_lanes"] == last["sort_lanes"]
    attr = [e for e in events if e.get("event") == "attribution"]
    assert any("sweep_sort_lanes" in a["stages"] for a in attr)
    rows = attribution.sweep_attribute(events)
    stages = [r["stage"] for r in rows]
    assert "sweep_sort" in stages and "sweep_compact" in stages
    table = attribution.render_attribution([("lv", events)])
    assert "sweep_sort" in table


# ---- heartbeat smoothing (satellite 2) ------------------------------


def test_heartbeat_ewma_and_partial_marker():
    """The heartbeat's displayed rate is an EWMA across beats (the
    fuse-batch sawtooth damper) and a line whose newest snapshot was
    an intra-level anchor carries the ~ marker."""
    lines = []
    snap = {"distinct_states": 0, "level": 3}
    hb = obs.Heartbeat(5.0, snap, log=lines.append)
    import time as _time

    t0 = _time.monotonic() - 1.0
    snap["distinct_states"] = 1000
    prev = hb._beat(t0, (t0, 0))
    assert hb.ewma_sps is not None
    first = hb.ewma_sps
    # a huge burst (a ramp batch landing 8 levels at once): the EWMA
    # moves toward the spike but stays well below the raw sample
    snap["distinct_states"] = 101000
    snap["partial"] = True
    _time.sleep(0.01)
    hb._beat(t0, prev)
    raw_spike = (101000 - 1000) / max(
        _time.monotonic() - prev[0], 1e-9
    )
    assert first < hb.ewma_sps < raw_spike
    assert hb.ewma_sps < 0.5 * raw_spike  # genuinely smoothed
    assert "~" in lines[1].split(")")[0]  # the partial marker
    assert "~" not in lines[0].split(")")[0]


def test_engine_snap_carries_partial_flag(tmp_path):
    """The engine's heartbeat snapshot tags intra-level anchors so the
    marker reflects the newest record kind."""
    ck = _mk(SMALL_CONFIGS["producer_on"])
    ck.run()
    # the final record of a clean run is a level boundary
    assert ck._snap.get("partial") is False


# ---- the run ledger (tentpole part 3) -------------------------------


def test_ledger_roundtrip_legacy_bench_artifacts(tmp_path, bench_dir):
    """Driver-wrapper BENCH artifacts, pre-schema r1 through schema-2
    r5, ingest, dedup, validate, and render."""
    path = str(tmp_path / "ledger.jsonl")
    sources = sorted(os.listdir(bench_dir))
    assert len(sources) == 5
    recs = [
        ledger.record_from_file(os.path.join(bench_dir, p))
        for p in sources
    ]
    assert ledger.append(path, recs) == len(sources)
    assert ledger.append(path, recs) == 0  # idempotent by digest
    assert ledger.validate_ledger(path) == []
    loaded = ledger.load(path)
    assert [r["source"] for r in loaded] == sources
    assert all(r["values"].get("value") for r in loaded)
    # rounds parsed from the driver wrapper
    assert [r["round"] for r in loaded] == [1, 2, 3, 4, 5]
    table = ledger.render_list(loaded)
    assert "BENCH_r05.json" in table


def test_ledger_compare_two_bench_artifacts(bench_dir):
    """The acceptance delta table: r04 -> r05 shows the headline rate
    moving by the artifacts' amounts."""
    a = ledger.record_from_file(os.path.join(bench_dir, "BENCH_r04.json"))
    b = ledger.record_from_file(os.path.join(bench_dir, "BENCH_r05.json"))
    rows = {r["key"]: r for r in ledger.compare(a, b)}
    assert rows["value"]["a"] == pytest.approx(2_000_000.0)
    assert rows["value"]["b"] == pytest.approx(3_150_000.0)
    assert rows["value"]["pct"] == pytest.approx(57.5, abs=0.1)
    assert rows["distinct_states"]["delta"] == 170_000_000 - 60_000_000
    out = ledger.render_compare(a, b)
    assert "+57.5%" in out
    # same config key: no incomparability warning
    assert "WARNING" not in out


def test_ledger_stream_record_and_key_grouping(tmp_path):
    """Telemetry streams ingest through the same bench_keys layer;
    runs of the same config/engine/modes share a config key, and a
    mode flip (fuse=stage) changes it."""
    s1 = str(tmp_path / "a.jsonl")
    s2 = str(tmp_path / "b.jsonl")
    s3 = str(tmp_path / "c.jsonl")
    _mk(SMALL_CONFIGS["producer_on"], telemetry=s1).run()
    _mk(SMALL_CONFIGS["producer_on"], telemetry=s2).run()
    _mk(SMALL_CONFIGS["producer_on"], fuse="stage", telemetry=s3).run()
    r1 = ledger.record_from_file(s1)
    r2 = ledger.record_from_file(s2)
    r3 = ledger.record_from_file(s3)
    assert r1["key"] == r2["key"]
    assert r1["key"] != r3["key"]
    assert "fuse=level" in r1["key"] and "fuse=stage" in r3["key"]
    assert r1["values"]["work_units_per_state"] > 0


def test_ledger_gate_tier1_pinned_baseline(tmp_path):
    """THE tier-1 gate: a fresh producer_on fused run gates clean
    against the committed mini-bench baseline on the deterministic
    economy keys; an injected dispatches/level or work-units/state
    regression fails with exit 1."""
    from pulsar_tlaplus_tpu import cli

    path = str(tmp_path / "gate_ledger.jsonl")
    shutil.copy(PINNED, path)
    assert ledger.validate_ledger(path) == []
    stream = str(tmp_path / "run.jsonl")
    _mk(SMALL_CONFIGS["producer_on"], telemetry=stream).run()
    rc = cli.main(["ledger", "--ledger", path, "add", stream])
    assert rc == 0
    rc = cli.main(
        [
            "ledger", "--ledger", path, "gate", "--threshold", "0.1",
            "--keys", "dispatches_per_level", "work_units_per_state",
        ]
    )
    assert rc == 0  # the current build does not regress the economy
    # inject a regression: a future PR that doubles dispatches/level
    # or work per state must fail the suite here
    cur = ledger.load(path)[-1]
    bad = dict(cur, values=dict(cur["values"]))
    bad["values"]["dispatches_per_level"] = (
        cur["values"]["dispatches_per_level"] * 2
    )
    bad["values"]["work_units_per_state"] = (
        cur["values"]["work_units_per_state"] * 1.5
    )
    bad["digest"] = ledger._digest(bad["values"])
    ledger.append(path, [bad])
    rc = cli.main(
        [
            "ledger", "--ledger", path, "gate", "--threshold", "0.1",
            "--keys", "dispatches_per_level", "work_units_per_state",
        ]
    )
    assert rc == 1
    violations = ledger.gate(
        cur, bad, threshold=0.1,
        keys=("dispatches_per_level", "work_units_per_state"),
    )
    assert {v["key"] for v in violations} == {
        "dispatches_per_level", "work_units_per_state",
    }


def test_ledger_validator_catches_tampering(tmp_path, bench_dir):
    path = str(tmp_path / "t.jsonl")
    rec = ledger.record_from_file(os.path.join(bench_dir, "BENCH_r05.json"))
    ledger.append(path, [rec])
    # hand-edit a value without refreshing the digest
    lines = open(path).read().splitlines()
    d = json.loads(lines[0])
    d["values"]["value"] = 999.0
    with open(path, "w") as f:
        f.write(json.dumps(d) + "\n")
    errs = ledger.validate_ledger(path)
    assert errs and any("digest" in e for e in errs)


def test_ledger_cli_validator_front_end(tmp_path, bench_dir):
    """check_telemetry_schema.py --ledger validates ledger files."""
    ckr = _checker_mod()
    path = str(tmp_path / "v.jsonl")
    ledger.append(
        path,
        [ledger.record_from_file(os.path.join(bench_dir, "BENCH_r05.json"))],
    )
    assert ckr.main([path, "--ledger"]) == 0
    with open(path, "a") as f:
        f.write('{"not": "a record"}\n')
    assert ckr.main([path, "--ledger"]) == 1


def test_liveness_stream_attributes_engine_and_sweep_stages(tmp_path):
    """A liveness stream carries TWO attribution records (the inner
    explorer's and the sweep's) — work_units merges them, so the
    engine per-stage rows never vanish behind the sweep-only record
    (review finding: last-record-wins dropped the whole explorer
    table)."""
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    stream = str(tmp_path / "lv2.jsonl")
    LivenessChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]),
        goal="Termination", fairness="wf_next", frontier_chunk=256,
        visited_cap=1 << 12, telemetry=stream,
    ).run()
    events, _ = report.load_events(stream)
    w = attribution.work_units(events)
    assert "probe_lanes" in w and "sweep_sort_lanes" in w
    rows = attribution.attribute(events)
    assert {r["stage"] for r in rows} >= {"expand", "flush", "append"}


def test_gate_rejects_unknown_keys(tmp_path, bench_dir):
    """A typo'd --keys must error (exit 2), never pass vacuously."""
    from pulsar_tlaplus_tpu import cli

    a = ledger.record_from_file(os.path.join(bench_dir, "BENCH_r04.json"))
    b = ledger.record_from_file(os.path.join(bench_dir, "BENCH_r05.json"))
    with pytest.raises(KeyError, match="dispaches_per_level"):
        ledger.gate(a, b, keys=("dispaches_per_level",))
    path = str(tmp_path / "l.jsonl")
    ledger.append(path, [a, b])
    rc = cli.main(
        [
            "ledger", "--ledger", path, "gate",
            "--keys", "dispaches_per_level",
        ]
    )
    assert rc == 2


def test_ledger_rejects_non_telemetry_jsonl(tmp_path, bench_dir):
    """The append-only ledger must refuse to ingest a .jsonl that is
    not a telemetry stream (e.g. the ledger file itself) — a junk
    record could never be deleted again."""
    from pulsar_tlaplus_tpu import cli

    path = str(tmp_path / "self.jsonl")
    ledger.append(
        path,
        [ledger.record_from_file(os.path.join(bench_dir, "BENCH_r05.json"))],
    )
    ledger.append(
        path,
        [ledger.record_from_file(os.path.join(bench_dir, "BENCH_r04.json"))],
    )
    with pytest.raises(ValueError, match="not a telemetry stream"):
        ledger.record_from_file(path)
    assert cli.main(["ledger", "--ledger", path, "add", path]) == 2
    assert len(ledger.load(path)) == 2  # nothing was appended


def test_gate_default_baseline_precedes_current(tmp_path):
    """Gating an OLDER record must pick an even earlier baseline —
    never a newer run (which would invert the comparison)."""
    from pulsar_tlaplus_tpu import cli

    base = ledger.record_from_file(PINNED)

    def forged(dpl, tag):
        r = dict(base, values=dict(base["values"]), source=tag)
        r["values"]["dispatches_per_level"] = dpl
        r["digest"] = ledger._digest(r["values"])
        return r

    old, mid, new = (
        forged(0.31, "old"), forged(0.32, "mid"), forged(0.10, "new")
    )
    path = str(tmp_path / "ord.jsonl")
    ledger.append(path, [old, mid, new])
    # gate `mid`: its baseline must be `old` (0.31 -> 0.32 = +3%,
    # passes), NOT `new` (0.10 -> 0.32 = +220%, would fail)
    rc = cli.main(
        [
            "ledger", "--ledger", path, "gate",
            "--current", mid["digest"],
            "--keys", "dispatches_per_level",
        ]
    )
    assert rc == 0


# ---- the 253k acceptance oracle -------------------------------------


FULL_253K = dataclasses.replace(
    pe.SHIPPED_CFG, model_producer=True, retain_null_key=False
)


def test_253k_single_fused_run_attribution(tmp_path):
    """ISSUE 10 acceptance: a SINGLE default-mode fused run on the
    253k CPU-mesh oracle yields the --attribution per-stage table —
    no ``-fuse stage`` rerun, zero extra device fetches (the work
    counters ride the one packed stats vector), and the counters
    reconcile against the run's own flush/level accounting."""
    stream = str(tmp_path / "full.jsonl")
    ck = DeviceChecker(
        CompactionModel(FULL_253K), invariants=(), sub_batch=4096,
        visited_cap=1 << 18, frontier_cap=1 << 17, flush_factor=2,
        telemetry=stream,
    )
    r = ck.run()
    assert r.distinct_states == 253361 and r.diameter == 23
    # zero-extra-fetch: every fetch is one the r13 economy already
    # paid (init chain + one per megakernel dispatch + growth exits)
    assert ck._fetch_n == ck.last_stats["stats_fetches"]
    w = _work(ck)
    assert w["work_probe_lanes"] == (
        ck.last_stats["fpset_flushes"] * ck.ACAP
    )
    assert w["work_append_rows"] == r.distinct_states
    assert w["work_expand_rows"] == sum(r.level_sizes)
    events, _ = report.load_events(stream)
    table = attribution.render_attribution([("253k", events)])
    assert "| flush |" in table and "253361" in table


@pytest.mark.slow
def test_253k_fused_vs_stage_work_parity():
    """The full differential at the 253k shape (two runs — slow-marked
    like the r10 253k compact differential; the real host runs it).
    The small-config + bug-oracle parity tests cover the same
    contract in-tier."""
    ck_f = DeviceChecker(
        CompactionModel(FULL_253K), invariants=(), sub_batch=4096,
        visited_cap=1 << 18, frontier_cap=1 << 17, flush_factor=2,
    )
    r_f = ck_f.run()
    ck_s = DeviceChecker(
        CompactionModel(FULL_253K), invariants=(), sub_batch=4096,
        visited_cap=1 << 18, frontier_cap=1 << 17, flush_factor=2,
        fuse="stage",
    )
    r_s = ck_s.run()
    assert r_f.distinct_states == r_s.distinct_states == 253361
    assert _work(ck_f) == _work(ck_s)
