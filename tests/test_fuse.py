"""Fused level megakernel tests (round 13, ``-fuse level``).

The acceptance bar (ISSUE 9):

- the dispatch-count REGRESSION GATE: on the pinned producer_on oracle
  the fused engine executes an exact, pinned number of megakernel
  dispatches and stats fetches — steady-state levels cost exactly
  1 dispatch + 1 fetch, the ramp batches >= 4 levels per dispatch, and
  no per-level stage dispatches survive (a future PR reintroducing a
  per-level host round trip fails here);
- fused-vs-stage state-for-state differentials: identical level sizes,
  rows, parent/lane logs on clean runs, identical violation gid +
  replayed trace on both published bug oracles;
- ramp-megakernel survivability: a mid-ramp ``kill@level:N`` drill
  crash-resumes to the exact uninterrupted result;
- the daemon time-slices ``-fuse level`` jobs with solo parity;
- telemetry: the v6 stream validates, and the validator's fused-run
  cross-check catches a corrupted per-level record.
"""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


# ---- the dispatch-count regression gate (tier-1 acceptance) ---------


def test_fused_dispatch_count_regression_gate(tmp_path):
    """Pinned dispatch economy on the producer_on oracle (1,654 states
    / 16 levels).  With sub_batch=256 every frontier fits one expand
    window, so the WHOLE run is two ramp batches of 8 levels: exactly
    2 megakernel dispatches + 3 stats fetches (init + one per batch),
    and zero per-level stage dispatches (the stage counters show only
    the init path's single flush/compact/append chain).  Any future
    change that reintroduces a per-level host round trip moves these
    exact numbers and fails here."""
    stream = str(tmp_path / "fuse_gate.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], telemetry=stream)
    r = ck.run()
    assert r.distinct_states == 1654 and r.diameter == 16
    assert ck.fuse == "level"
    assert ck.last_stats["stage_fused_n"] == 2
    assert ck._fetch_n == 3  # init fetch + 1 per megakernel dispatch
    assert ck.last_stats["fuse_levels"] == 16
    assert ck.last_stats["dispatches_per_level"] < 0.5
    # the init path is the ONLY user of the stage chain
    assert ck.last_stats["stage_flush_n"] == 1
    assert ck.last_stats["stage_compact_n"] == 1
    assert ck.last_stats["stage_append_n"] == 1
    assert "stage_expand_n" not in ck.last_stats
    evs = [json.loads(x) for x in open(stream)]
    fuse_evs = [e for e in evs if e["event"] == "fuse"]
    assert [e["levels"] for e in fuse_evs] == [8, 8]
    # ramp acceptance: >= 4 levels batched into one dispatch
    assert max(e["levels"] for e in fuse_evs) >= 4


def test_fused_steady_state_one_dispatch_one_fetch_per_level(tmp_path):
    """With sub_batch=64 the deep producer_on levels (sizes 76..212)
    exceed one expand window, so the ramp hands off after its 4-level
    opening batch and every steady-state level costs EXACTLY one
    megakernel dispatch + one stats fetch."""
    stream = str(tmp_path / "fuse_steady.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], sub_batch=64,
             telemetry=stream)
    r = ck.run()
    assert r.distinct_states == 1654 and r.diameter == 16
    assert ck.last_stats["stage_fused_n"] == 13  # 1 ramp + 12 steady
    assert ck._fetch_n == 14
    assert ck.last_stats["dispatches_per_level"] == 1.0
    evs = [json.loads(x) for x in open(stream)]
    fuse_evs = [e for e in evs if e["event"] == "fuse"]
    assert fuse_evs[0]["levels"] == 4  # the ramp batch
    # every steady-state dispatch closed exactly one level
    assert all(e["levels"] == 1 for e in fuse_evs[1:])


# ---- fused-vs-stage state-for-state differentials -------------------


@pytest.mark.parametrize("name", ["producer_on", "two_crashes"])
def test_fused_vs_stage_state_for_state(name):
    """Same states in the same order: level sizes, packed rows, and
    parent/lane trace logs must be bit-identical between the fused
    megakernel and the r10 stage chain."""
    c = SMALL_CONFIGS[name]
    ck_f = _mk(c)
    r_f = ck_f.run()
    ck_s = _mk(c, fuse="stage")
    r_s = ck_s.run()
    assert r_f.distinct_states == r_s.distinct_states
    assert r_f.level_sizes == r_s.level_sizes
    nv, W = r_f.distinct_states, ck_f.W
    for key in ("parent", "lane"):
        a = np.asarray(ck_f.last_bufs[key][:nv])
        b = np.asarray(ck_s.last_bufs[key][:nv])
        assert (a == b).all(), key
    a = np.asarray(ck_f.last_bufs["rows"][: nv * W])
    b = np.asarray(ck_s.last_bufs["rows"][: nv * W])
    assert (a == b).all()


@pytest.mark.parametrize(
    "invariant,depth",
    [("CompactedLedgerLeak", 12), ("DuplicateNullKeyMessage", 4)],
)
def test_fused_vs_stage_bug_oracles(invariant, depth):
    """Both published counterexamples: identical violation gid and an
    identical replayed trace through the fused path."""
    m1 = CompactionModel(pe.SHIPPED_CFG)
    r_f = DeviceChecker(
        m1, invariants=(invariant,), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    ).run()
    m2 = CompactionModel(pe.SHIPPED_CFG)
    r_s = DeviceChecker(
        m2, invariants=(invariant,), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15, fuse="stage",
    ).run()
    assert r_f.violation == r_s.violation == invariant
    assert r_f.violation_gid == r_s.violation_gid
    assert r_f.diameter == r_s.diameter == depth
    assert r_f.trace == r_s.trace
    assert r_f.trace_actions == r_s.trace_actions
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r_f.trace, r_f.trace_actions, invariant
    )


def test_fused_growth_and_flush_factor_matches_oracle():
    """Tiny capacities force mid-level segmentation (the megakernel
    exits on its in-kernel capacity guard, the host grows, re-enters
    via w_off) and flush_factor>1 exercises multi-window groups with
    masked partial tails; counts must stay exact."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = _mk(c, sub_batch=64, visited_cap=1 << 6,
              frontier_cap=1 << 6, group=2).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    got = _mk(c, sub_batch=128, visited_cap=1 << 10,
              frontier_cap=1 << 10, flush_factor=4).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_fuse_ctor_validation():
    with pytest.raises(ValueError, match="fuse must be"):
        _mk(SMALL_CONFIGS["producer_on"], fuse="banana")
    with pytest.raises(ValueError, match="fuse_group"):
        _mk(SMALL_CONFIGS["producer_on"], fuse_group=0)


def test_fuse_group_one_disables_ramp_batching(tmp_path):
    stream = str(tmp_path / "fuse_g1.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], fuse_group=1,
             telemetry=stream)
    r = ck.run()
    assert r.distinct_states == 1654
    evs = [json.loads(x) for x in open(stream)]
    assert all(
        e["levels"] <= 1 for e in evs if e["event"] == "fuse"
    )
    assert ck.last_stats["stage_fused_n"] == 16


# ---- ramp survivability: mid-ramp kill drill ------------------------


def _run_drill(tmp_path, fault, resume=False):
    env = dict(os.environ)
    env["PTT_FAULT"] = "" if resume else fault
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [
        sys.executable, "-m", "tests._survivable_run",
        "--checkpoint", str(tmp_path / "frame.npz"),
        "--every", "4",
        "--telemetry", str(tmp_path / "drill.jsonl"),
    ]
    if resume:
        cmd.append("--resume")
    return subprocess.run(
        cmd, cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900,
    )


def test_mid_ramp_kill_drill_crash_resume_parity(tmp_path):
    """kill@level:7 with checkpoint_every=4: level 7 sits mid-batch
    (batches end on checkpoint boundaries — levels 5..8 share one
    dispatch on the shipped ramp), so the kill fires during the
    host-side replay of a multi-level megakernel batch.  The resumed
    run must land the exact 45,198/diam-20 published result."""
    p = _run_drill(tmp_path, "kill@level:7")
    assert p.returncode == 137, p.stderr[-500:]
    # the crashed run's stream proves the drill hit a RAMP batch: a
    # fuse record closing >1 level precedes the kill breadcrumb
    evs = [json.loads(x) for x in open(tmp_path / "drill.jsonl")]
    assert any(
        e["event"] == "fuse" and e["levels"] > 1 for e in evs
    )
    assert any(e["event"] == "fault" for e in evs)
    p2 = _run_drill(tmp_path, "", resume=True)
    assert p2.returncode == 0, p2.stderr[-500:]
    out = json.loads(p2.stdout.strip().splitlines()[-1])
    assert out["distinct_states"] == 45198
    assert out["diameter"] == 20
    assert not out["truncated"]


# ---- the daemon time-slices fused jobs with solo parity -------------


def test_daemon_timeslices_fused_jobs_with_solo_parity(tmp_path):
    """Two queued jobs share one device through suspend/resume at
    level boundaries while BOTH run the fused megakernel (the r13
    default): results match solo runs state-for-state and the pool's
    checkers genuinely dispatched fused."""
    from pulsar_tlaplus_tpu.service import jobs as jobmod
    from pulsar_tlaplus_tpu.service.scheduler import (
        CheckerPool,
        Scheduler,
        ServiceConfig,
    )

    cfgs = tmp_path / "cfgs"
    cfgs.mkdir()
    (cfgs / "a.cfg").write_text(
        "CONSTANTS\n    MessageSentLimit = 2\n"
        "    CompactionTimesLimit = 2\n    ModelConsumer = FALSE\n"
        "    ConsumeTimesLimit = 2\n    KeySpace = {1}\n"
        "    ValueSpace = {1}\n    RetainNullKey = TRUE\n"
        "    MaxCrashTimes = 1\n    ModelProducer = TRUE\n"
        "SPECIFICATION Spec\nINVARIANTS\n"
    )
    config = ServiceConfig(
        state_dir=str(tmp_path / "state"),
        slice_s=0.02,
        sub_batch=64,
        visited_cap=1 << 10,
        frontier_cap=1 << 8,
        max_states=1 << 14,
        checkpoint_every=1,
        prewarm_tiers=False,
    )
    pool = CheckerPool(config)
    sched = Scheduler(config, pool=pool)
    j1 = sched.submit("compaction", str(cfgs / "a.cfg"), invariants=[])
    j2 = sched.submit("compaction", str(cfgs / "a.cfg"), invariants=[])
    sched.run_until_idle()
    assert j1.state == j2.state == jobmod.DONE
    assert j1.suspends >= 1  # time-slicing genuinely happened
    solo = _mk(
        SMALL_CONFIGS["producer_on"], sub_batch=64,
        visited_cap=1 << 10, frontier_cap=1 << 8,
        max_states=1 << 14,
    ).run()
    for j in (j1, j2):
        assert j.result["distinct_states"] == solo.distinct_states
        assert j.result["diameter"] == solo.diameter
        assert j.result["level_sizes"] == list(solo.level_sizes)
    # the pooled checker ran the fused path, not a silent fallback
    (_key, ck), = pool._checkers.items()
    assert ck.fuse == "level"
    assert ck.last_stats.get("stage_fused_n", 0) > 0


# ---- telemetry schema v6 + the fused-run validator cross-check ------


def test_fused_stream_validates_and_crosschecks(tmp_path):
    ckr = _checker_mod()
    stream = tmp_path / "v6.jsonl"
    ck = _mk(SMALL_CONFIGS["producer_on"], telemetry=str(stream))
    r = ck.run()
    assert ckr.validate_stream(str(stream)) == []
    evs = [json.loads(x) for x in open(stream)]
    # boundary level records reproduce the result's level sizes
    bound = [
        e for e in evs
        if e["event"] == "level" and not e.get("partial")
    ]
    assert [e["new_states"] for e in bound] == list(r.level_sizes)[1:]
    # negative: corrupt one boundary record's count — the v6
    # cross-check must flag it (sizes no longer match the result)
    bad = []
    done = False
    for e in evs:
        if (
            not done and e["event"] == "level"
            and not e.get("partial")
        ):
            e = dict(e, new_states=e["new_states"] + 1)
            done = True
        bad.append(e)
    p = tmp_path / "v6_bad.jsonl"
    p.write_text("".join(json.dumps(e) + "\n" for e in bad))
    errs = ckr.validate_stream(str(p))
    assert errs and any("level" in e for e in errs)
    # negative: a dropped boundary record breaks nothing (levels may
    # legally be absent) but a DUPLICATED one breaks monotonicity
    dup = evs + [e for e in evs if e["event"] == "level"][:1]
    for i, e in enumerate(dup):
        dup[i] = dict(e, seq=i)
    p2 = tmp_path / "v6_dup.jsonl"
    p2.write_text("".join(json.dumps(e) + "\n" for e in dup))
    errs2 = ckr.validate_stream(str(p2))
    assert errs2 and any("strictly increasing" in e for e in errs2)


def test_bench_schema_v6_keys(tmp_path):
    """bench_schema 6 artifacts must carry the fuse keys; a v6
    artifact missing them fails the validator."""
    ckr = _checker_mod()
    base = {k: 1 for k in ckr.BENCH_KEYS_V6}
    base.update(bench_schema=6, value=1.0)
    assert ckr.validate_bench_artifact(dict(base), "good") == []
    bad = dict(base)
    del bad["fuse"], bad["dispatches_per_level"]
    errs = ckr.validate_bench_artifact(bad, "bad")
    assert any("fuse" in e for e in errs)
    assert any("dispatches_per_level" in e for e in errs)


def test_shipped_oracle_through_fused_path():
    """The 45,198-state / diameter-20 vendored reference binding,
    state-count-pinned through the fused megakernel (the ISSUE 9
    acceptance restated on the engine default)."""
    ck = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    )
    assert ck.fuse == "level"
    r = ck.run()
    assert r.distinct_states == 45198
    assert r.diameter == 20
    assert r.violation is None and not r.deadlock
    assert ck.last_stats["dispatches_per_level"] <= 2.0


def test_fused_prewarm_zero_compiles_across_tier_crossing():
    """warmup(tiers=True) walks the unified fused staircase: a run
    that crosses capacity tiers adds ZERO jitted programs after run()
    starts (the r10 prewarm contract, now covering the megakernel's
    (TCAP, LCAP, PCAP) triples)."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    ck = _mk(c, sub_batch=64, visited_cap=1 << 6, frontier_cap=1 << 6,
             group=2, max_states=1 << 12)
    v0 = ck.VCAP
    ck.warmup(seed=False, tiers=True)
    keys_before = set(ck._jits)
    r = ck.run()
    assert set(ck._jits) == keys_before  # zero post-run() compiles
    assert ck.VCAP > v0  # the run genuinely crossed tiers
    assert r.distinct_states == want.distinct_states


def test_fused_frontier_window_matches_oracle():
    """rows_window="frontier" under the fused path: ramp batching is
    host-disabled (the boundary shift is host-side) but levels still
    run as single fused dispatches; counts stay exact."""
    m = CompactionModel(pe.SHIPPED_CFG)
    r = DeviceChecker(
        m, sub_batch=256, visited_cap=1 << 16,
        rows_window="frontier", row_cap_states=1 << 13,
    ).run()
    assert r.distinct_states == 45198
    assert r.diameter == 20
    assert not r.truncated
