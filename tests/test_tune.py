"""Self-tuning checker tests (ISSUE r15, tune/).

- the knob SPACE enumerates validity-pruned candidates, defaults
  first;
- the PREDICT stage ranks by the calibrated cost model (dispatch
  overhead and probe-schedule scaling move ranks the right way);
- PROFILES round-trip, resolve by config signature, and are
  warned-and-ignored when corrupt / version-mismatched / renamed /
  cross-config — the engine always falls back to defaults, never
  crashes, and a profile written for one config-sig is NEVER applied
  to another;
- the ENGINE resolves profiles at construction (explicit knobs win),
  records ``profile_sig`` on the v8 run header, and discovery order
  is state-for-state identical under tuned profiles AND online
  adaptation — pinned on both published compaction bug oracles;
- the ONLINE controller nudges only within its declared bounds, at
  dispatch boundaries, with every change a telemetry ``tune`` event;
- the DAEMON prewarms tuned knobs: a warm submit against a profiled
  key pays zero jit compiles (the r10/r13 ``set(ck._jits)`` harness)
  and its slice headers carry the profile sig;
- the LEDGER splits tuned vs default trajectories (``profile_sig``
  on records, gate ``--profile none`` = the "tuning never regresses"
  check against the pinned machine-independent keys);
- ``cli.py tune`` runs the whole predict -> measure -> persist loop
  end-to-end and the written profile resolves back into the engine.
"""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.bookkeeper import (
    BookkeeperConstants,
    BookkeeperModel,
)
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import ledger
from pulsar_tlaplus_tpu.obs import telemetry
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.tune import online, predict, profiles, space
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINNED = os.path.join(
    ROOT, "tests", "data", "mini_bench_producer_on.jsonl"
)
BK_KW = dict(sub_batch=256, visited_cap=1 << 12, frontier_cap=1 << 10)


@pytest.fixture(autouse=True)
def _isolated_profiles(tmp_path, monkeypatch):
    """Every test gets its own empty profile store — a stray
    ~/.ptt_profiles must never shape test runs."""
    monkeypatch.setenv(
        profiles.TUNE_DIR_ENV, str(tmp_path / "profiles")
    )
    monkeypatch.delenv(online.ADAPT_ENV, raising=False)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bk_model():
    return BookkeeperModel(BookkeeperConstants())


def _bk_profile(knobs, model=None, invariants=None, **over):
    """Write a profile keyed for the shipped-bookkeeper test config
    and return (sig, profile)."""
    m = model or _bk_model()
    invs = (
        invariants
        if invariants is not None
        else tuple(m.default_invariants)
    )
    sig = profiles.profile_key(
        model=m, invariants=invs, engine="device_bfs"
    )
    prof = profiles.build(
        sig=sig, engine="device_bfs", backend="cpu", knobs=knobs,
        spec="bookkeeper", **over,
    )
    profiles.save(prof)
    return sig, prof


# ---- knob space ------------------------------------------------------


def test_space_defaults_first_and_validity_pruned():
    m = _bk_model()
    cands = space.candidates(m, base_sub_batch=8192)
    assert cands[0] == {}  # the baseline the winner must beat
    assert space.describe(cands[0]) == "defaults"
    assert len(cands) > 100
    for c in cands:
        g = c.get("sub_batch", 8192)
        ff = c.get("flush_factor", 1)
        # the engine's int32 flat-addressing constraint holds for
        # every enumerated candidate
        assert g * m.A * ff * m.layout.W < 1 << 31
    # sub_batch multipliers resolve to powers of two
    subs = {c["sub_batch"] for c in cands if "sub_batch" in c}
    assert subs and all(s & (s - 1) == 0 for s in subs)
    # limit caps enumeration
    assert len(space.candidates(m, limit=7)) == 7


# ---- prediction ------------------------------------------------------


def _ref(levels, sub_batch=2048):
    return {
        "backend": "cpu",
        "work": {
            "expand_rows": 50_000, "probe_lanes": 400_000,
            "compact_elems": 120_000, "append_rows": 45_000,
        },
        "level_sizes": levels,
        "distinct_states": sum(levels),
        "sub_batch": sub_batch,
        "fuse_group": 8,
        "flush_factor": 1,
        "group": 4,
        "A": 22,
        "dense_rounds": 4,
        "stages": ((4, 16), (16, 64)),
        "avg_probe_rounds": 3.0,
        "wall_s": 1.0,
    }


def test_predict_dispatch_overhead_ranks_fuse_group():
    """A long ramp makes fuse_group=1 strictly more expensive than
    fuse_group=16 — the overhead term the megakernel exists for."""
    ref = _ref([10, 20, 40, 80, 160, 300, 700, 1500], sub_batch=2048)
    p1 = predict.predict_candidate({"fuse_group": 1}, ref)
    p16 = predict.predict_candidate({"fuse_group": 16}, ref)
    assert p1["dispatches"] > p16["dispatches"]
    assert p1["est_s"] > p16["est_s"]


def test_predict_probe_schedule_scales_lanes():
    """Fewer dense rounds present fewer full-width probe lanes (the
    work the adaptation loop watches); more dense rounds present
    more."""
    ref = _ref([100, 400, 1000])
    # observed probe depth must exceed the dense rounds under test:
    # raising dense ABOVE the depth lanes actually reach changes
    # nothing (and the model is right to say so)
    ref["avg_probe_rounds"] = 6.0
    base = predict.predict_candidate({}, ref)
    lo = predict.predict_candidate({"fpset_dense_rounds": 2}, ref)
    hi = predict.predict_candidate({"fpset_dense_rounds": 8}, ref)
    assert lo["est_work"]["probe_lanes"] < base["est_work"]["probe_lanes"]
    assert hi["est_work"]["probe_lanes"] > base["est_work"]["probe_lanes"]
    # state-determined work never moves
    for k in ("expand_rows", "append_rows", "compact_elems"):
        assert lo["est_work"][k] == ref["work"][k]


def test_predict_rank_orders_by_cost():
    ref = _ref([10, 20, 40, 80])
    ranked = predict.rank(
        [{}, {"fuse_group": 1}, {"fuse_group": 16}], ref
    )
    costs = [p["est_s"] for _c, p in ranked]
    assert costs == sorted(costs)


# ---- profile lifecycle ----------------------------------------------


def test_profile_roundtrip_and_key_identity():
    sig, prof = _bk_profile({"fuse_group": 2, "sub_batch": 512})
    assert profiles.load(sig)["knobs"]["fuse_group"] == 2
    # key is stable across model instances with equal constants...
    assert sig == profiles.profile_key(
        model=_bk_model(),
        invariants=tuple(_bk_model().default_invariants),
        engine="device_bfs",
    )
    # ...and differs across constants, invariant sets, and engines
    other = BookkeeperModel(BookkeeperConstants(entry_limit=3))
    assert sig != profiles.profile_key(
        model=other, invariants=tuple(other.default_invariants),
        engine="device_bfs",
    )
    assert sig != profiles.profile_key(
        model=_bk_model(), invariants=("TypeOK",),
        engine="device_bfs",
    )
    assert sig != profiles.profile_key(
        model=_bk_model(),
        invariants=tuple(_bk_model().default_invariants),
        engine="liveness",
    )


def test_corrupt_stale_and_mismatched_profiles_warned_and_ignored(
    capsys,
):
    """Every bad-profile mode degrades to defaults with a stderr
    note — never a crash, never a silently-applied wrong profile."""
    sig, prof = _bk_profile({"fuse_group": 2})
    path = profiles.path_for(sig)

    # corrupt JSON
    with open(path, "w") as f:
        f.write("{not json")
    assert profiles.load(sig) is None
    assert "ignored" in capsys.readouterr().err

    # version mismatch
    stale = dict(prof, profile_v=profiles.PROFILE_VERSION + 1)
    with open(path, "w") as f:
        json.dump(stale, f)
    assert profiles.load(sig) is None
    assert "profile_v" in capsys.readouterr().err

    # wrong engine
    profiles.save(prof)
    assert profiles.load(sig, engine="liveness") is None
    assert "engine" in capsys.readouterr().err

    # a renamed/copied file never crosses config signatures
    other_sig = "0" * 16
    shutil.copy(path, profiles.path_for(other_sig))
    assert profiles.load(other_sig) is None
    assert "sig" in capsys.readouterr().err

    # the engine shrugs all of this off: corrupt file -> defaults
    with open(path, "w") as f:
        f.write("\x00garbage")
    ck = DeviceChecker(_bk_model(), profile="auto", **BK_KW)
    assert ck.profile_sig is None
    assert ck.G == 256 and ck.RMAX == 8  # untouched defaults


def test_profile_never_applied_to_another_config():
    sig, prof = _bk_profile({"fuse_group": 2})
    # same profile dict handed to a DIFFERENT config: refused
    m2 = CompactionModel(pe.SHIPPED_CFG)
    assert (
        profiles.resolve(
            prof, model=m2,
            invariants=tuple(pe.DEFAULT_INVARIANTS),
            engine="device_bfs",
        )
        is None
    )
    ck = DeviceChecker(
        m2, profile=prof, sub_batch=2048, visited_cap=1 << 16,
        frontier_cap=1 << 15,
    )
    assert ck.profile_sig is None and ck.RMAX == 8


def test_profile_validator_catches_unknown_knobs():
    sig, prof = _bk_profile({"fuse_group": 2})
    bad = dict(prof, knobs={"warp_drive": 11})
    errs = profiles.validate(bad)
    assert errs and "warp_drive" in errs[0]
    with pytest.raises(ValueError, match="warp_drive"):
        profiles.save(bad)


@pytest.mark.parametrize(
    "knob,value",
    [
        ("probe_impl", "tile"), ("expand_impl", "pallas"),
        ("sieve_impl", "tile"), ("compact_impl", "sort"),
    ],
)
def test_profile_naming_a_removed_knob_is_ignored(knob, value, capsys):
    """A profile on disk is input from outside the program, and earlier
    versions wrote the kernel selectors into it.  Such a file is
    refused whole, with a note that names the knob, and the run
    completes at the defaults."""
    m = _bk_model()
    sig = profiles.profile_key(
        model=m, invariants=tuple(m.default_invariants),
        engine="device_bfs",
    )
    prof = profiles.build(
        sig=sig, engine="device_bfs", backend="cpu",
        knobs={"fuse_group": 2}, spec="bookkeeper",
    )
    prof["knobs"][knob] = value
    path = profiles.path_for(sig)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(prof, f)
    ck = DeviceChecker(_bk_model(), profile="auto", **BK_KW)
    note = capsys.readouterr().err
    assert "tuned profile ignored" in note and knob in note
    assert ck.profile_sig is None and ck.profile_applied == ()
    assert ck.RMAX == 8  # the profile's fuse_group went with it
    r = ck.run()
    assert (r.distinct_states, r.diameter) == (297, 14)


# ---- engine resolution ----------------------------------------------


def test_engine_resolves_profile_and_explicit_knobs_win(tmp_path):
    sig, _prof = _bk_profile(
        {"fuse_group": 2, "sub_batch": 512, "fpset_dense_rounds": 2}
    )
    stream = str(tmp_path / "run.jsonl")
    ck = DeviceChecker(
        _bk_model(), profile="auto", telemetry=stream,
        visited_cap=1 << 12, frontier_cap=1 << 10,
    )
    assert ck.profile_sig == sig
    assert ck.G == 512 and ck.RMAX == 2 and ck.fps_dense == 2
    assert set(ck.profile_applied) == {
        "fuse_group", "sub_batch", "fpset_dense_rounds",
    }
    r = ck.run()
    assert (r.distinct_states, r.diameter) == (297, 14)  # pinned
    hd = [json.loads(x) for x in open(stream)][0]
    assert hd["event"] == "run_header"
    assert hd["profile_sig"] == sig
    assert hd["v"] == telemetry.SCHEMA_VERSION

    # explicit ctor knobs beat the profile, sig still attributes
    ck2 = DeviceChecker(
        _bk_model(), profile="auto", fuse_group=8, **BK_KW
    )
    assert ck2.profile_sig == sig
    assert ck2.RMAX == 8 and ck2.G == 256
    assert "fuse_group" not in ck2.profile_applied
    assert "sub_batch" not in ck2.profile_applied  # explicit BK_KW


def test_liveness_engine_resolves_its_own_profile(tmp_path):
    m = _bk_model()
    sig = profiles.profile_key(
        model=m, invariants=(), engine="liveness"
    )
    profiles.save(
        profiles.build(
            sig=sig, engine="liveness", backend="cpu",
            knobs={"sweep_group": 2}, spec="bookkeeper",
        )
    )
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    stream = str(tmp_path / "live.jsonl")
    lck = LivenessChecker(
        m, goal="Termination", fairness="wf_next", profile="auto",
        telemetry=stream,
    )
    assert lck.profile_sig == sig
    assert lck.sweep_group == 2
    r = lck.run()
    assert r.holds, r.reason
    headers = [
        json.loads(x)
        for x in open(stream)
        if '"run_header"' in x
    ]
    live_hd = [h for h in headers if h["engine"] == "liveness"]
    assert live_hd and live_hd[0]["profile_sig"] == sig


# ---- discovery-order differentials (the acceptance pins) ------------


TUNED_KNOBS = {
    "fuse_group": 2,
    "fpset_dense_rounds": 2,
    "flush_factor": 2,
    "group": 2,
}


@pytest.mark.parametrize(
    "invariant,depth",
    [("CompactedLedgerLeak", 12), ("DuplicateNullKeyMessage", 4)],
)
def test_tuned_and_adapted_bug_oracles_state_for_state(
    invariant, depth
):
    """Both published counterexamples: identical violation gid and
    identical replayed trace under (a) hand defaults, (b) a tuned
    profile moving every schedule knob, (c) online adaptation —
    tuning changes schedules and batching, never semantics."""
    kw = dict(
        invariants=(invariant,), sub_batch=2048,
        visited_cap=1 << 16, frontier_cap=1 << 15,
    )
    r_def = DeviceChecker(CompactionModel(pe.SHIPPED_CFG), **kw).run()
    m = CompactionModel(pe.SHIPPED_CFG)
    sig = profiles.profile_key(
        model=m, invariants=(invariant,), engine="device_bfs"
    )
    profiles.save(
        profiles.build(
            sig=sig, engine="device_bfs", backend="cpu",
            knobs=dict(TUNED_KNOBS, sub_batch=1024),
            spec="compaction",
        )
    )
    ck_t = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), profile="auto",
        invariants=(invariant,), visited_cap=1 << 16,
        frontier_cap=1 << 15,
    )
    assert ck_t.profile_sig == sig and ck_t.G == 1024
    r_tun = ck_t.run()
    r_ada = DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), adapt=True, **kw
    ).run()
    for r in (r_tun, r_ada):
        assert r.violation == r_def.violation == invariant
        assert r.violation_gid == r_def.violation_gid
        assert r.diameter == r_def.diameter == depth
        assert r.trace == r_def.trace
        assert r.trace_actions == r_def.trace_actions
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r_def.trace, r_def.trace_actions, invariant
    )


def test_online_adaptation_state_for_state_with_tune_events(tmp_path):
    """Adaptation on the producer_on oracle: identical states in the
    identical order (level sizes, packed rows, trace logs), every
    adjustment a bounded v8 ``tune`` event at a dispatch boundary."""
    c = SMALL_CONFIGS["producer_on"]
    kw = dict(sub_batch=512, visited_cap=1 << 13, frontier_cap=1 << 12)
    ck_a = DeviceChecker(CompactionModel(c), **kw)
    r_a = ck_a.run()
    stream = str(tmp_path / "adapt.jsonl")
    ck_b = DeviceChecker(
        CompactionModel(c), adapt=True, telemetry=stream, **kw
    )
    r_b = ck_b.run()
    assert r_b.distinct_states == r_a.distinct_states
    assert r_b.level_sizes == r_a.level_sizes
    nv, W = r_a.distinct_states, ck_a.W
    for key in ("parent", "lane"):
        assert (
            np.asarray(ck_b.last_bufs[key][:nv])
            == np.asarray(ck_a.last_bufs[key][:nv])
        ).all(), key
    assert (
        np.asarray(ck_b.last_bufs["rows"][: nv * W])
        == np.asarray(ck_a.last_bufs["rows"][: nv * W])
    ).all()
    evs = [json.loads(x) for x in open(stream)]
    assert evs[0]["adapt"] is True
    tunes = [e for e in evs if e["event"] == "tune"]
    # the controller moved at least one knob on this workload (the
    # shipped schedule's 4 dense rounds are oversized for a table
    # that never probes deep), and every move respected its bounds
    assert tunes
    for e in tunes:
        assert e["v"] == telemetry.SCHEMA_VERSION
        assert e["knob"] in (
            "fuse_cap", "fpset_dense_rounds",
        )
        if e["knob"] == "fuse_cap":
            assert 1 <= e["value"] <= ck_b.RMAX
        else:
            assert online.MIN_DENSE <= e["value"] <= online.MAX_DENSE
    assert ck_b.last_stats["tune_adjustments"] == len(tunes)
    # kill switch: PTT_TUNE_ADAPT=0 beats the explicit ctor flag
    os.environ[online.ADAPT_ENV] = "0"
    try:
        ck_c = DeviceChecker(CompactionModel(c), adapt=True, **kw)
        assert ck_c.adapt is False
    finally:
        del os.environ[online.ADAPT_ENV]


def test_online_controller_policy_bounds():
    ctl = online.OnlineController(8, 4, ((4, 16), (16, 64)))
    # two consecutive ramp early-exits shrink the cap to what ran
    assert not ctl.observe(
        levels_closed=3, cap_asked=8, max_probe_rounds=3
    )
    adjs = ctl.observe(levels_closed=3, cap_asked=8, max_probe_rounds=3)
    assert [a["knob"] for a in adjs] == ["fuse_cap"]
    assert ctl.fuse_cap == 3
    # two consecutive full batches double it back (bounded by rmax)
    ctl.observe(levels_closed=3, cap_asked=3, max_probe_rounds=3)
    adjs = ctl.observe(levels_closed=3, cap_asked=3, max_probe_rounds=3)
    assert ctl.fuse_cap == 6 and adjs
    # probe pressure doubles dense rounds ONCE per observed max: the
    # engine feeds a run-lifetime maximum, so repeating the same max
    # must not ratchet (each raise would re-jit the megakernel), and
    # calm can never lower a pressured controller (hysteresis)
    adjs = ctl.observe(
        levels_closed=1, cap_asked=1, max_probe_rounds=40
    )
    assert [a["knob"] for a in adjs] == ["fpset_dense_rounds"]
    assert ctl.dense == 8
    for _ in range(6):
        ctl.observe(levels_closed=1, cap_asked=1, max_probe_rounds=40)
    assert ctl.dense == 8
    # only a NEW high (genuinely deeper probing) escalates again
    adjs = ctl.observe(
        levels_closed=1, cap_asked=1, max_probe_rounds=55
    )
    assert ctl.dense == 16 and adjs
    # calm controller (fresh) lowers toward the floor, never below
    ctl2 = online.OnlineController(8, 4, ((4, 16), (16, 64)))
    for _ in range(8):
        ctl2.observe(levels_closed=1, cap_asked=1, max_probe_rounds=1)
    assert ctl2.dense == online.MIN_DENSE


# ---- schema v8 + validators -----------------------------------------


@pytest.fixture(scope="module")
def checker_mod():
    return _load_script("check_telemetry_schema")


def test_v8_stream_validates_and_profile_sig_required(
    tmp_path, checker_mod
):
    stream = str(tmp_path / "v8.jsonl")
    DeviceChecker(_bk_model(), telemetry=stream, **BK_KW).run()
    assert checker_mod.validate_stream(stream) == []
    evs = [json.loads(x) for x in open(stream)]
    assert evs[0]["profile_sig"] is None  # untuned: null, not absent
    # a v8 header WITHOUT the field fails; the same header at v7
    # stays clean (FIELD_SINCE gating — committed streams unaffected)
    del evs[0]["profile_sig"]
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    errs = checker_mod.validate_stream(bad)
    assert errs and "profile_sig" in errs[0]
    evs[0]["v"] = 7
    ok = str(tmp_path / "v7.jsonl")
    with open(ok, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    assert checker_mod.validate_stream(ok) == []


def test_tune_event_required_fields(tmp_path, checker_mod):
    stream = str(tmp_path / "adapt.jsonl")
    ck = DeviceChecker(
        _bk_model(), adapt=True, telemetry=stream, **BK_KW
    )
    ck.run()
    assert checker_mod.validate_stream(stream) == []
    evs = [json.loads(x) for x in open(stream)]
    tunes = [e for e in evs if e["event"] == "tune"]
    assert tunes  # bookkeeper's shallow table triggers the calm rule
    bad = dict(tunes[0])
    del bad["knob"]
    p = str(tmp_path / "bad.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps(evs[0]) + "\n")
        bad["seq"] = evs[0]["seq"] + 1
        f.write(json.dumps(bad) + "\n")
    errs = checker_mod.validate_stream(p)
    assert errs and "knob" in errs[0]


def test_profile_validator_front_end(tmp_path, checker_mod):
    sig, _prof = _bk_profile({"fuse_group": 4})
    path = profiles.path_for(sig)
    assert checker_mod.main([path, "--profile"]) == 0
    assert profiles.validate_file(path) == []
    # renamed copy: filename/sig disagreement is a violation
    rogue = str(tmp_path / ("f" * 16 + ".json"))
    shutil.copy(path, rogue)
    assert checker_mod.main([rogue, "--profile"]) == 1
    # unknown knob is a violation
    d = json.load(open(path))
    d["knobs"]["warp_drive"] = 1
    with open(path, "w") as f:
        json.dump(d, f)
    assert checker_mod.main([path, "--profile"]) == 1


# ---- ledger: tuned vs default context -------------------------------


def test_ledger_gate_with_tuning_enabled(tmp_path):
    """The acceptance pin: a tuned run gates CLEAN against the
    committed machine-independent baseline (tuning never regresses
    dispatches/level or work-units/state), records carry
    profile_sig, and ``gate --profile none`` is the tuned-vs-hand-
    defaults check."""
    from pulsar_tlaplus_tpu import cli

    # EXACTLY the pinned mini-bench shape (test_attribution._mk:
    # invariants=(), sub_batch=256) so the ledger config keys match
    c = SMALL_CONFIGS["producer_on"]
    m = CompactionModel(c)
    kw = dict(
        invariants=(), sub_batch=256, visited_cap=1 << 12,
        frontier_cap=1 << 12,
    )
    sig = profiles.profile_key(
        model=m, invariants=(), engine="device_bfs"
    )
    # schedule-only knobs: the dispatch economy must not regress
    profiles.save(
        profiles.build(
            sig=sig, engine="device_bfs", backend="cpu",
            knobs={"fpset_dense_rounds": 2, "group": 8},
            spec="compaction",
        )
    )
    stream = str(tmp_path / "tuned.jsonl")
    ck = DeviceChecker(m, profile="auto", telemetry=stream, **kw)
    assert ck.profile_sig == sig
    ck.run()
    rec = ledger.record_from_file(stream)
    assert rec["values"]["profile_sig"] == sig
    assert ledger.profile_of(rec) == sig

    path = str(tmp_path / "ledger.jsonl")
    shutil.copy(PINNED, path)
    rc = cli.main(["ledger", "--ledger", path, "add", stream])
    assert rc == 0
    # tuned current vs the UNTUNED pinned baseline on the
    # machine-independent keys: --profile none finds it and passes
    rc = cli.main(
        [
            "ledger", "--ledger", path, "gate",
            "--profile", "none", "--threshold", "0.1",
            "--keys", "dispatches_per_level", "work_units_per_state",
        ]
    )
    assert rc == 0
    # default context "same" has no tuned baseline yet: exit 2, not
    # a vacuous pass
    rc = cli.main(["ledger", "--ledger", path, "gate"])
    assert rc == 2
    # ...and once a tuned baseline exists, "same" gates against it
    stream2 = str(tmp_path / "tuned2.jsonl")
    DeviceChecker(
        CompactionModel(c), profile="auto", telemetry=stream2, **kw
    ).run()
    assert cli.main(["ledger", "--ledger", path, "add", stream2]) == 0
    rc = cli.main(
        [
            "ledger", "--ledger", path, "gate",
            "--keys", "dispatches_per_level", "work_units_per_state",
        ]
    )
    assert rc == 0
    # the trajectory table shows the profile column
    out = ledger.render_list(ledger.load(path))
    assert "profile_sig" in out and sig in out


# ---- daemon: warm tuned submits -------------------------------------


def test_daemon_warm_tuned_submit_zero_compiles(tmp_path):
    """The serving acceptance pin: the pool resolves the tuned
    profile at construction, prewarm compiles the TUNED programs,
    a warm submit adds zero jits, and the slice's run header carries
    profile_sig."""
    from pulsar_tlaplus_tpu.service import jobs as jobmod
    from pulsar_tlaplus_tpu.service.scheduler import (
        CheckerPool,
        Scheduler,
        ServiceConfig,
    )
    from pulsar_tlaplus_tpu.utils import cfg as cfgmod

    bk_cfg = os.path.join(ROOT, "specs", "bookkeeper.cfg")
    config = ServiceConfig(
        state_dir=str(tmp_path / "state"),
        sub_batch=256, visited_cap=1 << 8, frontier_cap=1 << 7,
        max_states=1 << 12, slice_s=30.0,
    )
    pool = CheckerPool(config)
    model = pool.build_model("bookkeeper", cfgmod.load(bk_cfg))
    invs = pool.resolve_invariants(
        "bookkeeper", cfgmod.load(bk_cfg), None
    )
    sig = profiles.profile_key(
        model=model, invariants=tuple(invs), engine="device_bfs"
    )
    profiles.save(
        profiles.build(
            sig=sig, engine="device_bfs", backend="cpu",
            knobs={"fuse_group": 4, "fpset_dense_rounds": 2},
            spec="bookkeeper",
        )
    )
    key, _compile_s = pool.warm("bookkeeper", bk_cfg)
    ck = pool._checkers[key]
    assert ck.profile_sig == sig  # tuned knobs were prewarmed
    assert ck.RMAX == 4 and ck.fps_dense == 2
    assert ck.adapt is False  # the pool pins adaptation off
    assert ck._jits
    keys_before = set(ck._jits)

    sched = Scheduler(config, pool=pool)
    job = sched.submit("bookkeeper", bk_cfg)
    sched.run_until_idle()
    assert job.state == jobmod.DONE
    assert job.result["status"] == "ok"
    assert job.result["distinct_states"] == 297  # pinned oracle
    assert set(ck._jits) == keys_before  # ZERO post-warm compiles
    evs = [
        json.loads(x)
        for x in open(os.path.join(job.dir, "events.jsonl"))
    ]
    hd = [e for e in evs if e["event"] == "run_header"][0]
    assert hd["profile_sig"] == sig


# ---- cli tune end-to-end --------------------------------------------


def test_cli_tune_end_to_end(tmp_path, capsys, checker_mod):
    """The whole loop: predict (full space, pruned), measure top-K
    interleaved min-of-2, persist — then the written profile
    resolves back into a fresh engine with the pinned count, and
    validates under the --profile schema mode."""
    from pulsar_tlaplus_tpu import cli

    rc = cli.main(
        [
            "tune", "bookkeeper",
            "--maxstates", "4096",
            "--visited-cap", "4096",
            "--frontier-cap", "2048",
            "--top-k", "1",
            "--repeat", "2",
            "--stream-dir", str(tmp_path / "streams"),
            "--ledger", str(tmp_path / "tune_ledger.jsonl"),
            "-cpu",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    # the report shows the predict-stage pruning: candidates
    # predicted vs measured, and a measured column
    assert "predicted 648 candidate(s)" in out.replace("\n", " ") or (
        "candidate" in out and "measured" in out
    )
    prof_files = os.listdir(profiles.profiles_dir())
    assert len(prof_files) == 1
    path = os.path.join(profiles.profiles_dir(), prof_files[0])
    assert checker_mod.main([path, "--profile"]) == 0
    prof = json.load(open(path))
    t = prof["tuner"]
    assert t["candidates_predicted"] > 100
    assert t["candidates_measured"] >= 2  # baseline + top-k
    # min-of-2 interleaved: the winner never loses to the baseline
    assert t["winner_s"] <= t["baseline_s"] + 1e-9
    # measured runs were ingested into the ledger
    recs = ledger.load(str(tmp_path / "tune_ledger.jsonl"))
    assert recs
    # the profile resolves back into a fresh engine
    from pulsar_tlaplus_tpu.models import registry
    from pulsar_tlaplus_tpu.utils import cfg as cfgmod

    tlc_cfg = cfgmod.load(os.path.join(ROOT, "specs", "bookkeeper.cfg"))
    model, _ = registry.COMPILED["bookkeeper"](tlc_cfg)
    ck = DeviceChecker(
        model, invariants=tuple(tlc_cfg.invariants), profile="auto",
        visited_cap=4096, frontier_cap=2048, max_states=4096,
    )
    assert ck.profile_sig == prof["sig"]
    r = ck.run()
    assert (r.distinct_states, r.diameter) == (297, 14)
