"""Incremental checking tests (r19, ``pulsar_tlaplus_tpu/warm/``).

The acceptance bar (ISSUE 15 / docs/incremental.md):

- a TRUNCATED job resubmitted at a widened budget **continues** from
  its warm artifact instead of restarting — distinct states, level
  sizes, verdict, violation gid, and full trace pinned equal to an
  uninterrupted cold run (both the clean compaction shape and the
  bookkeeper crash2 violation shape);
- a constant-widening **reseed** on subscription (MaxCrashTimes 2->3)
  is pinned warm-vs-cold state-for-state — exact reachable STATE-SET
  equality, not just counts;
- the **fallback matrix**: every non-reusable change (module edit,
  invariant change, non-widening binding change, narrowing, a bitlen
  layout step, digest tamper, version skew, torn artifact) plans/
  demotes COLD with its typed reason — never a wrong verdict;
- the robustness drills: ``kill@warmwrite`` mid-harvest (subprocess),
  ``torn@warmwrite``, and ``corrupt@warm`` all leave the daemon
  serving correct results with quarantined artifacts;
- satellites: sim-job admission pricing, ledger warm tagging + gate
  baseline scoping, the ``--warm`` validator flag, and the fuzz
  ``--widen`` fast drill.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models import registry
from pulsar_tlaplus_tpu.models.subscription import (
    SubscriptionConstants,
    SubscriptionModel,
)
from pulsar_tlaplus_tpu.obs import ledger
from pulsar_tlaplus_tpu.obs import metrics as metrics_mod
from pulsar_tlaplus_tpu.obs import report
from pulsar_tlaplus_tpu.service import admission as admmod
from pulsar_tlaplus_tpu.service import jobs as jobmod
from pulsar_tlaplus_tpu.service.scheduler import (
    CheckerPool,
    Scheduler,
    ServiceConfig,
)
from pulsar_tlaplus_tpu.utils import cfg as cfgmod
from pulsar_tlaplus_tpu.utils import faults
from pulsar_tlaplus_tpu.warm import plan as warm_plan
from pulsar_tlaplus_tpu.warm import store as warm_store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GEOM = dict(
    sub_batch=64,
    visited_cap=1 << 10,
    frontier_cap=1 << 8,
    max_states=1 << 20,
    checkpoint_every=1,
)

SMALL_COMPACTION_CFG = """
CONSTANTS
    MessageSentLimit = 2
    CompactionTimesLimit = 2
    ModelConsumer = FALSE
    ConsumeTimesLimit = 2
    KeySpace = {1}
    ValueSpace = {1}
    RetainNullKey = TRUE
    MaxCrashTimes = 1
    ModelProducer = TRUE
SPECIFICATION Spec
INVARIANTS
"""

BK_CRASH2_CFG = """
CONSTANTS
    NumBookies = 3
    WriteQuorum = 2
    AckQuorum = 2
    EntryLimit = 2
    MaxBookieCrashes = 2
SPECIFICATION Spec
INVARIANTS
    ConfirmedEntryReadable
"""

SUB_CFG = """
CONSTANTS
    MessageLimit = 2
    MaxCrashTimes = 2
SPECIFICATION Spec
INVARIANTS
"""

# the declared-monotone widening: MaxCrashTimes 2 -> 3 keeps
# bitlen(2) == bitlen(3) == 2, so the packed layout is bit-identical
SUB_CFG_WIDE = SUB_CFG.replace("MaxCrashTimes = 2", "MaxCrashTimes = 3")
# a NARROWING of the same axis (the planner must refuse)
SUB_CFG_NARROW = SUB_CFG.replace(
    "MaxCrashTimes = 2", "MaxCrashTimes = 1"
)
# a non-axis binding change (MessageLimit sizes the layout)
SUB_CFG_OTHER = SUB_CFG.replace("MessageLimit = 2", "MessageLimit = 3")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def checker_mod():
    return _load_script("check_telemetry_schema")


@pytest.fixture(scope="module")
def cfg_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("warm_cfgs")
    (d / "small_compaction.cfg").write_text(SMALL_COMPACTION_CFG)
    (d / "bk_crash2.cfg").write_text(BK_CRASH2_CFG)
    (d / "sub.cfg").write_text(SUB_CFG)
    (d / "sub_wide.cfg").write_text(SUB_CFG_WIDE)
    (d / "sub_narrow.cfg").write_text(SUB_CFG_NARROW)
    (d / "sub_other.cfg").write_text(SUB_CFG_OTHER)
    return d


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    config = ServiceConfig(
        state_dir=str(tmp_path_factory.mktemp("warm_pool")), **GEOM
    )
    return CheckerPool(config)


def _sched(state_dir, pool, **kw):
    base = dict(GEOM)
    base.update(kw)
    config = ServiceConfig(state_dir=str(state_dir), **base)
    return Scheduler(config, pool=pool), config


def _solo(pool, spec, cfg_path, max_states=None):
    tlc = cfgmod.load(str(cfg_path))
    invs = pool.resolve_invariants(spec, tlc, None)
    _k, ck = pool.get(spec, tlc, invs, max_states)
    return ck.run()


def _validate_streams(checker_mod, paths):
    errors = []
    for p in paths:
        if os.path.exists(p):
            errors += checker_mod.validate_stream(p)
    return errors


# ---- the continue fast path -----------------------------------------


def test_truncated_resubmit_continues_clean_shape(
    tmp_path, pool, cfg_dir, checker_mod
):
    """THE acceptance pin: a truncated producer_on-shape job
    resubmitted at a widened state budget CONTINUES from its warm
    artifact — distinct states, level sizes, diameter, and verdict
    pinned equal to an uninterrupted cold run."""
    sched, config = _sched(tmp_path / "state", pool)
    cfg = str(cfg_dir / "small_compaction.cfg")
    j1 = sched.submit("compaction", cfg, max_states=600)
    assert (j1.warm_mode, j1.warm_reason) == (
        "cold", warm_plan.REASON_NO_ARTIFACT
    )
    sched.run_until_idle()
    assert j1.result["status"] == "truncated"
    assert j1.result["distinct_states"] == 600
    # the truncation frame became a digest-verified warm artifact
    entries = [
        d for d in os.listdir(config.warm_dir)
        if d != "quarantine" and not d.startswith(".")
    ]
    assert len(entries) == 1
    ok, why = sched.warm_store.verify(
        os.path.join(config.warm_dir, entries[0])
    )
    assert ok, why

    j2 = sched.submit("compaction", cfg, max_states=GEOM["max_states"])
    assert (j2.warm_mode, j2.warm_reason) == ("continue", "sig_match")
    sched.run_until_idle()
    solo = _solo(pool, "compaction", cfg, GEOM["max_states"])
    assert j2.result["status"] == "ok"
    assert j2.result["warm"] == "continue"
    assert j2.result["distinct_states"] == solo.distinct_states == 1654
    assert j2.result["diameter"] == solo.diameter == 16
    assert j2.result["level_sizes"] == [
        int(x) for x in solo.level_sizes
    ]
    assert j2.result["violation"] is None
    # warm attribution on the continued slice's engine run header
    # (filter to j2's OWN run ids: the pooled checker's stale
    # telemetry path also routes the solo baseline's header here)
    headers = []
    with open(j2.events_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("event") == "run_header" and (
                rec.get("run_id") in j2.run_ids
            ):
                headers.append(rec)
    assert headers and all(h["warm"] == "continue" for h in headers)
    assert headers[0]["resume"] is True  # continued, not restarted
    # streams v12-validator-clean (daemon + both jobs)
    assert _validate_streams(
        checker_mod,
        [config.telemetry_path, j1.events_path, j2.events_path],
    ) == []
    assert sched.warm_counts[("continue", "sig_match")] == 1

    # the spec-CI cache hit: resubmitting the identical COMPLETED job
    # continues from the final frame — the frontier is empty, so the
    # identical verdict returns without re-expanding a single state
    j3 = sched.submit("compaction", cfg, max_states=GEOM["max_states"])
    assert j3.warm_mode == "continue"
    sched.run_until_idle()
    for k in ("status", "distinct_states", "diameter", "level_sizes"):
        assert j3.result[k] == j2.result[k]

    # ptt_warm_* from the live scheduler counters
    text = metrics_mod.render_exposition(
        metrics_mod.scheduler_metrics(sched)
    )
    assert 'ptt_warm_cold_total{reason="no_artifact"} 1' in text
    assert 'ptt_warm_hit_total{reason="sig_match"} 2' in text
    assert "ptt_warm_cache_bytes" in text

    # ---- the VIOLATION half of the pin, same resident daemon:
    # bookkeeper crash2 truncated BEFORE its ConfirmedEntryReadable
    # counterexample is reachable, then resubmitted at the full
    # budget — violation, violation_gid, and the full 9-state trace
    # pinned equal to the cold run
    bk = str(cfg_dir / "bk_crash2.cfg")
    b1 = sched.submit("bookkeeper", bk, max_states=150)
    sched.run_until_idle()
    assert b1.result["status"] == "truncated"
    assert b1.result["violation"] is None

    b2 = sched.submit("bookkeeper", bk)
    assert b2.warm_mode == "continue"
    sched.run_until_idle()
    solo_bk = _solo(pool, "bookkeeper", bk)
    assert solo_bk.violation == "ConfirmedEntryReadable"
    assert b2.result["status"] == "violation"
    assert b2.result["violation"] == solo_bk.violation
    assert b2.result["violation_gid"] == solo_bk.violation_gid == 305
    assert b2.result["trace"] == [repr(s) for s in solo_bk.trace]
    assert b2.result["trace_actions"] == list(solo_bk.trace_actions)
    # a violation run is NEVER harvested: the bookkeeper artifact is
    # still b1's truncation frame, not a verdict-bearing one
    bk_mans = [
        m for _d, m in sched.warm_store.manifests()
        if m["spec"] == "bookkeeper"
    ]
    assert len(bk_mans) == 1
    assert bk_mans[0]["truncated"] is True
    assert bk_mans[0]["distinct_states"] == b1.result[
        "distinct_states"
    ]


# ---- the reseed path ------------------------------------------------


def _rows_set(ck, n):
    W = int(ck.model.layout.W)
    rows = np.asarray(ck.last_bufs["rows"])[: n * W].reshape(n, W)
    return rows[np.lexsort(rows.T[::-1])]


def test_reseed_widening_pinned_state_for_state(
    tmp_path, pool, cfg_dir, base_artifact
):
    """The reseed acceptance pin (subscription MaxCrashTimes 2->3,
    bitlen-stable): the daemon plans reseed across the widening, and
    a standalone reseed through the same planner/seed machinery pins
    exact reachable STATE-SET equality against a cold run."""
    # standalone set-equality half (reuses the module base artifact)
    _store, adir, _ck, invs, r_old = base_artifact
    man = _store.load_manifest(adir)
    c_new = SubscriptionConstants(message_limit=2, max_crash_times=3)
    m_new = SubscriptionModel(c_new)
    seed, info = warm_plan.build_reseed_seed(
        adir, man, m_new, {"MaxCrashTimes": (2, 3)}
    )
    assert info["replay_rows"] >= 1
    assert info["reused_rows"] >= 1
    assert info["reused_rows"] + info["replay_rows"] == (
        r_old.distinct_states
    )
    ck_warm = DeviceChecker(m_new, invariants=invs, **GEOM_ENGINE)
    ck_warm.extra_trace_depth = len(r_old.level_sizes)
    r_warm = ck_warm.run(seed=seed)
    ck_cold = DeviceChecker(m_new, invariants=invs, **GEOM_ENGINE)
    r_cold = ck_cold.run()
    assert r_warm.violation is None and r_cold.violation is None
    assert r_warm.distinct_states == r_cold.distinct_states
    assert np.array_equal(
        _rows_set(ck_warm, r_warm.distinct_states),
        _rows_set(ck_cold, r_cold.distinct_states),
    )

    # daemon half: the scheduler plans + installs the same reseed
    sched, _config = _sched(tmp_path / "state", pool)
    j1 = sched.submit("subscription", str(cfg_dir / "sub.cfg"))
    sched.run_until_idle()
    assert j1.result["status"] == "ok"

    j2 = sched.submit("subscription", str(cfg_dir / "sub_wide.cfg"))
    assert j2.warm_mode == "reseed"
    assert j2.warm_reason == "widened:MaxCrashTimes"
    assert j2.warm_widened == {"MaxCrashTimes": [2, 3]}
    sched.run_until_idle()
    assert j2.result["status"] == "ok"
    assert j2.result["warm"] == "reseed"
    # the reachable COUNT is engine-shape-independent: the daemon's
    # reseed agrees with the standalone cold run above
    assert j2.result["distinct_states"] == r_cold.distinct_states
    assert sched.warm_counts[
        ("reseed", "widened:MaxCrashTimes")
    ] == 1


GEOM_ENGINE = dict(
    sub_batch=64, visited_cap=1 << 10, frontier_cap=1 << 8,
    max_states=1 << 18,
)


# ---- the fallback matrix --------------------------------------------


@pytest.fixture(scope="module")
def base_artifact(tmp_path_factory):
    """ONE real subscription artifact shared by the matrix/validator
    tests — each consumer copies the store dir and forges what it
    needs (one engine run instead of fifteen)."""
    root = tmp_path_factory.mktemp("warm_base")
    c = SubscriptionConstants(message_limit=2, max_crash_times=2)
    m = SubscriptionModel(c)
    invs = tuple(m.default_invariants)
    frame = str(root / "frame.npz")
    ck = DeviceChecker(
        m, invariants=invs, checkpoint_path=frame, **GEOM_ENGINE
    )
    ck.final_frame = True
    r = ck.run()
    store = warm_store.WarmStore(str(root / "store"))
    man = warm_plan.manifest_for(
        "subscription", {"MessageLimit": 2, "MaxCrashTimes": 2},
        invs, ck,
        {
            "distinct_states": r.distinct_states,
            "levels": len(r.level_sizes),
            "truncated": False, "stop_reason": None,
        },
    )
    adir = store.save(frame, man)
    assert adir and store.verify(adir)[0]
    return store, adir, ck, invs, r


def _copy_store(base_artifact, dst):
    """A private mutable copy of the base artifact's store."""
    store, adir, ck, invs, r = base_artifact
    shutil.copytree(store.root, str(dst))
    new_store = warm_store.WarmStore(str(dst))
    new_adir = os.path.join(str(dst), os.path.basename(adir))
    return new_store, new_adir, ck, invs


def _replan(store, ck, invs, constants, **over):
    kw = dict(
        spec="subscription",
        constants=constants,
        invariants=invs,
        config_sig=ck._config_sig(),
        module_digest=registry.module_digest("subscription"),
        lsig=warm_plan.layout_sig(ck.model),
        n_initial=int(ck.model.n_initial),
        max_states=1 << 18,
        check_deadlock=True,
    )
    kw.update(over)
    return warm_plan.plan(store, **kw)


def _rewrite_manifest(store, adir, **mutations):
    """Forge manifest fields, keeping the file digests valid (the
    planner reads manifests; only verify() checks content digests)."""
    man = store.load_manifest(adir)
    man.update(mutations)
    with open(os.path.join(adir, warm_store.MANIFEST), "w") as f:
        json.dump(man, f)


def test_fallback_matrix_table_driven(tmp_path, base_artifact):
    """Satellite: (change kind) x (expected mode/reason), enumerated.
    Every non-reusable change must plan COLD with its typed reason —
    the planner never guesses.  ``incoming_sig`` stands in for the
    changed model's engine config signature (any binding or module
    change changes the real one)."""
    base = {"MessageLimit": 2, "MaxCrashTimes": 2}
    wide = {"MessageLimit": 2, "MaxCrashTimes": 3}
    other = "incoming-changed-config-sig"
    cases = [
        # (name, manifest mutations, incoming constants,
        #  incoming config_sig override, want mode, want reason)
        ("identical", {}, base, None, "continue", "sig_match"),
        (
            "widening", {}, wide, other,
            "reseed", "widened:MaxCrashTimes",
        ),
        (
            "module_edit", {"module_digest": "deadbeef"}, wide, other,
            "cold", warm_plan.REASON_MODULE_EDIT,
        ),
        (
            # a re-guarded action keeps the config signature (it
            # identifies the model by name + bindings, not source):
            # the SOURCE digest alone must block the continue path
            "module_edit_same_sig", {"module_digest": "deadbeef"},
            base, None, "cold", warm_plan.REASON_MODULE_EDIT,
        ),
        (
            "invariant_change", {"invariants": ["SomethingElse"]},
            wide, other, "cold", warm_plan.REASON_INVARIANT_CHANGE,
        ),
        (
            "non_axis_binding", {},
            {"MessageLimit": 3, "MaxCrashTimes": 2}, other,
            "cold", warm_plan.REASON_BINDING_CHANGE,
        ),
        (
            "narrowing", {},
            {"MessageLimit": 2, "MaxCrashTimes": 1}, other,
            "cold", warm_plan.REASON_NARROWED,
        ),
        (
            "layout_step", {"layout_sig": "other-layout"}, wide,
            other, "cold", warm_plan.REASON_LAYOUT_CHANGE,
        ),
        (
            "init_change", {"n_initial": 99}, wide, other,
            "cold", warm_plan.REASON_INIT_CHANGE,
        ),
        (
            "rows_windowed", {"rows_all": False}, wide, other,
            "cold", warm_plan.REASON_ROWS,
        ),
        (
            "budget_narrowed_reseed",
            {"distinct_states": (1 << 18) + 1}, wide, other,
            "cold", warm_plan.REASON_BUDGET,
        ),
        (
            "deadlock_config", {"check_deadlock": False}, wide,
            other, "cold", warm_plan.REASON_ENGINE_CONFIG,
        ),
        (
            "engine_config_same_bindings", {}, base, other,
            "cold", warm_plan.REASON_ENGINE_CONFIG,
        ),
    ]
    for name, mut, constants, sig_over, want_mode, want_reason in cases:
        store, adir, ck, invs = _copy_store(
            base_artifact, tmp_path / name
        )
        if mut:
            _rewrite_manifest(store, adir, **mut)
        over = {"config_sig": sig_over} if sig_over else {}
        p = _replan(store, ck, invs, constants, **over)
        assert (p.mode, p.reason) == (want_mode, want_reason), (
            f"{name}: got {p.mode}/{p.reason}, want "
            f"{want_mode}/{want_reason}"
        )

    # budget narrowed below the artifact's states: CONTINUE refused
    store, adir, ck, invs = _copy_store(base_artifact, tmp_path / "bud")
    man = store.load_manifest(adir)
    p = _replan(
        store, ck, invs, base,
        max_states=int(man["distinct_states"]) - 1,
    )
    assert (p.mode, p.reason) == ("cold", warm_plan.REASON_BUDGET)

    # version skew: a newer warm_v is refused as torn/unreadable
    store, adir, ck, invs = _copy_store(base_artifact, tmp_path / "ver")
    _rewrite_manifest(store, adir, warm_v=warm_store.WARM_VERSION + 1)
    p = _replan(store, ck, invs, base)
    assert p.mode == "cold"
    assert p.reason in (
        warm_plan.REASON_TORN, warm_plan.REASON_NO_ARTIFACT
    )

    # torn manifest (half-written file) -> unreadable -> cold, and
    # the startup sweep quarantines it
    store, adir, ck, invs = _copy_store(
        base_artifact, tmp_path / "torn"
    )
    mpath = os.path.join(adir, warm_store.MANIFEST)
    blob = open(mpath).read()
    with open(mpath, "w") as f:
        f.write(blob[: len(blob) // 2])
    p = _replan(store, ck, invs, base)
    assert p.mode == "cold"
    assert store.sweep()  # quarantined
    assert not os.path.isdir(adir)
    assert os.listdir(store.quarantine_dir)

    # digest tamper: verify() fails (the install-time gate)
    store, adir, ck, invs = _copy_store(
        base_artifact, tmp_path / "tamper"
    )
    fpath = os.path.join(adir, warm_store.FRAME)
    raw = bytearray(open(fpath, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(fpath, "wb") as f:
        f.write(bytes(raw))
    ok, why = store.verify(adir)
    assert not ok and why.startswith(warm_plan.REASON_DIGEST)


# ---- robustness drills ----------------------------------------------


def test_corrupt_warm_demotes_to_cold_with_parity(
    tmp_path, pool, cfg_dir
):
    """``corrupt@warm:N``: the install-time digest verification
    computes a corrupted digest — the job demotes to a full cold
    recheck (typed reason, quarantined artifact) and the verdict
    still equals the solo run."""
    sched, config = _sched(tmp_path / "state", pool)
    cfg = str(cfg_dir / "small_compaction.cfg")
    j1 = sched.submit("compaction", cfg, max_states=600)
    sched.run_until_idle()
    assert j1.result["status"] == "truncated"
    prev = os.environ.get("PTT_FAULT")
    os.environ["PTT_FAULT"] = (
        f"corrupt@warm:{sched.warm_store._verify_n + 1}"
    )
    faults.reset()
    try:
        j2 = sched.submit("compaction", cfg)
        assert j2.warm_mode == "continue"  # the plan trusts the store
        sched.run_until_idle()
    finally:
        if prev is None:
            os.environ.pop("PTT_FAULT", None)
        else:
            os.environ["PTT_FAULT"] = prev
        faults.reset()
    assert j2.warm_mode == "cold"
    assert j2.warm_reason == warm_plan.REASON_DIGEST
    assert j2.result["warm"] == "cold"
    assert j2.result["warm_reason"] == warm_plan.REASON_DIGEST
    solo = _solo(pool, "compaction", cfg, GEOM["max_states"])
    assert j2.result["distinct_states"] == solo.distinct_states
    assert j2.result["level_sizes"] == [
        int(x) for x in solo.level_sizes
    ]
    assert os.listdir(sched.warm_store.quarantine_dir)
    assert sched.warm_counts[("cold", warm_plan.REASON_DIGEST)] == 1


def test_torn_warmwrite_artifact_quarantined(tmp_path, pool, cfg_dir):
    """``torn@warmwrite:N``: the harvest publishes half a manifest —
    the artifact is unreadable, the next submit plans cold, and the
    startup sweep quarantines the torn dir."""
    sched, config = _sched(tmp_path / "state", pool)
    cfg = str(cfg_dir / "small_compaction.cfg")
    prev = os.environ.get("PTT_FAULT")
    os.environ["PTT_FAULT"] = "torn@warmwrite:1"
    faults.reset()
    try:
        j1 = sched.submit("compaction", cfg, max_states=600)
        sched.run_until_idle()
    finally:
        if prev is None:
            os.environ.pop("PTT_FAULT", None)
        else:
            os.environ["PTT_FAULT"] = prev
        faults.reset()
    assert j1.result["status"] == "truncated"  # job unaffected
    j2 = sched.submit("compaction", cfg)
    assert j2.warm_mode == "cold"
    assert j2.warm_reason in (
        warm_plan.REASON_NO_ARTIFACT, warm_plan.REASON_TORN
    )
    # a freshly constructed store (daemon restart) quarantines it
    store2 = warm_store.WarmStore(config.warm_dir)
    assert store2.sweep()
    assert os.listdir(store2.quarantine_dir)


def test_kill_mid_warm_write_subprocess_drill(tmp_path, cfg_dir):
    """THE mid-harvest crash drill: ``kill@warmwrite:1`` hard-kills
    the daemon process between the artifact's frame copy and its
    manifest publish.  The restarted scheduler's startup sweep
    quarantines the manifest-less dir, the resubmit plans an honest
    cold recheck, and the verdict is still exact."""
    state = tmp_path / "state"
    driver = f"""
import os, sys
sys.path.insert(0, {ROOT!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PTT_FAULT"] = "kill@warmwrite:1"
from pulsar_tlaplus_tpu.service.scheduler import Scheduler, ServiceConfig
config = ServiceConfig(state_dir={str(state)!r}, **{GEOM!r})
sched = Scheduler(config)
sched.submit("compaction", {str(cfg_dir / "small_compaction.cfg")!r},
             max_states=600)
sched.run_until_idle()
print("UNREACHED")  # the kill fires inside the harvest
"""
    proc = subprocess.run(
        [sys.executable, "-c", driver],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 137, (proc.stdout, proc.stderr)
    assert "UNREACHED" not in proc.stdout
    # the artifact dir exists but has no manifest (frame copied, kill
    # before publish): a fresh scheduler quarantines it at startup
    config = ServiceConfig(state_dir=str(state), **GEOM)
    leftovers = [
        d for d in os.listdir(config.warm_dir)
        if d != "quarantine" and not d.startswith(".")
    ]
    assert leftovers  # the torn dir is there...
    sched = Scheduler(config)
    sched.recover()
    assert [
        d for d in os.listdir(config.warm_dir)
        if d != "quarantine" and not d.startswith(".")
    ] == []  # ...and swept into quarantine
    assert os.listdir(sched.warm_store.quarantine_dir)
    j = sched.submit(
        "compaction", str(cfg_dir / "small_compaction.cfg")
    )
    assert (j.warm_mode, j.warm_reason) == (
        "cold", warm_plan.REASON_NO_ARTIFACT
    )


def test_no_warm_opt_out(tmp_path, pool, cfg_dir):
    """--no-warm: neither reuse nor harvest."""
    sched, config = _sched(tmp_path / "state", pool)
    cfg = str(cfg_dir / "small_compaction.cfg")
    j1 = sched.submit("compaction", cfg, max_states=600, warm=False)
    assert (j1.warm_mode, j1.warm_reason) == (
        "cold", warm_plan.REASON_OPT_OUT
    )
    sched.run_until_idle()
    assert [
        d for d in os.listdir(config.warm_dir)
        if d != "quarantine" and not d.startswith(".")
    ] == []  # no artifact harvested
    j2 = sched.submit("compaction", cfg, warm=False)
    assert j2.warm_reason == warm_plan.REASON_OPT_OUT


def test_warm_store_lru_byte_cap(tmp_path, base_artifact):
    """--warm-max-bytes: oldest-touched artifacts evict past the cap
    (mtime LRU)."""
    store, adir, _ck, _invs = _copy_store(base_artifact, tmp_path / "s")
    nbytes = store.entry_bytes(adir)
    # a second entry under a forged sig key, with the first made OLD
    dst = os.path.join(store.root, "ffffffffffffffff")
    shutil.copytree(adir, dst)
    os.utime(os.path.join(adir, warm_store.MANIFEST), (1, 1))
    store.max_bytes = nbytes + 10  # room for ONE artifact
    assert store.enforce_cap() == 1
    assert not os.path.isdir(adir)  # oldest-touched evicted
    assert os.path.isdir(dst)
    store.max_bytes = 0  # 0 = the layer is off, cap never enforced
    assert store.enforce_cap() == 0


# ---- satellites -----------------------------------------------------


def test_sim_admission_priced_from_walk_budget(tmp_path, pool, cfg_dir):
    """Satellite (r18 NOTE): a sim job prices at its ACTUAL step/walk
    budget, not the BFS default max_states."""
    assert admmod.state_price(None, "check", None, 500) == 500
    assert admmod.state_price(1000, "check", None, 500) == 1000
    assert admmod.state_price(
        None, "simulate", {"n_walkers": 16, "depth": 64}, 10**9
    ) == 16 * 65
    assert admmod.state_price(
        None, "simulate", {"max_steps": 4096}, 10**9
    ) == 4096
    # end to end through the scheduler door: the quota admits the
    # small sim job where a default-priced BFS job is rejected
    sched, _config = _sched(
        tmp_path / "state", pool, tenant_max_states=10_000
    )
    cfg = str(cfg_dir / "small_compaction.cfg")
    js = sched.submit(
        "compaction", cfg, tenant="alpha", mode="simulate",
        sim={"n_walkers": 16, "depth": 64},
    )
    assert js.state == jobmod.QUEUED  # admitted: priced 1,040
    with pytest.raises(admmod.AdmissionError) as ei:
        # a check job at the 1M default blows the 10k quota
        sched.submit("compaction", cfg, tenant="alpha")
    assert ei.value.reason == "tenant_states"
    # the live sim job's aggregate price is its walk budget too: a
    # second small sim job still fits under the quota
    sched.submit(
        "compaction", cfg, tenant="alpha", mode="simulate",
        sim={"n_walkers": 16, "depth": 64},
    )


def test_rejected_submit_never_builds_a_checker(
    tmp_path, cfg_dir
):
    """Admission gates BEFORE warm planning: an over-quota submit is
    shed at the door without constructing (and permanently pooling) a
    DeviceChecker — the submit-spam cost admission control exists to
    prevent."""
    config = ServiceConfig(
        state_dir=str(tmp_path / "state"), tenant_max_queued=1, **GEOM
    )
    own_pool = CheckerPool(config)
    sched = Scheduler(config, pool=own_pool)
    cfg = str(cfg_dir / "small_compaction.cfg")
    sched.submit("compaction", cfg, tenant="alpha")
    n_before = len(own_pool._checkers)
    with pytest.raises(admmod.AdmissionError):
        # a DISTINCT pool key (max_states differs): were planning to
        # run before admission, this would build + pool a checker
        sched.submit(
            "compaction", cfg, tenant="alpha", max_states=12345
        )
    assert len(own_pool._checkers) == n_before
    assert not any(k[3] == 12345 for k in own_pool._checkers)


def test_ledger_warm_tagging_and_gate_baseline(tmp_path):
    """Satellite: warm mode tags ledger records from the v12 run
    header; the default gate baseline never crosses warm contexts;
    re-ingesting the same stream under a new path dedupes."""

    def stream(warm, path, states):
        events = [
            {
                "v": 12, "event": "run_header", "t": 0.0, "seq": 0,
                "run_id": "r1", "engine": "device_bfs",
                "visited_impl": "fpset", "config_sig": "SIG",
                "profile_sig": None, "hbm_budget": None,
                "tenant": None, "mode": "check", "warm": warm,
                "fuse": "level", "compact_impl": "logshift",
            },
            {
                "v": 12, "event": "result", "t": 1.0, "seq": 1,
                "run_id": "r1", "distinct_states": states,
                "diameter": 3, "wall_s": 1.0, "truncated": False,
                "stats": {},
            },
        ]
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")
        return events

    cold_events = stream(None, tmp_path / "cold.jsonl", 1000)
    warm_events = stream("continue", tmp_path / "warm.jsonl", 400)
    rc = ledger.record_from_stream(cold_events, source="cold.jsonl")
    rw = ledger.record_from_stream(warm_events, source="warm.jsonl")
    assert "warm" not in rc["values"]
    assert rw["values"]["warm"] == "continue"
    assert ledger.warm_of(rc) == "cold"
    assert ledger.warm_of(rw) == "continue"
    assert not ledger.baseline_matches_warm(rw, rc)
    assert not ledger.baseline_matches_warm(rc, rw)
    assert ledger.baseline_matches_warm(rc, rc)
    # same config key either way (comparability grouping unchanged)
    assert rc["key"] == rw["key"]

    # dedupe: the SAME stream content under a NEW file path is one
    # ledger record (digest is over values, not the path)
    lpath = str(tmp_path / "LEDGER.jsonl")
    assert ledger.append(lpath, [rc]) == 1
    shutil.copyfile(tmp_path / "cold.jsonl", tmp_path / "cold2.jsonl")
    rc2 = ledger.record_from_file(str(tmp_path / "cold2.jsonl"))
    assert rc2["digest"] == rc["digest"]
    assert ledger.append(lpath, [rc2]) == 0  # deduped
    assert ledger.append(lpath, [rw]) == 1

    # the default-baseline scan (the cli `ledger gate` rule): gating
    # the cold record must refuse the warm-continue partial
    rc_new = dict(rc)
    rc_new["values"] = dict(rc["values"], distinct_states=1001)
    rc_new["digest"] = "f" * 16
    with open(lpath, "a") as f:
        f.write(json.dumps(rc_new) + "\n")
    recs = ledger.load(lpath)
    cur = recs[-1]
    base = next(
        (
            r for r in reversed(recs[:-1])
            if r.get("key") == cur.get("key")
            and ledger.baseline_matches_warm(r, cur)
        ),
        None,
    )
    assert base is not None and base["digest"] == rc["digest"]


def test_validator_warm_flag_and_v12(
    tmp_path, checker_mod, base_artifact
):
    """Satellite: ``check_telemetry_schema --warm`` validates artifact
    digests; the v12 stream schema gates run_header.warm and the warm
    event."""
    store, adir, _ck, _invs = _copy_store(base_artifact, tmp_path / "v")
    assert checker_mod.main(["--warm", adir]) == 0
    # tamper -> violations
    fpath = os.path.join(adir, warm_store.FRAME)
    raw = bytearray(open(fpath, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(fpath, "wb") as f:
        f.write(bytes(raw))
    assert checker_mod.main(["--warm", adir]) == 1

    # v12 stream rules: a v12 run_header without `warm` fails, a v11
    # one stays clean (FIELD_SINCE); a warm event needs mode+reason
    def write_stream(path, recs):
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return str(path)

    head = {
        "event": "run_header", "t": 0.0, "seq": 0, "run_id": "x",
        "engine": "device_bfs", "visited_impl": "fpset",
        "config_sig": "S", "profile_sig": None, "hbm_budget": None,
        "tenant": None, "mode": "check",
    }
    bad = write_stream(tmp_path / "bad.jsonl", [dict(head, v=12)])
    assert any(
        "warm" in e for e in checker_mod.validate_stream(bad)
    )
    ok11 = write_stream(tmp_path / "ok11.jsonl", [dict(head, v=11)])
    assert checker_mod.validate_stream(ok11) == []
    ok12 = write_stream(
        tmp_path / "ok12.jsonl", [dict(head, v=12, warm=None)]
    )
    assert checker_mod.validate_stream(ok12) == []
    badw = write_stream(
        tmp_path / "badw.jsonl",
        [
            dict(head, v=12, warm=None),
            {
                "v": 12, "event": "warm", "t": 0.1, "seq": 1,
                "run_id": "x", "mode": "cold",
            },
        ],
    )
    assert any(
        "reason" in e for e in checker_mod.validate_stream(badw)
    )


def test_warm_metrics_stream_scrape_parity(tmp_path, pool, cfg_dir):
    """ptt_warm_{hit,reseed,cold}_total{reason} derive from the
    daemon stream tail with the SAME names and counting points as the
    live scheduler (cold counts at plan, continue/reseed at install —
    a demotion counts once, as cold)."""
    from pulsar_tlaplus_tpu.obs import telemetry as obs

    config = ServiceConfig(state_dir=str(tmp_path / "state"), **GEOM)
    tel = obs.Telemetry(config.telemetry_path)
    sched = Scheduler(config, pool=pool, telemetry=tel)
    # the exact event shapes the scheduler emits, without re-running
    # engines: one cold plan, one continue plan + install (counts at
    # install), one demoted install, one harvest (not counted)
    tel.emit("warm", phase="plan", mode="cold", reason="no_artifact")
    tel.emit("warm", phase="plan", mode="continue", reason="sig_match")
    tel.emit(
        "warm", phase="install", mode="continue", reason="sig_match"
    )
    tel.emit(
        "warm", phase="install", mode="cold", reason="digest_mismatch"
    )
    tel.emit("warm", phase="harvest", mode="cold", reason="harvested")
    events, _errs = report.load_events(config.telemetry_path)
    stext = metrics_mod.render_exposition(
        metrics_mod.stream_metrics(events)
    )
    assert 'ptt_warm_cold_total{reason="no_artifact"} 1' in stext
    assert 'ptt_warm_hit_total{reason="sig_match"} 1' in stext
    assert (
        'ptt_warm_cold_total{reason="digest_mismatch"} 1' in stext
    )
    assert "harvested" not in stext  # harvest is not an outcome
    # and the live renderer names the same families from the counters
    sched.warm_counts[("cold", "no_artifact")] = 1
    ltext = metrics_mod.render_exposition(
        metrics_mod.scheduler_metrics(sched)
    )
    assert 'ptt_warm_cold_total{reason="no_artifact"} 1' in ltext


@pytest.mark.slow
def test_fuzz_soak_slow_lane():
    """The scheduled long-randomized soak (ROADMAP r18 follow-up +
    ISSUE 15 satellite): 20 bindings/spec through the plain
    device-vs-interpreter differential AND 20 widenings/spec through
    the warm-reseed differential."""
    fuzz = _load_script("fuzz")
    _records, failures = fuzz.run(20, 20, log=lambda m: None)
    assert failures == []
    _records, failures = fuzz.run_widen(20, 20, log=lambda m: None)
    assert failures == []


def test_fuzz_widen_fast_drill(tmp_path):
    """Satellite: the pinned-seed --widen drill on the spec whose
    axis is layout-stable under every widening (bookkeeper's popcount
    axis) — a genuine reseed differential runs warm-vs-cold in
    tier-1; the all-spec randomized sweep is the slow soak lane."""
    fuzz = _load_script("fuzz")
    # the suite-common geometry: every jit shape is already in the
    # persistent compile cache, so the drill pays no fresh compiles
    fuzz.DEVICE_KW = dict(
        sub_batch=64, visited_cap=1 << 10, frontier_cap=1 << 8,
        max_states=1 << 18,
    )
    records, failures = fuzz.run_widen(
        seed=5, per_spec=1, specs=("bookkeeper",), log=lambda m: None
    )
    assert failures == []
    assert len(records) == 1
    assert (records[0].get("plan") or {}).get("mode") == "reseed"
    assert records[0]["reseed"]["replay_rows"] >= 1
    assert records[0]["reseed"]["reused_rows"] >= 1
