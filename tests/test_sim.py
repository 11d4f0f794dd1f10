"""Swarm simulation subsystem tests (round 18, ISSUE 14): the
streaming engine's determinism/resume contract, both published
bug-discovery oracles with interpreter-replayed traces, the
kill->resume drill, daemon time-slicing with solo parity, the
differential fuzz fast drill, the sim ledger gate, and the v11
telemetry/bench_schema-9 validator gates."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import ledger, metrics, report
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.sim.engine import StreamingSimulator
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIM_PINNED = os.path.join(
    ROOT, "tests", "data", "mini_bench_sim_producer_on.jsonl"
)

# the deterministic small shape every stream-identity test shares
# (producer_on: 1,654 reachable states — walkers revisit heavily,
# which is exactly what the duplicate estimator should report)
SMALL_KW = dict(
    n_walkers=128, depth=16, segment_len=4, seed=3,
    max_steps=128 * 16 * 3,
)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sim_events(path):
    """The deterministic view of a stream's `sim` records (cumulative
    counters only — no clocks)."""
    evs, errs = report.load_events(path)
    assert not errs
    return [
        {
            k: e[k]
            for k in (
                "steps", "states", "walks", "violations",
                "stutter_steps", "enabled_lanes", "dup_attempts",
                "dup_hits", "epoch",
            )
        }
        for e in evs
        if e.get("event") == "sim"
    ]


@pytest.fixture(scope="module")
def small_model():
    return CompactionModel(SMALL_CONFIGS["producer_on"])


@pytest.fixture(scope="module")
def shipped_model():
    return CompactionModel(pe.SHIPPED_CFG)


# ------------------------------------------------------------- units


def test_segment_len_clamps_to_depth_divisor(small_model):
    s = StreamingSimulator(
        small_model, depth=48, segment_len=20
    )
    assert s.L == 16 and 48 % s.L == 0  # largest divisor <= 20
    s2 = StreamingSimulator(
        small_model, depth=48, segment_len=500
    )
    assert s2.L == 48  # clamped to depth


def test_unknown_invariant_raises(small_model):
    with pytest.raises(ValueError, match="unknown invariant"):
        StreamingSimulator(
            small_model, invariants=("NoSuchInv",)
        )


def test_default_budget_is_one_round(small_model):
    s = StreamingSimulator(small_model, n_walkers=8, depth=4)
    assert s.max_rounds == 1


def test_one_round_contract_spans_multiple_segments(small_model):
    """The legacy one-round budget must cover the FULL depth even when
    a round spans several segments (steps are swarm-total: one round =
    B * depth, not depth — the r18 review regression)."""
    r = StreamingSimulator(
        small_model, n_walkers=16, depth=64
    ).run()
    assert r.steps == 16 * 64
    assert r.states_visited == 16 * 65
    assert r.walks == 16
    assert r.stop_reason == "round_budget"


def test_resume_restores_frame_budgets(small_model, tmp_path):
    """A resume constructed WITHOUT explicit budgets adopts the
    frame's persisted ones — `simulate -recover` must finish the
    original step budget, never the one-round default (which would
    end a recovered long run immediately, reported clean)."""
    ck = str(tmp_path / "f.npz")
    budget = 128 * 16 * 3
    polls = [0]

    def hook():
        polls[0] += 1
        return None if polls[0] <= 3 else "suspended"

    r1 = StreamingSimulator(
        small_model, n_walkers=128, depth=16, segment_len=4, seed=3,
        max_steps=budget, checkpoint_path=ck, suspend_hook=hook,
    ).run()
    assert r1.stop_reason == "suspended" and r1.steps < budget
    # note: NO budget args — the frame must supply them
    r2 = StreamingSimulator(
        small_model, n_walkers=128, depth=16, segment_len=4, seed=3,
        checkpoint_path=ck,
    ).run(resume=True)
    assert r2.steps == budget
    assert r2.stop_reason == "step_budget"


def test_heartbeat_reports_walks_rate():
    from pulsar_tlaplus_tpu.obs.telemetry import Heartbeat

    lines = []
    snap = {"distinct_states": 100, "generated": 90, "walks": 0}
    hb = Heartbeat(60.0, snap, log=lines.append)
    import time as _time

    t0 = _time.monotonic() - 1.0
    prev = hb._beat(t0, (t0, 0))
    snap.update(distinct_states=300, generated=280, walks=128)
    hb._beat(t0, prev)
    assert hb.ewma_wps is not None and hb.ewma_wps > 0
    assert any("walks/s" in ln for ln in lines)


# --------------------------------------------- determinism + resume


def test_deterministic_stream_and_counters(small_model, tmp_path):
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    r1 = StreamingSimulator(small_model, telemetry=a, **SMALL_KW).run()
    r2 = StreamingSimulator(small_model, telemetry=b, **SMALL_KW).run()
    assert _sim_events(a) == _sim_events(b)
    assert (r1.steps, r1.states_visited, r1.walks, r1.dup_ratio_est) \
        == (r2.steps, r2.states_visited, r2.walks, r2.dup_ratio_est)
    assert r1.steps == SMALL_KW["max_steps"]
    assert r1.walks == 128 * 3  # three completed rounds
    assert r1.stop_reason == "step_budget" and not r1.truncated
    # a producer_on swarm revisits the 1,654-state space constantly —
    # the advisory estimator must see substantial duplication
    assert r1.dup_ratio_est is not None and r1.dup_ratio_est > 0.2
    # a different seed is a different (deterministic) stream
    kw = dict(SMALL_KW, seed=4)
    r3 = StreamingSimulator(small_model, **kw).run()
    assert (r3.steps, r3.states_visited) == (r1.steps, r1.states_visited)
    assert r3.dup_ratio_est != r1.dup_ratio_est


def test_suspend_resume_continues_identical_stream(
    small_model, tmp_path
):
    solo = str(tmp_path / "solo.jsonl")
    r_solo = StreamingSimulator(
        small_model, telemetry=solo, **SMALL_KW
    ).run()
    ck = str(tmp_path / "f.npz")
    sliced = str(tmp_path / "sliced.jsonl")
    polls = [0]

    def hook():
        polls[0] += 1
        return None if polls[0] <= 4 else "suspended"

    r1 = StreamingSimulator(
        small_model, telemetry=sliced, checkpoint_path=ck,
        suspend_hook=hook, **SMALL_KW,
    ).run()
    assert r1.stop_reason == "suspended" and r1.truncated
    assert r1.steps < r_solo.steps
    r2 = StreamingSimulator(
        small_model, telemetry=sliced, checkpoint_path=ck, **SMALL_KW
    ).run(resume=True)
    assert (r2.steps, r2.states_visited, r2.walks, r2.dup_ratio_est) \
        == (
            r_solo.steps, r_solo.states_visited, r_solo.walks,
            r_solo.dup_ratio_est,
        )
    # the sliced stream (suspend + resume) carries the IDENTICAL sim
    # records as the uninterrupted run — the r18 resumability contract
    assert _sim_events(sliced) == _sim_events(solo)
    # resume linking: the resumed header names the prior run's frame
    evs, _ = report.load_events(sliced)
    headers = [e for e in evs if e.get("event") == "run_header"]
    assert headers[-1]["resume"] is True
    assert headers[-1]["resume_of"] == headers[0]["run_id"]
    assert headers[-1]["mode"] == "simulate"


def test_keys_digest_refuses_foreign_frame(small_model, tmp_path):
    ck = str(tmp_path / "f.npz")
    eng = StreamingSimulator(
        small_model, checkpoint_path=ck, checkpoint_every=1, **SMALL_KW
    )
    eng.run()
    # a frame from a different seed's stream must refuse to anchor
    kw = dict(SMALL_KW, seed=99)
    other = StreamingSimulator(
        small_model, checkpoint_path=ck, **kw
    )
    with pytest.raises(ValueError, match="different simulation"):
        other.run(resume=True)


def test_kill_resume_drill_identical_post_resume_stream(
    small_model, tmp_path
):
    """THE acceptance drill: a hard kill mid-stream (PTT_FAULT
    kill@segment:N), then resume — the post-resume stream continues
    the identical walk stream (sim records equal to an uninterrupted
    solo run's, final counters equal)."""
    solo = str(tmp_path / "solo.jsonl")
    r_solo = StreamingSimulator(
        small_model, telemetry=solo, **SMALL_KW
    ).run()
    ck = str(tmp_path / "f.npz")
    stream = str(tmp_path / "killed.jsonl")
    code = f"""
import jax
jax.config.update("jax_platforms", "cpu")
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.sim.engine import StreamingSimulator
c = pe.Constants(message_sent_limit=2, compaction_times_limit=2,
                 num_keys=1, num_values=1, max_crash_times=1,
                 model_producer=True)
StreamingSimulator(CompactionModel(c), n_walkers=128, depth=16,
                   segment_len=4, seed=3, max_steps=128*16*3,
                   telemetry={stream!r},
                   checkpoint_path={ck!r}, checkpoint_every=1).run()
"""
    env = dict(os.environ, PTT_FAULT="kill@segment:4",
               JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300, cwd=ROOT,
    )
    assert p.returncode == 137, (p.returncode, p.stderr[-500:])
    assert os.path.exists(ck)
    killed_events = _sim_events(stream)
    assert killed_events  # progress reached the stream pre-kill
    r2 = StreamingSimulator(
        small_model, telemetry=stream, checkpoint_path=ck, **SMALL_KW
    ).run(resume=True)
    assert (r2.steps, r2.states_visited, r2.walks, r2.dup_ratio_est) \
        == (
            r_solo.steps, r_solo.states_visited, r_solo.walks,
            r_solo.dup_ratio_est,
        )
    assert _sim_events(stream) == _sim_events(solo)
    # both streams are v11-validator-clean
    checker = _load_script("check_telemetry_schema")
    assert checker.validate_stream(stream) == []
    assert checker.validate_stream(solo) == []


# ------------------------------------- published bug oracles, pinned


def test_sim_finds_leak_bug_pinned(shipped_model, tmp_path):
    """The retention-leak bug config (CompactedLedgerLeak, published
    diameter 12: a walk's trace is no shorter) found within a pinned
    (seed, n_walkers, depth) budget; the trace replays state-for-state
    through the interpreter;
    a deterministic re-run yields the identical discovery."""
    kw = dict(
        n_walkers=256, depth=32, segment_len=16, seed=1,
        invariants=("TypeSafe", "CompactedLedgerLeak"),
    )
    st = str(tmp_path / "leak.jsonl")
    r = StreamingSimulator(shipped_model, telemetry=st, **kw).run()
    assert r.violation == "CompactedLedgerLeak"
    assert r.stop_reason == "violation" and not r.truncated
    # a random walk promises a real trace to the violation, not the
    # shortest: the published diameter is the lower bound
    assert len(r.trace) >= 12
    assert r.verified is True
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, "CompactedLedgerLeak"
    )
    r2 = StreamingSimulator(shipped_model, **kw).run()
    assert (r2.violation_walker, r2.violation_step, r2.steps) == (
        r.violation_walker, r.violation_step, r.steps
    )
    assert r2.trace == r.trace and r2.trace_actions == r.trace_actions
    checker = _load_script("check_telemetry_schema")
    assert checker.validate_stream(st) == []


def test_sim_finds_dup_null_key_bug_pinned(shipped_model):
    """The dup-null-key bug config (DuplicateNullKeyMessage, published
    diameter 4) found within a pinned budget, interpreter-replayed."""
    kw = dict(
        n_walkers=256, depth=16, segment_len=8, seed=0,
        invariants=("DuplicateNullKeyMessage",),
    )
    r = StreamingSimulator(shipped_model, **kw).run()
    assert r.violation == "DuplicateNullKeyMessage"
    assert len(r.trace) == 4  # the published shortest-diameter shape
    assert r.verified is True
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions,
        "DuplicateNullKeyMessage",
    )
    r2 = StreamingSimulator(shipped_model, **kw).run()
    assert r2.trace == r.trace and r2.trace_actions == r.trace_actions


# ----------------------------------------------- daemon time-slicing

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


def test_daemon_two_job_slice_with_sim_solo_parity(
    small_model, tmp_path
):
    """A simulation job and a BFS job time-slice one device; the sim
    job suspends/resumes at SEGMENT boundaries and finishes with the
    counters of an uninterrupted solo run (`submit --mode simulate`
    acceptance)."""
    from pulsar_tlaplus_tpu.obs.telemetry import Telemetry
    from pulsar_tlaplus_tpu.service.scheduler import (
        CheckerPool,
        Scheduler,
        ServiceConfig,
    )

    cfg = str(tmp_path / "small.cfg")
    with open(cfg, "w") as f:
        f.write(SMALL_COMPACTION_CFG)
    config = ServiceConfig(
        state_dir=str(tmp_path / "state"), slice_s=0.02, sub_batch=64,
        visited_cap=1 << 10, frontier_cap=1 << 8, max_states=1 << 20,
        prewarm_tiers=False, checkpoint_every=1,
    )
    pool = CheckerPool(config)
    tel = Telemetry(str(tmp_path / "service.jsonl"))
    sched = Scheduler(config, pool=pool, telemetry=tel)
    sim_kw = {
        "n_walkers": 128, "depth": 16, "segment_len": 4, "seed": 3,
        "max_steps": 128 * 16 * 6,
    }
    j1 = sched.submit("compaction", cfg, mode="simulate", sim=sim_kw)
    j2 = sched.submit("compaction", cfg, invariants=[])
    sched.run_until_idle()
    tel.close()
    assert j1.state == "done" and j2.state == "done"
    assert j1.suspends >= 1 and j2.suspends >= 1  # genuine slicing
    r_solo = StreamingSimulator(
        small_model,
        **{
            "n_walkers": 128, "depth": 16, "segment_len": 4,
            "seed": 3, "max_steps": 128 * 16 * 6,
        },
    ).run()
    assert j1.result["mode"] == "simulate"
    assert j1.result["status"] == "ok"
    assert j1.result["steps"] == r_solo.steps
    assert j1.result["states_visited"] == r_solo.states_visited
    assert j1.result["walks"] == r_solo.walks
    assert j1.result["dup_ratio_est"] == r_solo.dup_ratio_est
    assert j2.result["distinct_states"] == 1654  # the pinned BFS job
    # per-job stream: v11-clean, and its tail exports ptt_sim_*
    checker = _load_script("check_telemetry_schema")
    job_stream = os.path.join(config.jobs_dir, j1.job_id, "events.jsonl")
    assert checker.validate_stream(job_stream) == []
    assert checker.validate_stream(str(tmp_path / "service.jsonl")) == []
    evs, _ = report.load_events(job_stream)
    text = metrics.render_stream_metrics(evs)
    fams, _types = metrics.parse_exposition(text)
    assert fams["ptt_sim_steps_total"][0][1] == r_solo.steps
    assert fams["ptt_sim_walks_total"][0][1] == r_solo.walks
    # every engine run header carries the slice's tenant + mode
    headers = [e for e in evs if e.get("event") == "run_header"]
    assert headers and all(
        h["mode"] == "simulate" and h["tenant"] == "local"
        for h in headers
    )


# ------------------------------------------------- fuzz fast drill


def test_fuzz_fast_drill_pinned_seed():
    """The differential fuzz harness's tier-1 drill: one pinned-seed
    binding per registered spec, device engine vs interpreter — any
    mismatch (counts, diameter, verdict, trace replay) fails."""
    fuzz = _load_script("fuzz")
    records, failures = fuzz.run(seed=0, per_spec=1, log=lambda m: None)
    assert len(records) == 4
    assert failures == [], failures
    # the drill genuinely exercises both verdict classes
    verdicts = {r["device"]["violation"] for r in records}
    assert None in verdicts and len(verdicts) > 1


# --------------------------------------------------- ledger + bench


def test_sim_ledger_gate_pinned_baseline(small_model, tmp_path):
    """The sim tier-1 gate: a fresh deterministic sim run gates clean
    against the committed baseline on steps_per_state; an injected
    walk-stream change fails."""
    from pulsar_tlaplus_tpu import cli

    path = str(tmp_path / "sim_ledger.jsonl")
    shutil.copy(SIM_PINNED, path)
    assert ledger.validate_ledger(path) == []
    # a simulate-mode bench artifact (made-up figures) ingests cleanly
    # beside the pinned baseline
    art = tmp_path / "BENCH_sim.json"
    art.write_text(json.dumps({
        "metric": "simulation steps/sec", "value": 1000.0,
        "unit": "sim steps/sec/chip", "bench_schema": 9,
        "mode": "simulate", "walks_per_sec": 10.0,
        "steps_per_state": 0.98, "sim_walkers": 4096, "sim_depth": 64,
    }))
    rec = ledger.record_from_file(str(art))
    assert rec["values"]["walks_per_sec"] > 0
    assert rec["values"]["mode"] == "simulate"
    assert ledger.append(path, [rec]) == 1
    stream = str(tmp_path / "run.jsonl")
    StreamingSimulator(
        small_model, telemetry=stream, **SMALL_KW
    ).run()
    assert cli.main(["ledger", "--ledger", path, "add", stream]) == 0
    keys = list(ledger.SIM_GATE_KEYS)
    rc = cli.main(
        ["ledger", "--ledger", path, "gate", "--threshold", "0.02",
         "--keys"] + keys
    )
    assert rc == 0
    cur = ledger.load(path)[-1]
    assert cur["values"]["steps_per_state"] == pytest.approx(
        ledger.load(SIM_PINNED)[0]["values"]["steps_per_state"]
    )
    bad = dict(cur, values=dict(cur["values"]))
    bad["values"]["steps_per_state"] = (
        cur["values"]["steps_per_state"] * 1.5
    )
    bad["digest"] = ledger._digest(bad["values"])
    ledger.append(path, [bad])
    rc = cli.main(
        ["ledger", "--ledger", path, "gate", "--threshold", "0.02",
         "--keys"] + keys
    )
    assert rc == 1


def test_bench_sim_and_matrix_artifacts_validate(tmp_path, capsys):
    """bench --mode simulate and one --matrix point both emit
    bench_schema-9 artifacts the validator accepts and the ledger
    ingests."""
    # load bench.py from the repo root
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(ROOT, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    checker = _load_script("check_telemetry_schema")
    # simulate mode at a tiny deterministic shape
    args = bench.parse_args(
        [
            "--mode", "simulate", "--walkers", "64", "--depth", "8",
            "--sim-steps", str(64 * 8 * 2),
            "--telemetry-path", str(tmp_path),
        ]
    )
    bench.run_sim_bench(args)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    d = json.loads(line)
    assert checker.validate_bench_artifact(d, path="sim-bench") == []
    assert d["mode"] == "simulate"
    assert d["walks_per_sec"] > 0 and d["steps_per_state"] > 0
    rec = ledger.record_from_bench(d, source="sim_bench.json")
    assert rec["values"]["walks_per_sec"] == d["walks_per_sec"]
    # one matrix point, ledger-ingested
    margs = bench.parse_args(
        [
            "--matrix", "--matrix-spec", "subscription",
            "--matrix-limit", "1",
            "--matrix-out", str(tmp_path / "mx"),
            "--matrix-ledger", str(tmp_path / "mx" / "L.jsonl"),
        ]
    )
    bench.run_matrix(margs)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(out)
    assert summary["matrix"], summary
    art = summary["matrix"][0]["artifact"]
    assert checker.validate_bench_artifact(art) == []
    recs = ledger.load(str(tmp_path / "mx" / "L.jsonl"))
    assert len(recs) == 1 and recs[0]["values"]["matrix_spec"] == (
        "subscription"
    )


# --------------------------------------------------- validator gates


def test_validator_rejects_backwards_sim_counters(tmp_path):
    from pulsar_tlaplus_tpu.obs import telemetry as obs

    checker = _load_script("check_telemetry_schema")
    path = str(tmp_path / "torn.jsonl")
    base = {
        "v": obs.SCHEMA_VERSION, "run_id": "r1",
        "event": "sim", "walkers": 8, "violations": 0,
    }
    with open(path, "w") as f:
        f.write(json.dumps(
            {**base, "t": 0.1, "seq": 0, "steps": 100, "states": 108}
        ) + "\n")
        f.write(json.dumps(
            {**base, "t": 0.2, "seq": 1, "steps": 60, "states": 200}
        ) + "\n")
    errs = checker.validate_stream(path)
    assert any("sim.steps went backwards" in e for e in errs)


def test_validator_requires_mode_at_v11(tmp_path):
    from pulsar_tlaplus_tpu.obs import telemetry as obs

    checker = _load_script("check_telemetry_schema")
    path = str(tmp_path / "nomode.jsonl")
    rec = {
        "v": obs.SCHEMA_VERSION, "run_id": "r1", "t": 0.1, "seq": 0,
        "event": "run_header", "engine": "sim", "visited_impl": None,
        "config_sig": "x", "profile_sig": None, "hbm_budget": None,
        "tenant": None,
    }
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    errs = checker.validate_stream(path)
    # (v12 additionally requires `warm`, so match the field, not the
    # exact missing-list rendering)
    assert any("missing" in e and "'mode'" in e for e in errs)
    # a v10 record without mode stays clean (FIELD_SINCE gate)
    rec10 = dict(rec, v=10)
    with open(path, "w") as f:
        f.write(json.dumps(rec10) + "\n")
    assert checker.validate_stream(path) == []


def test_bench_schema9_requires_sim_keys():
    checker = _load_script("check_telemetry_schema")
    d = {k: None for k in checker.BENCH_KEYS_V9}
    d.update(bench_schema=9, value=1.0)
    assert checker.validate_bench_artifact(d, path="ok") == []
    del d["walks_per_sec"]
    errs = checker.validate_bench_artifact(d, path="bad")
    assert any("walks_per_sec" in e for e in errs)
    # schema 8 artifacts do NOT need the sim keys (committed history)
    d8 = {k: None for k in checker.BENCH_KEYS_V8}
    d8.update(bench_schema=8, value=1.0)
    assert checker.validate_bench_artifact(d8, path="v8") == []


# ------------------------------------------------------- CLI surface


def test_cli_simulate_subcommand(tmp_path, capsys):
    from pulsar_tlaplus_tpu import cli

    cfg = str(tmp_path / "small.cfg")
    with open(cfg, "w") as f:
        f.write(SMALL_COMPACTION_CFG)
    st = str(tmp_path / "s.jsonl")
    rc = cli.main(
        [
            "simulate", "compaction", "-config", cfg, "-walkers", "64",
            "-depth", "8", "-seed", "5", "-cpu", "-telemetry", st,
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "64 walkers of depth 8" in out
    assert "walks/sec" in out
    checker = _load_script("check_telemetry_schema")
    assert checker.validate_stream(st) == []


def test_cli_check_simulate_routes_streaming_engine(
    tmp_path, capsys
):
    from pulsar_tlaplus_tpu import cli

    cfg = str(tmp_path / "small.cfg")
    with open(cfg, "w") as f:
        f.write(SMALL_COMPACTION_CFG)
    tla = os.path.join(ROOT, "specs", "compaction.tla")
    st = str(tmp_path / "s.jsonl")
    rc = cli.main(
        [
            "check", tla, "-config", cfg, "-simulate", "64",
            "-depth", "8", "-sim-seed", "5", "-cpu",
            "-telemetry", st,
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "64 walkers of depth 8" in out
    evs, _ = report.load_events(st)
    hd = report.header(evs)
    assert hd["engine"] == "sim" and hd["mode"] == "simulate"


# ---- what a simulation's dispatches are made of (ISSUE 54) --------------


def test_a_simulation_says_what_its_dispatches_are_made_of(
    small_model, tmp_path
):
    from tests.test_spans import (
        SPLIT_KEYS, _checker_mod, assert_split_adds_up,
    )

    stream = str(tmp_path / "sim.jsonl")
    sim = StreamingSimulator(
        small_model, telemetry=stream, dump_path=str(tmp_path / "b"),
        dump_num=4, **SMALL_KW,
    )
    r = sim.run()
    st = sim.last_stats
    for k in SPLIT_KEYS:
        assert k in st, k
    assert_split_adds_up(st)
    # a segment a dispatch: one call and one scalar (the epoch) each
    segments = r.steps // (SMALL_KW["n_walkers"] * SMALL_KW["segment_len"])
    assert list(st["dispatch_by_program"]) == ["ptt_sim_segment"]
    seg = st["dispatch_by_program"]["ptt_sim_segment"]
    assert seg[0] == seg[2] == segments == st["dispatch_calls"]
    # (the programs are units: traced once a process, maybe before this)
    assert 0.0 <= st["dispatch_jit_s"] <= st["dispatch_call_s"]
    # the dump replays the last round and checks it on the device
    dump = st["programs_by_phase"]["dump"]
    assert dump["ptt_sim_replay"][0] == 1 and dump["ptt_sim_replay"][2] == 2
    assert dump["ptt_sim_replay_check"][0] == 1
    assert st["calls_by_phase"]["dump"][0] == 2
    assert len(r.dump_files) == 4 and st["sim_dump_mismatches"] == 0
    with open(stream, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    (res,) = [e for e in events if e["event"] == "result"]
    for k in SPLIT_KEYS:
        assert k in res["stats"], k
    assert _checker_mod().validate_stream(stream) == []


def test_a_warm_up_before_any_run_has_a_clock_to_count_on(small_model):
    sim = StreamingSimulator(small_model, **SMALL_KW)
    assert sim.warmup() > 0.0
    assert sim._clock.calls[""]["ptt_sim_segment"][0] == 2
    r = sim.run()  # and the run's own clock starts at nothing
    assert sim.last_stats["dispatch_calls"] == r.steps // (128 * 4)
