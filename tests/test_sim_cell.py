"""Simulation mode as a supported deployment (ISSUE 52): the scaled
binding as a ``.cfg``; the chunked step's walk stream against the
parent's, bit for bit; behaviours on request (``-sim-dump``) held to
the benchmark's own reference (``benchmark/ref/pyeval.py``) by the
cell's own comparison; the draw's uniformity over the reference's
successor sets; the simulated line and its digest; the engine's stage
scopes, host phases and compile counters.  Since ISSUE 53 a walker's
step builds the one successor it drew (``successor_at``): the method
against ``successors`` lane for lane, the two forms of the step as one
stream, the compiled program without a lane axis, a frame the parent
wrote resumed to the parent's digest.
"""

import contextlib
import io
import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from benchmark.lib import plug, tlafmt
from benchmark.ref import pyeval as ref
from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.sim import engine as E
from pulsar_tlaplus_tpu.sim.engine import StreamingSimulator
from pulsar_tlaplus_tpu.utils import cfg as cfgmod
from tests.helpers import SMALL_CONFIGS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "specs", "compaction.tla")
CFG = os.path.join(ROOT, "specs", "compaction_253k.cfg")
SCALED = os.path.join(ROOT, "specs", "compaction_scaled.cfg")

REPLAY = plug.load_file("comparisons", "sim-walk-replay")
TRACE_REPLAY = plug.load_file("comparisons", "trace-replay")

WALKERS, DEPTH, ROUNDS, K = 96, 24, 2, 8
ARGV = ["check", "specs/compaction.tla", "-config",
        "specs/compaction_253k.cfg", "-simulate", str(WALKERS), "-depth",
        str(DEPTH), "-sim-steps", str(WALKERS * DEPTH * ROUNDS),
        "-sim-dump-num", str(K)]
TRAFFIC = {"argv": ARGV, "cfg_path": CFG}
CONFIG = {"assumed": {"invariants": ["TypeSafe",
                                     "CompactionHorizonCorrectness"]},
          "shapes": {"state_bytes_unpacked": 592}}


def check(argv, tmp_path, seed=5, dump=True, tel=False):
    """One ``cli.main`` of ``argv`` as the cell's driver makes it: an
    answer of the benchmark's (``rc``, ``text``, ``dump_prefix``,
    ``stats``) and what went to standard error."""
    argv = [os.path.join(ROOT, a) if a.startswith("specs/") else a
            for a in argv] + ["-sim-seed", str(seed)]
    prefix = str(tmp_path / f"dump_{seed}" / "behaviour")
    if dump:
        os.makedirs(os.path.dirname(prefix), exist_ok=True)
        argv += ["-sim-dump", prefix]
    tel_path = str(tmp_path / f"tel_{seed}.jsonl")
    if tel:
        argv += ["-telemetry", tel_path]
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        try:
            rc = cli.main(argv)
        except SystemExit as e:
            rc = e.code
    stats, header = {}, {}
    if tel and os.path.exists(tel_path):
        with open(tel_path, encoding="utf-8") as f:
            for line in f:
                e = json.loads(line)
                if e.get("event") == "result":
                    stats = e["stats"]
                if e.get("event") == "run_header":
                    header = e
    return {"rc": rc, "text": out.getvalue(), "stats": stats,
            "header": header,
            "dump_prefix": prefix if dump else None}, err.getvalue()


@pytest.fixture(scope="module")
def clean(tmp_path_factory):
    return check(ARGV, tmp_path_factory.mktemp("clean"), tel=True)[0]


def wrong(answers):
    return [c["name"] for c in REPLAY.compare(CONFIG, TRAFFIC, answers, 5)
            if not c["ok"]]


# ---- step 1: the binding ---------------------------------------------------

def test_the_scaled_cfg_is_the_flagship_binding():
    assert cfgmod.to_constants(cfgmod.load(SCALED)) == bench.scaled_config()
    assert tuple(cfgmod.load(SCALED).invariants) == (
        "TypeSafe", "CompactionHorizonCorrectness")
    # the benchmark's own reader of a .cfg agrees
    c = tlafmt.constants_from_cfg(SCALED)
    assert (c.num_keys, c.message_sent_limit, c.model_producer) == (8, 64, True)


# ---- step 2: the chunked step walks the parent's stream ---------------------

# final walker states (the engine's keys-digest) and counters of the
# PARENT's step (commit 63059ff: one vmap over the whole swarm, no
# chunk) at the small producer-modelled binding, recorded before the
# step was cut
PARENT_STREAMS = [
    (dict(n_walkers=100, depth=12, segment_len=4, seed=7), 2,
     "f45f298f6e1eca84ce6d377bbc9c09bff471a09dff903f45f4c4bc646c851fe7",
     dict(sim_stutter_steps=0, sim_enabled_lanes=4894, sim_dup_hits=1370)),
    (dict(n_walkers=1000, depth=16, segment_len=8, seed=11), 1,
     "6115504df79e5020fd5451981e5c6fc749516d26a713a235e0d35821a054d006",
     dict(sim_stutter_steps=635, sim_enabled_lanes=28522,
          sim_dup_hits=1628)),
    (dict(n_walkers=257, depth=10, segment_len=5, seed=2**31 + 5), 3,
     "c9948e392308fa8423426878c4ee796ce7e46939cdb6289a2b083a46f3ae3746",
     dict(sim_stutter_steps=0, sim_enabled_lanes=17265, sim_dup_hits=5801)),
]


class LanesOnly(CompactionModel):
    """The compaction model with ``successor_at`` hidden: what the three
    other hand models and every generated ``CompiledSpec`` look like to
    the step, which then picks the drawn lane from all ``A``."""

    successor_at = None


FORMS = {"drawn": CompactionModel, "lanes": LanesOnly}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("chunk", [None, 48])
@pytest.mark.parametrize("kw,rounds,digest,counters", PARENT_STREAMS)
def test_the_chunked_step_walks_the_parents_stream_bit_for_bit(
        monkeypatch, kw, rounds, digest, counters, chunk, form):
    """Both forms of the step, chunked or not, are ONE stream: the
    parent's (PR 52's parent: every lane built, no chunk)."""
    if chunk is not None:
        monkeypatch.setattr(E, "SIM_STEP_CHUNK", chunk)
        monkeypatch.setattr(E, "SIM_DRAWN_STEP_CHUNK", chunk)
    sim = StreamingSimulator(
        FORMS[form](SMALL_CONFIGS["producer_on"]), max_rounds=rounds, **kw)
    st = sim.run().stats
    assert st["sim_keys_digest"] == digest
    assert {k: st[k] for k in counters} == counters
    assert st["sim_violations"] == 0
    want = 1 if chunk is None else -(-kw["n_walkers"] // chunk)
    assert st["sim_step_chunks"] == want
    # the counter that says which mechanism built the successors
    steps = kw["n_walkers"] * kw["depth"] * rounds
    assert sim.k.form == form
    assert st["sim_steps"] == steps
    assert st["sim_drawn_steps"] == (steps if form == "drawn" else 0)


def test_the_form_is_in_the_run_header_and_the_sim_records(clean, tmp_path):
    assert clean["header"]["sim_step_form"] == "drawn"
    assert clean["stats"]["sim_drawn_steps"] == clean["stats"]["sim_steps"]
    tel = str(tmp_path / "lanes.jsonl")
    StreamingSimulator(
        LanesOnly(SMALL_CONFIGS["producer_on"]), n_walkers=16, depth=8,
        segment_len=4, max_rounds=2, telemetry=tel).run()
    with open(tel, encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    headers = [e for e in events if e["event"] == "run_header"]
    assert [h["sim_step_form"] for h in headers] == ["lanes"]
    sims = [e for e in events if e["event"] == "sim"]
    assert len(sims) == 4 and all(e["drawn_steps"] == 0 for e in sims)
    assert [e["steps"] for e in sims] == [64, 128, 192, 256]


def _bindings():
    return {
        "producer_on": SMALL_CONFIGS["producer_on"],
        "producer_off": SMALL_CONFIGS["no_retain"],
        "scaled": cfgmod.to_constants(cfgmod.load(SCALED)),
    }


@pytest.mark.parametrize("binding", ["producer_on", "producer_off", "scaled"])
def test_successor_at_is_the_lane_of_successors_leaf_for_leaf(binding):
    """``successor_at(s, l)`` against ``successors(s)[0][l]`` for EVERY
    lane ``l``, enabled or not, on every state of 24 simulated
    behaviours: each leaf equal, of one dtype and one shape."""
    c = _bindings()[binding]
    assert c.model_producer == (binding != "producer_off")
    model = CompactionModel(c)
    depth = 100 if binding == "scaled" else 16
    sim = StreamingSimulator(model, n_walkers=24, depth=depth, seed=53)
    s0, states, _lanes = sim._replay(jnp.arange(24, dtype=jnp.uint32), 0)
    flat = jax.tree.map(
        lambda a, b: jnp.concatenate(
            [a, b.reshape((-1,) + b.shape[2:])]), s0, states)
    n = 24 * (depth + 1)
    every, valid = jax.jit(jax.vmap(model.successors))(flat)
    lanes = jnp.arange(model.A, dtype=jnp.int32)
    one = jax.jit(jax.vmap(
        lambda s: jax.vmap(lambda ln: model.successor_at(s, ln))(lanes)
    ))(flat)
    valid = np.asarray(valid)
    assert valid.shape == (n, model.A)
    # disabled lanes are among those compared, and every lane is met
    # enabled somewhere but where the binding never enables it
    assert (~valid).any() and valid.any(axis=0).sum() >= model.A - 2
    assert len(np.unique(np.asarray(flat.cstate))) >= 4
    for name in every._fields:
        want, got = np.asarray(getattr(every, name)), np.asarray(
            getattr(one, name))
        assert want.dtype == got.dtype and want.shape == got.shape, name
        assert np.array_equal(want, got), name


def _segment_hlo(model, walkers=16):
    sim = StreamingSimulator(
        model, invariants=("TypeSafe", "CompactionHorizonCorrectness"),
        n_walkers=walkers, depth=4, segment_len=2)
    states, table = sim._fresh_buffers()
    return E.ptt_sim_segment.lower(
        states, table, jnp.int32(0), *sim._bases(), k=sim.k, restart=False
    ).compile().as_text()


def test_the_drawn_forms_program_holds_no_lane_axis():
    """The compiled segment program at the scaled binding (34 lanes,
    64 positions): the lanes form stacks ``[walkers, 34, 64]``, the
    drawn form holds no array with a lane axis beside a ``[M]`` leaf's,
    so the stacking cannot come back unseen."""
    c = _bindings()["scaled"]
    a, m = CompactionModel(c).A, c.message_sent_limit
    assert (a, m) == (34, 64)
    stacked = re.compile(rf"\[(?:\d+,)*{a},{m}\]|\[(?:\d+,)*{m},{a}\]")
    assert stacked.search(_segment_hlo(LanesOnly(c)))
    assert not stacked.search(_segment_hlo(CompactionModel(c)))


def test_a_frame_the_parent_wrote_resumes_to_the_parents_digest(tmp_path):
    """``tests/data/sim_frame_parent_pr52.ckpt``: written by the tree of
    commit 94b16fc (every lane built) when suspended after four segments
    of this run, which that tree finished at the digest below.  Frames
    carry states and keys, not lanes."""
    ck = str(tmp_path / "frame.ckpt")
    shutil.copy(os.path.join(ROOT, "tests", "data",
                             "sim_frame_parent_pr52.ckpt"), ck)
    sim = StreamingSimulator(
        CompactionModel(SMALL_CONFIGS["producer_on"]), n_walkers=96,
        depth=12, segment_len=4, seed=53, max_rounds=3, checkpoint_path=ck)
    res = sim.run(resume=True)
    st = res.stats
    assert sim.k.form == "drawn" and res.segments == 9
    assert st["sim_keys_digest"] == (
        "5d76b64d85bc8ea231b415eeff88653cda49a88961fd7402a2d31f398b8e0577")
    assert {k: st[k] for k in ("sim_steps", "sim_stutter_steps",
                               "sim_enabled_lanes", "sim_dup_hits")} == {
        "sim_steps": 3456, "sim_stutter_steps": 0,
        "sim_enabled_lanes": 7066, "sim_dup_hits": 2355}
    # the steps of the frame's run were the parent's, this run's drawn
    assert st["sim_drawn_steps"] == 3456


def test_the_draw_is_jax_random_choice_draw_for_draw():
    """``_draw`` counts where ``jax.random.choice`` binary-searches:
    4,096 keys over weight vectors with disabled lanes, a lone enabled
    lane and the all-disabled fallback."""
    rng = np.random.default_rng(7)
    n = 35
    valid = rng.random((4096, n)) < 0.4
    valid[:64] = False
    valid[:64, -1] = True            # the fallback: the self-loop alone
    valid[64:128] = False
    valid[np.arange(64, 128), rng.integers(0, n, 64)] = True
    w = valid.astype(np.float32)
    probs = jnp.asarray(w / np.maximum(w.sum(axis=1, keepdims=True), 1.0))
    keys = jax.random.split(jax.random.PRNGKey(52), 4096)
    ours = jax.jit(jax.vmap(E._draw))(keys, probs)
    theirs = jax.jit(jax.vmap(
        lambda k, p: jax.random.choice(k, n, p=p)))(keys, probs)
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    assert ours.dtype == jnp.int32
    assert valid[np.arange(4096), np.asarray(ours)].all()


def test_the_chunk_is_a_constant_of_the_module_and_bounds_a_step():
    """One constant a form of the step, chosen on the chip (PERF.md 6,
    PR 52 and PR 53), and the form's is the one in the kernel's key."""
    assert E.SIM_STEP_CHUNK == 1 << 14
    assert E.SIM_DRAWN_STEP_CHUNK == 1 << 20
    for form, chunk in (("drawn", E.SIM_DRAWN_STEP_CHUNK),
                        ("lanes", E.SIM_STEP_CHUNK)):
        sim = StreamingSimulator(
            FORMS[form](SMALL_CONFIGS["producer_on"]), n_walkers=64)
        assert (E.step_form(sim.model), sim.k.chunk) == (form, chunk)


# ---- step 3 and 5 (a): behaviours on request --------------------------------

def test_a_clean_check_is_correct_by_the_cells_own_comparison(clean):
    assert clean["rc"] == 0
    assert wrong([clean]) == []


def test_dumped_behaviours_are_behaviours_of_next_by_the_reference(clean):
    c = tlafmt.constants_from_cfg(CFG)
    files = REPLAY.dumped_files(clean)
    assert len(files) == K
    # K walkers spread evenly over the swarm, rotated by the seed (5),
    # of the last completed round (the second)
    assert [os.path.basename(f) for f in files] == sorted(
        f"behaviour_{ROUNDS}_{(i * WALKERS // K + 5) % WALKERS}"
        for i in range(K))
    self_loops = 0
    for path in files:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        assert REPLAY.behaviour_faults(
            c, text, DEPTH, CONFIG["assumed"]["invariants"]) == {
                "length": 0, "first_state": 0, "transition": 0,
                "early_violation": 0}
        _v, states, actions = tlafmt.parse_trace(text, c.compaction_times_limit)
        assert len(states) == DEPTH + 1 and len(actions) == DEPTH
        for s, act, t in zip(states, actions, states[1:]):
            if act == "Terminating":
                self_loops += 1
                assert s == t and ref.termination_goal(c, s)
    assert self_loops > 0  # the dump keeps the spec's own self-loop steps


def test_the_comparison_reads_a_behaviour_that_is_not_one_of_next(
        clean, tmp_path):
    """A dumped file with one state altered: the reference finds the
    step into it and the step out of it."""
    files = REPLAY.dumped_files(clean)
    prefix = str(tmp_path / "behaviour")
    for f in files:
        with open(f, encoding="utf-8") as src:
            text = src.read()
        if f == files[0]:
            text = re.sub(r"(State 5: <\w+>\n(?:.*\n)*?/\\ crashTimes = )\d+",
                          r"\g<1>7", text, count=1)
        with open(prefix + "_" + os.path.basename(f).split("_", 1)[1], "w",
                  encoding="utf-8") as dst:
            dst.write(text)
    names = wrong([dict(clean, dump_prefix=prefix)])
    assert "behaviour_wrong_transition" in names
    assert "behaviour_wrong_length_not_25" not in names
    # a file short of the depth, and a missing one
    os.remove(prefix + "_" + os.path.basename(files[1]).split("_", 1)[1])
    assert f"behaviours_dumped_differ_from_{K}" in wrong(
        [dict(clean, dump_prefix=prefix)])


def test_the_simulated_line_says_what_was_walked(clean):
    ln = REPLAY.parse_simulated_line(clean["text"])
    st = clean["stats"]
    assert ln == {
        "walkers": WALKERS, "depth": DEPTH, "segment": 24, "rounds": ROUNDS,
        "steps": WALKERS * DEPTH * ROUNDS, "dumped": K, "mismatches": 0,
        "digest": st["sim_keys_digest"]}
    assert clean["text"].rstrip().endswith(cli.simulated_line(st))
    assert clean["text"].count("Simulated: ") == 1
    assert "simulation is NOT exhaustive" in clean["text"]
    assert st["sim_dump_behaviours"] == K and st["sim_dump_mismatches"] == 0
    assert st["sim_states"] == WALKERS * (DEPTH + 1) * ROUNDS
    assert st["sim_walks"] == WALKERS * ROUNDS


def test_the_replay_check_counts_a_walker_the_scan_carried_elsewhere():
    sim = StreamingSimulator(
        CompactionModel(SMALL_CONFIGS["producer_on"]), n_walkers=32,
        depth=8, seed=1)
    states, table = sim._fresh_buffers()
    states, table, _c = sim._segment(states, table, 0, True)
    ws = jnp.asarray([0, 9, 31], jnp.uint32)
    _s0, replayed, _lanes = sim._replay(ws, 0)
    assert int(E.ptt_sim_replay_check(replayed, ws, states)) == 0
    moved = states._replace(crash=states.crash.at[9].add(1))
    assert int(E.ptt_sim_replay_check(replayed, ws, moved)) == 1


# ---- step 5 (b): the draw is uniform over the reference's successors --------

def test_the_lane_drawn_is_uniform_over_the_references_enabled_set():
    """All 4,096 walkers of one round: at step 2 (24 distinct states,
    every walker in a group large enough to test) walkers are grouped
    by the state they stand in, and within a group the lanes drawn are
    held to the uniform distribution over the reference's successor set
    of that state by a chi-square at the 0.1% level; at step 2 and at
    step 13 (where the spec's own ``Terminating`` self-loop is enabled
    for some) no walker draws a lane the reference does not enable."""
    from scipy.stats import chi2

    c = SMALL_CONFIGS["producer_on"]
    model = CompactionModel(c)
    b = 4096
    sim = StreamingSimulator(model, n_walkers=b, depth=16, seed=20261004)
    _s0, states, lanes = jax.tree.map(
        np.asarray, sim._replay(jnp.arange(b, dtype=jnp.uint32), 0))
    self_loops = 0
    for step in (2, 13):
        groups = {}
        for w in range(b):
            before = model.to_pystate(
                jax.tree.map(lambda x: x[w, step - 1], states))
            groups.setdefault(before, []).append(int(lanes[w, step]))
        stat, dof, tested = 0.0, 0, 0
        for ps, drawn in groups.items():
            enabled = set()
            for aid, child in ref.successors(c, ps):
                enabled.add(-1 if aid >= 8 else model._lane_of(aid, child))
            assert set(drawn) <= enabled, (ps, set(drawn) - enabled)
            self_loops += drawn.count(-1)
            expect = len(drawn) / len(enabled)
            if expect < 5 or len(enabled) < 2:
                continue
            tested += len(drawn)
            stat += sum(
                (drawn.count(ln) - expect) ** 2 / expect for ln in enabled)
            dof += len(enabled) - 1
        if step == 2:
            assert tested == b and dof >= 50
            assert stat < chi2.ppf(0.999, dof), (stat, dof)
    assert self_loops > 0


# ---- step 5 (c): a violation's trace replays ---------------------------------

def test_the_leak_is_found_and_its_trace_passes_the_benchmarks_replay(
        tmp_path):
    argv = ARGV[:-2] + ["-invariant", "CompactedLedgerLeak"]
    a, _err = check(argv, tmp_path, dump=False)
    assert a["rc"] == 1
    assert "Error: Invariant CompactedLedgerLeak is violated." in a["text"]
    checks = TRACE_REPLAY.compare(
        {}, {"cfg_path": CFG, "invariant": "CompactedLedgerLeak"}, [a], 5)
    bad = [c["name"] for c in checks if not c["ok"]]
    # a random walk's counterexample need not be a shortest one
    assert [n for n in bad if not n.startswith("trace_wrong_length")] == []
    # the line is printed after a violation too; nothing was dumped
    ln = REPLAY.parse_simulated_line(a["text"])
    assert ln is not None and ln["dumped"] == 0


# ---- step 5 (d): one seed, one digest ----------------------------------------

def test_one_seed_one_digest_and_another_seed_another(clean, tmp_path):
    again, _ = check(ARGV, tmp_path, seed=5)
    other, _ = check(ARGV, tmp_path, seed=6)
    d = [REPLAY.parse_simulated_line(a["text"])["digest"]
         for a in (clean, again, other)]
    assert d[0] == d[1] != d[2]
    assert wrong([clean, again]) == []
    assert wrong([clean, other]) == ["digests_differ"]


# ---- step 5 (f): a budget off a round boundary -------------------------------

def test_a_dump_needs_a_budget_that_ends_on_a_round_boundary(tmp_path):
    argv = list(ARGV)
    argv[argv.index("-sim-steps") + 1] = str(WALKERS * DEPTH + WALKERS)
    a, err = check(argv, tmp_path)
    assert a["rc"] == 2
    assert "-sim-dump" in err and "round boundary" in err
    assert f"walkers x depth = {WALKERS * DEPTH}" in err
    assert REPLAY.dumped_files(a) == []
    with pytest.raises(E.DumpBudgetError):
        StreamingSimulator(
            CompactionModel(SMALL_CONFIGS["producer_on"]), n_walkers=8,
            depth=4, time_budget_s=1.0, dump_path=str(tmp_path / "b"))


def test_the_simulate_subcommand_dumps_too(tmp_path, capsys):
    prefix = str(tmp_path / "b")
    rc = cli.main(["simulate", SPEC, "-config", CFG, "-walkers", "16",
                   "-depth", "10", "-rounds", "1", "-seed", "3",
                   "-sim-dump", prefix, "-sim-dump-num", "2"])
    out = capsys.readouterr().out
    assert rc == 0 and "2 behaviours dumped (0 replay mismatches)" in out
    assert sorted(os.listdir(tmp_path)) == ["b_1_11", "b_1_3"]


# ---- the control's reading ---------------------------------------------------

def test_the_reference_holding_the_leak_reads_early_violation_alone(
        tmp_path):
    """The control ``reference-holds-leak``: the same check, the
    reference holding every dumped state to the leak as well, on the
    cell's own binding (13% of its behaviours of depth 100 meet the
    leak; 0.2% of the small binding's of depth 24), every walker of a
    narrow swarm dumped."""
    argv = ["check", "specs/compaction.tla", "-config",
            "specs/compaction_scaled.cfg", "-simulate", "32", "-depth",
            "100", "-sim-steps", "3200", "-sim-dump-num", "32"]
    a, _err = check(argv, tmp_path)
    traffic = {"argv": argv, "cfg_path": SCALED}
    names = lambda ans: [
        c["name"] for c in REPLAY.compare(CONFIG, traffic, [ans], 5)
        if not c["ok"]]
    assert names(a) == []
    assert names(dict(a, hold_also=["CompactedLedgerLeak"])) == [
        "behaviour_wrong_early_violation"]


# ---- step 4: scopes, phases, counters ----------------------------------------

def test_host_phases_add_up_to_the_wall_and_the_meter_is_read(clean):
    st = clean["stats"]
    phases = [f"host_{p}_s" for p in spans.SIM_PHASES]
    assert all(st[p] >= 0 for p in phases)
    assert st["host_dump_s"] > 0 and st["sim_dump_s"] == st["host_dump_s"]
    total = sum(st[p] for p in phases) + st["host_unaccounted_s"]
    assert abs(total - st_wall(clean)) < 0.05
    assert abs(st["host_unaccounted_s"]) < 0.05
    for k in ("jit_host_s", "jit_body_traces", "jit_traces",
              "jit_backend_compiles"):
        assert k in st
    assert st["sim_step_chunks"] == 1
    assert "sim_peak_bytes" in st  # None on the CPU, bytes on a chip


def st_wall(answer):
    m = re.search(r"^Finished in ([\d.]+)s \(", answer["text"], re.M)
    return float(m.group(1))


def test_a_second_simulation_of_a_process_traces_nothing(tmp_path):
    check(ARGV, tmp_path, seed=5, tel=True)
    # another seed, the same programs: the base keys are arguments
    b, _ = check(ARGV, tmp_path, seed=9, tel=True)
    assert b["stats"]["jit_body_traces"] == 0
    assert b["stats"]["jit_backend_compiles"] == 0


def test_the_report_gives_the_rate_after_each_programs_first_dispatch(clean):
    m = re.search(r"steps/sec, ([\d,]+) steps/sec after each program's "
                  r"first dispatch", clean["text"])
    # depth 24 in one segment: every dispatch is the restart program,
    # and the second round's is the one after its first
    assert m and clean["stats"]["steady_steps_per_sec"] > 0


def test_the_programs_carry_the_stage_scopes():
    sim = StreamingSimulator(
        CompactionModel(SMALL_CONFIGS["producer_on"]), n_walkers=16,
        depth=8, segment_len=4)
    states, table = sim._fresh_buffers()
    text = E.ptt_sim_segment.lower(
        states, table, jnp.int32(0), *sim._bases(), k=sim.k, restart=True
    ).as_text(debug_info=True)
    for scope in ("sim_init", "sim_expand", "sim_choose", "sim_inv",
                  "sim_dup"):
        assert f"ptt.{scope}" in text, scope
    replay = E.ptt_sim_replay.lower(
        jnp.zeros((2,), jnp.uint32), jnp.int32(0), *sim._bases(), k=sim.k
    ).as_text(debug_info=True)
    assert "ptt.sim_replay" in replay and "ptt.sim_expand" not in replay


def test_depth_help_states_tlcs_default(capsys):
    with pytest.raises(SystemExit):
        cli.main(["check", "-h"])
    out = " ".join(capsys.readouterr().out.split())
    assert "TLC's -depth defaults to 100" in out
    assert "-sim-dump F" in out and "-sim-dump-num K" in out
