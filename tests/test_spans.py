"""Spans, stage scopes and the compile meter (``obs/spans.py``, ISSUE 27).

What is held here: the host phases of one ``run()`` are exclusive and
sum to its wall; scopes and spans change no behaviour (the counts below
were read off the parent commit); the lowered text of the level kernel
and of every stage jit carries the four stage names; a profiler trace
shows the phase spans nested in the run's span; the compile meter
counts per run and per thread.
"""

import glob
import importlib.util
import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from pulsar_tlaplus_tpu.engine import bodies, device_bfs
from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, assert_valid_counterexample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("ptt.expand", "ptt.probe", "ptt.compact", "ptt.append")


def _mk(c, fuse="level", invariants=(), **kw):
    return DeviceChecker(
        CompactionModel(c), invariants=invariants, sub_batch=256,
        fuse=fuse, visited_cap=1 << 12, frontier_cap=1 << 12, **kw,
    )


# ---- (f) the clock itself --------------------------------------------


def test_phase_clock_nesting_is_exclusive():
    clock = spans.PhaseClock("rid")
    with clock.phase("account"):
        time.sleep(0.02)
        with clock.phase("dispatch", level=3):
            time.sleep(0.03)
            with clock.phase("grow"):
                time.sleep(0.01)
        time.sleep(0.02)
    s = clock.seconds
    assert 0.03 <= s["account"] < 0.06  # both halves, not the inside
    assert 0.025 <= s["dispatch"] < 0.05
    assert 0.008 <= s["grow"] < 0.03
    st = clock.stats()
    total = sum(
        v for k, v in st.items()
        if k.startswith("host_") and k.endswith("_s")
    )
    assert abs(total - clock.elapsed()) < 0.005
    assert abs(st["host_unaccounted_s"]) < 0.005
    assert st["host_ckpt_s"] == 0.0  # a phase never entered still reads


def test_phase_clock_survives_an_exception_in_a_phase():
    clock = spans.PhaseClock()
    with pytest.raises(ValueError):
        with clock.phase("account"):
            with clock.phase("fetch"):
                raise ValueError("in a phase")
    assert clock._stack == []
    assert set(clock.seconds) == {"account", "fetch"}
    with clock.phase("result"):  # and goes on counting
        assert clock.seconds_of("result") >= 0.0
    assert "result" in clock.seconds


def test_level_wall_max_names_the_level_the_stretch_ends_on():
    clock = spans.PhaseClock()
    clock.level_boundary(2)
    time.sleep(0.03)
    clock.level_boundary(3)
    clock.level_boundary(4)
    st = clock.stats()
    assert st["level_wall_max_at"] == 3
    assert 0.025 <= st["level_wall_max_s"] < 0.2


# ---- (a) the keys, their sum, and the stream --------------------------


def _checker_mod():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(ROOT, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


JIT_KEYS = (
    "jit_traces", "jit_trace_s", "jit_lower_s", "jit_backend_compiles",
    "jit_compile_s", "jit_cache_hits", "jit_cache_load_s", "jit_host_s",
)


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_phases_sum_to_the_wall_and_ride_the_result_event(fuse, tmp_path):
    stream = str(tmp_path / f"spans_{fuse}.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], fuse=fuse, telemetry=stream)
    t0 = time.perf_counter()
    r = ck.run()
    wall = time.perf_counter() - t0
    st = ck.last_stats
    phase_keys = [f"host_{p}_s" for p in spans.PHASES]
    for k in (*phase_keys, "host_unaccounted_s", "level_wall_max_s",
              "level_wall_max_at", *JIT_KEYS):
        assert k in st, k
    total = sum(st[k] for k in phase_keys)
    assert abs(total - wall) <= 0.05 * wall
    assert abs(total + st["host_unaccounted_s"] - r.wall_s) <= 0.01 * wall
    assert st["host_wait_s"] == st["host_fetch_s"] > 0.0
    assert st["host_dispatch_s"] > 0.0 and st["host_account_s"] > 0.0
    assert 2 <= st["level_wall_max_at"] <= r.diameter
    assert 0.0 < st["level_wall_max_s"] <= wall
    assert st["jit_host_s"] == pytest.approx(
        st["jit_trace_s"] + st["jit_lower_s"] + st["jit_compile_s"]
        + st["jit_cache_load_s"]
    )
    with open(stream, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    (res,) = [e for e in events if e["event"] == "result"]
    for k in (*phase_keys, "host_unaccounted_s", "host_wait_s", *JIT_KEYS):
        assert k in res["stats"], k
    # the stream stays valid: ``stats`` is free-form, no schema bump
    assert _checker_mod().validate_stream(stream) == []


# ---- (b) no behaviour changes -----------------------------------------

# read off the parent commit (81af5b3), same constructor arguments
PARENT = {
    "level": dict(fetches=3, dpl=0.31),
    "stage": dict(fetches=18, dpl=4.19),
}
PARENT_LEVELS = [1, 5, 24, 56, 76, 108, 124, 128, 156, 156, 160, 192,
                 212, 56, 88, 112]
PARENT_WORK = {
    "work_init_lanes": 1, "work_probe_lanes": 47872,
    "work_compact_elems": 47872, "work_groups": 17,
    "work_append_rows": 1654, "work_expand_rows": 1654,
}
PARENT_BUG = {
    "level": dict(n=5832, levels=[729, 1458, 1458, 2187], fetches=4,
                  dpl=1.5),
    "stage": dict(n=5181, levels=[729, 1458, 1458, 1536], fetches=9,
                  dpl=14.0),
}
PARENT_BUG_ACTIONS = [
    "CompactorPhaseOne", "CompactorPhaseTwoWrite",
    "CompactorPhaseTwoUpdateContext",
]


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_counts_and_syncs_are_the_parents(fuse):
    ck = _mk(SMALL_CONFIGS["producer_on"], fuse=fuse)
    r = ck.run()
    st = ck.last_stats
    assert [int(x) for x in r.level_sizes] == PARENT_LEVELS
    assert st["stats_fetches"] == PARENT[fuse]["fetches"]
    assert st["dispatches_per_level"] == PARENT[fuse]["dpl"]
    assert {k: v for k, v in st.items()
            if k.startswith("work_")} == PARENT_WORK


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_counterexample_is_the_parents(fuse):
    inv = "DuplicateNullKeyMessage"
    ck = _mk(pe.SHIPPED_CFG, fuse=fuse, invariants=(inv,))
    r = ck.run()
    want = PARENT_BUG[fuse]
    assert r.violation == inv and r.violation_gid == 3645
    assert r.distinct_states == want["n"]
    assert [int(x) for x in r.level_sizes] == want["levels"]
    assert ck.last_stats["stats_fetches"] == want["fetches"]
    assert ck.last_stats["dispatches_per_level"] == want["dpl"]
    assert [str(a) for a in r.trace_actions] == PARENT_BUG_ACTIONS
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r.trace, r.trace_actions, inv
    )
    assert ck.last_stats["host_trace_walk_s"] > 0.0


# ---- (c) the scopes are in what is compiled ---------------------------


def _struct(args):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        args,
    )


def _recorded_jits(monkeypatch, fuse):
    """``{program name: lower()}`` of every engine program one run
    calls, to be re-lowered at the shapes it was called with: the jits
    a checker builds for itself (``device_bfs``'s ``jax.jit``) and the
    program units of ``engine/bodies.py`` it dispatches."""
    real = jax.jit
    seen = {}

    def recording_jit(fn, **kw):
        j = real(fn, **kw)

        def call(*args):
            shapes = _struct(args)
            seen.setdefault(fn.__name__, lambda: j.lower(*shapes))
            return j(*args)

        return call

    def recording_unit(name, unit):
        def call(*args, **statics):
            shapes = _struct(args)
            seen.setdefault(
                name, lambda: unit.lower(*shapes, **statics)
            )
            return unit(*args, **statics)

        return call

    monkeypatch.setattr(device_bfs.jax, "jit", recording_jit)
    for name, unit in vars(bodies).items():
        if name.startswith("ptt_"):
            monkeypatch.setattr(bodies, name, recording_unit(name, unit))
    _mk(SMALL_CONFIGS["producer_on"], fuse=fuse).run()
    monkeypatch.undo()
    return seen


def _lowered_texts(monkeypatch, fuse):
    """``{program name: lowered text}`` of every engine program one
    run calls, re-lowered at the shapes it was called with."""
    return {
        name: lower().as_text(debug_info=True)
        for name, lower in _recorded_jits(monkeypatch, fuse).items()
    }


def test_level_kernel_carries_the_four_stage_scopes(monkeypatch):
    txt = _lowered_texts(monkeypatch, "level")["ptt_level2"]
    found = set(re.findall(r"ptt\.[a-z]+", txt))
    assert set(STAGES) <= found and "ptt.levelctl" in found
    # a stage nests inside the kernel's own scope: innermost wins
    assert re.search(r"ptt\.levelctl/while/body/ptt\.probe", txt)


def test_each_stage_jit_carries_its_scope(monkeypatch):
    texts = _lowered_texts(monkeypatch, "stage")
    for name, scope in (
        ("ptt_slice", "ptt.expand"), ("ptt_expand", "ptt.expand"),
        ("ptt_fpflush2", "ptt.probe"), ("ptt_compact", "ptt.compact"),
        ("ptt_append", "ptt.append"), ("ptt_stats", "ptt.levelctl"),
        ("ptt_init", "ptt.init"), ("ptt_rehash2", "ptt.rehash"),
    ):
        assert scope in texts[name], name


@pytest.mark.parametrize("materialize", ["shift", "gather"])
def test_every_operation_of_the_rehash_lies_under_its_scope(materialize):
    """A doubling of two chunks, as compiled: the chunk loop, the
    ladder's three probe loops and the three compactions (the pack and
    two hand-overs; a loop each where they are shift passes) all carry
    ``ptt.rehash``, so no second of it reads as unscoped device time.
    The names without it are the reducers' own (a scatter's ``min``, a
    sum's ``add``), which no device event carries."""
    slots = 2 * fpset.REHASH_CHUNK
    old = tuple(
        jax.ShapeDtypeStruct((slots + 1,), jnp.uint32) for _ in range(2)
    )
    hlo = bodies.ptt_rehash2.lower(
        old, materialize=materialize
    ).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    scoped = [
        n for n in names if n.startswith("jit(ptt_rehash2)/ptt.rehash/")
    ]
    assert len(scoped) > 500
    assert {n.rsplit("/", 1)[-1] for n in set(names) - set(scoped)} <= {
        "reduce_sum", "reduce_window_sum", "scatter", "scatter-min",
        "min", "old[0]", "old[1]",
    }
    assert set(re.findall(r"ptt\.[a-z]+", hlo)) == {"ptt.rehash"}
    loops = {"shift": 4 + 3, "gather": 4}[materialize]
    assert len(re.findall(r" while\(", hlo)) == loops


# ---- (c2) the parts of a probe (ISSUE 38) ------------------------------

PARTS = ("gather", "claims_fill", "claims_bid", "write", "reread", "narrow")
ROUND = "/while/body/"  # a probe round: the body of a step's loop


def _stage_and_part(path):
    """The rule of docs/observability.md "Parts", as the benchmark's
    reader applies it: the innermost ``ptt.`` scope, and the innermost
    ``part.`` scope below it."""
    from benchmark.lib import probe_parts

    return probe_parts.stage_and_part(path, "")


def _primitive(path):
    return path.rsplit("/", 1)[-1]


@pytest.fixture(scope="module")
def flush_unit_texts():
    """The flush unit at a ``cli check`` flush's shape (65,536 lanes,
    six ladder steps), as lowered and as compiled."""
    shape = jax.ShapeDtypeStruct
    lowered = bodies.ptt_fpflush2.lower(
        tuple(shape(((1 << 17) + 1,), jnp.uint32) for _ in range(2)),
        tuple(shape((1 << 16,), jnp.uint32) for _ in range(2)),
        shape((), jnp.int32), shape((fpset.FPM_WIDE_N,), jnp.int32),
        dense_rounds=fpset.DENSE_ROUNDS, stages=fpset.STAGES,
        materialize="shift",
    )
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return lowered.as_text(debug_info=True), [
        n for n in names if n.startswith("jit(ptt_fpflush2)/")
    ]


@pytest.mark.parametrize("part", PARTS)
def test_the_flush_unit_carries_each_part_below_its_stage(
    part, flush_unit_texts,
):
    """Every part is in what is traced, below ``ptt.probe`` (a round's
    five inside the loop's body, ``narrow`` between the loops), and the
    stage the existing readers' rule reads is ``probe`` for every
    operation that carries it.  As compiled, each ladder step's loop
    keeps its part on the operations the part is about (on the CPU the
    refill's table-sized ``broadcast`` keeps no ``op_name`` at all, so
    ``claims_fill`` is held to the traced text alone)."""
    from benchmark.lib import program_spans

    lowered, names = flush_unit_texts
    where = (
        "ptt.probe/part.narrow/" if part == "narrow"
        else f"ptt.probe/while/body/part.{part}/"
    )
    assert where in lowered
    mine = [n for n in names if _stage_and_part(n) == ("probe", part)]
    assert all(program_spans.scope_of(n) == "probe" for n in mine)
    assert not [n for n in names if f"part.{part}/" in n and n not in mine]
    steps = len(fpset.ladder_steps(1 << 16, fpset.DENSE_ROUNDS, fpset.STAGES))
    assert steps == 6
    primitives = {_primitive(n) for n in mine}
    want = {
        "gather": {"gather", "eq"}, "claims_fill": set(),
        "claims_bid": {"scatter-min", "gather"}, "write": {"scatter"},
        "reread": {"gather", "eq"},
        "narrow": {"select_n", "concatenate", "slice"},
    }[part]
    assert want <= primitives
    for prim in want & {"gather", "scatter", "scatter-min"}:
        # one a key column (K = 2) a step, or one a step for the bid
        n = sum(_primitive(x) == prim for x in mine)
        assert n >= steps and n % steps == 0, (prim, n)


def test_every_table_operation_of_a_round_carries_a_part(flush_unit_texts):
    """No ``gather``, ``scatter`` or ``scatter-min`` of a probe round is
    under no part, each lies under the part that is about it, and the
    ladder's compactions are wholly under ``narrow``: what is left
    under the stage alone is the slot arithmetic, the masks, the sums
    and the loops' shells."""
    _lowered, names = flush_unit_texts
    by_part = {}
    for n in names:
        stage, part = _stage_and_part(n)
        assert stage == "probe", n
        by_part.setdefault(part, []).append(n)
    assert set(PARTS) - {"claims_fill"} | {"(no part)"} <= set(by_part)
    assert set(by_part) <= set(PARTS) | {"(no part)"}
    allowed = {
        "gather": {"gather", "reread", "claims_bid", "narrow"},
        "scatter": {"write", "narrow"}, "scatter-min": {"claims_bid"},
    }
    for part, paths in by_part.items():
        for n in paths:
            if _primitive(n) in allowed:
                assert part in allowed[_primitive(n)], n
            if part != "narrow" and part != "(no part)":
                assert ROUND + f"part.{part}/" in n, n
    loose = by_part["(no part)"]
    assert not {_primitive(n) for n in loose} & {
        "gather", "scatter", "scatter-min", "closed_call",
        "shift_right_arithmetic", "dynamic_slice", "cumsum", "sort",
    }
    # the compactions are thousands of operations (6,324 of 7,121 named
    # ones when the parts came): none of them outside ``narrow``
    outside_rounds = [n for n in loose if ROUND not in n]
    assert len(by_part["narrow"]) > 4000
    assert len(outside_rounds) < len(by_part["narrow"]) // 10
    assert len(loose) < len(names) // 6


def test_the_rehash_reads_its_own_stage_with_the_probes_parts():
    """``rehash_cols`` traces the same ``lookup_or_insert``: every
    operation with a part reads stage ``rehash`` (the existing rule:
    there is no ``ptt.probe`` in the program), and a round's parts are
    all there."""
    from benchmark.lib import program_spans

    slots = 2 * fpset.REHASH_CHUNK
    old = tuple(
        jax.ShapeDtypeStruct((slots + 1,), jnp.uint32) for _ in range(2)
    )
    lowered = bodies.ptt_rehash2.lower(old, materialize="shift")
    # (the lowered text nests its locations: an inner jit's paths are
    # relative to its call site)
    assert "while/body/part.claims_fill/broadcast_in_dim" in (
        lowered.as_text(debug_info=True)
    )
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    # (a scatter-min's own reducer keeps a path without the program's
    # name, which no device event carries: the existing rehash test)
    parted = [
        n for n in names
        if "/part." in n and n.startswith("jit(ptt_rehash2)/")
    ]
    assert len(parted) > 400
    assert {_stage_and_part(n)[0] for n in parted} == {"rehash"}
    assert {program_spans.scope_of(n) for n in parted} == {"rehash"}
    # (whether the refill's broadcast keeps its ``op_name`` as compiled
    # is the backend's: held to the traced text above)
    found = {_stage_and_part(n)[1] for n in parted}
    assert set(PARTS) - {"claims_fill"} <= found <= set(PARTS)
    # the chunk's own packing is the rehash's, under no part
    assert any(
        _stage_and_part(n) == ("rehash", "(no part)")
        and _primitive(n) == "concatenate" for n in names
    )


def test_part_scopes_hold_no_stage_prefix():
    """What reads stages must read what it read before the parts: the
    prefix matches no ``ptt.`` pattern, and a part entered under a
    stage leaves the stage's name the only ``ptt.`` one."""
    assert "ptt." not in spans.PART_PREFIX
    assert not re.search(r"ptt\.([a-z_]+)", spans.PART_PREFIX + "gather")

    @jax.jit
    def program(x):
        with spans.stage("probe"):
            with spans.part("gather"):
                return x + 1

    txt = program.lower(jnp.zeros((4,))).as_text(debug_info=True)
    assert "ptt.probe/part.gather/add" in txt
    assert set(re.findall(r"ptt\.[a-z_]+", txt)) == {"ptt.probe"}


def test_level_kernel_part_paths_are_the_same_on_a_miss_and_on_a_hit(
    monkeypatch,
):
    """The three cases of the unit test below, for the parts alone: the
    level kernel's ``op_name`` paths that carry a part are the same
    when its body is traced (a miss), when JAX's cache answers (a hit)
    and with the stage chain, which shares ``ops/fpset.py``'s bodies,
    traced first; all six parts are among them, under ``ptt.probe``."""

    def part_paths(lower):
        txt = lower().as_text(debug_info=True)
        return sorted(
            n for n in re.findall(r'"(jit\(ptt_level2\)/[^"]*)"', txt)
            if "/part." in n
        )

    level = _recorded_jits(monkeypatch, "level")["ptt_level2"]
    jax.clear_caches()
    miss = part_paths(level)
    hit = part_paths(level)
    jax.clear_caches()
    for lower in _recorded_jits(monkeypatch, "stage").values():
        lower()
    stage_first = part_paths(level)
    assert len(miss) > 30  # distinct paths: a location is written once
    assert {_stage_and_part(n) for n in miss} == {
        ("probe", p) for p in PARTS
    }
    assert miss == hit == stage_first


def test_level_kernel_scopes_are_the_same_on_a_miss_and_on_a_hit(
    monkeypatch,
):
    """The level kernel is a unit (``engine/units.py``): its
    operations carry the same ``op_name`` paths when its body is
    traced (a miss), when JAX's cache answers it (a hit), and when the
    stage chain's programs, which share its bodies, were traced
    first."""

    def op_names(lower):
        txt = lower().as_text(debug_info=True)
        return sorted(re.findall(r'"(jit\(ptt_level2\)/[^"]*)"', txt))

    level = _recorded_jits(monkeypatch, "level")["ptt_level2"]
    meter = spans.compile_meter()
    jax.clear_caches()
    before = meter.snapshot()
    miss = op_names(level)
    assert meter.since(before)["jit_body_traces"] == 1
    before = meter.snapshot()
    hit = op_names(level)
    assert meter.since(before)["jit_body_traces"] == 0
    jax.clear_caches()
    for lower in _recorded_jits(monkeypatch, "stage").values():
        lower()
    stage_first = op_names(level)
    assert len(miss) > 100
    assert any("/ptt.levelctl/while/body/ptt.probe/" in n for n in miss)
    assert miss == hit == stage_first


def test_an_ops_body_carries_the_scope_of_the_site_that_traces_it():
    """``ops/``'s bodies are plain functions with no scope and no cache
    of their own: each program that traces one (the sharded engine's,
    the seed merge, the level kernel) puts it under its own scope, and
    the code is the same whichever site traced it first."""
    from pulsar_tlaplus_tpu.ops import compact

    drop = jnp.asarray([0, 1, 0, 1, 1, 0, 0, 1, 1], jnp.uint32)
    col = jnp.arange(9, dtype=jnp.uint32)

    def site(scope):
        @jax.jit
        def program(drop, col):
            with spans.stage(scope):
                return compact.compact_by_flag(drop, (col,))[0][0]

        lowered = program.lower(drop, col)
        return lowered.as_text(debug_info=True), lowered.as_text()

    first, first_code = site("seed")
    second, second_code = site("route")
    assert "ptt.seed" in first and "ptt.route" not in first
    assert "ptt.route" in second and "ptt.seed" not in second
    assert first_code == second_code


# ---- (d) the spans are in a profiler trace ----------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.SPAN_PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return out


def test_profiler_trace_shows_phase_spans_inside_the_run_span(tmp_path):
    ck = _mk(SMALL_CONFIGS["producer_on"])
    ck.run()  # compiled, so the traced run is short
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=po)
    try:
        ck.run()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    (run,) = [e for e in ev if e[0] == "ptt:run"]
    rid = run[3]["run_id"]
    assert rid == ck._clock.run_id and len(rid) == 12
    for name in ("ptt:dispatch", "ptt:fetch"):
        inside = [e for e in ev if e[0] == name]
        assert inside, name
        for _n, s, e, stats in inside:
            assert run[1] <= s and e <= run[2]
            assert stats["run_id"] == rid
    assert any("level" in e[3] for e in ev if e[0] == "ptt:dispatch")
    assert {"ptt:init", "ptt:account", "ptt:result", "ptt:grow"} <= {
        e[0] for e in ev}


# ---- (e) the compile meter --------------------------------------------


def test_compile_meter_counts_per_run():
    ck = _mk(SMALL_CONFIGS["producer_on"])
    ck.run()
    first = dict(ck.last_stats)
    ck.run()
    second = dict(ck.last_stats)
    assert first["jit_traces"] > 0 and first["jit_trace_s"] > 0.0
    assert first["jit_backend_compiles"] > 0
    assert second["jit_traces"] < first["jit_traces"]
    assert second["jit_host_s"] < first["jit_host_s"]
    assert spans.compile_meter() is spans.compile_meter()


def test_compile_meter_threads_do_not_read_each_other():
    meter = spans.compile_meter()
    got = {}

    def compiles():
        before = meter.snapshot()
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
        got["busy"] = meter.since(before)

    def idles(started, done):
        before = meter.snapshot()
        started.set()
        done.wait(60)
        got["idle"] = meter.since(before)

    started, done = threading.Event(), threading.Event()
    idle = threading.Thread(target=idles, args=(started, done))
    idle.start()
    started.wait(60)
    busy = threading.Thread(target=compiles)
    busy.start()
    busy.join()
    done.set()
    idle.join()
    assert got["busy"]["jit_traces"] >= 1
    assert got["busy"]["jit_backend_compiles"] >= 1
    assert got["idle"]["jit_traces"] == 0
    assert got["idle"]["jit_host_s"] == 0.0


# ---- (g) what a dispatch is made of (ISSUE 54) --------------------------

SPLIT_KEYS = (
    "dispatch_calls", "dispatch_call_s", "dispatch_uploads",
    "dispatch_upload_s", "dispatch_jit_s", "dispatch_python_s",
    "dispatch_by_program", "calls_by_phase", "programs_by_phase",
)
# the stage counters ``dispatches_per_level`` is made of, and the
# programs whose calls each one counts
STAGE_PROGRAMS = {
    "flush": ("ptt_fpflush2",), "compact": ("ptt_compact",),
    "append": ("ptt_append",), "expand": ("ptt_expand",),
    "fused": ("ptt_level2",), "sieve": ("ptt_spill_sieve",),
    "unflag": ("ptt_spill_unflag",),
    "evict": ("ptt_spill_evict", "ptt_spill_rehash"),
}


def calls_of(st, program, prefix=""):
    """Calls of ``program`` under every phase, from a result's stats."""
    tables = [st[prefix + "dispatch_by_program"]]
    tables += st[prefix + "programs_by_phase"].values()
    return sum(t[program][0] for t in tables if program in t)


def assert_split_adds_up(st, phase="dispatch"):
    """The three parts are the phase, within a millisecond, the jit
    seconds lie inside the calls', and the table is the totals."""
    parts = (st[f"{phase}_call_s"] + st[f"{phase}_upload_s"]
             + st[f"{phase}_python_s"])
    assert abs(parts - st[f"host_{phase}_s"]) < 1e-3
    assert 0.0 <= st[f"{phase}_jit_s"] <= st[f"{phase}_call_s"] + 1e-9
    assert st[f"{phase}_python_s"] > -1e-6
    table = st[f"{phase}_by_program"].values()
    assert sum(r[0] for r in table) == st[f"{phase}_calls"]
    assert sum(r[2] for r in table) == st[f"{phase}_uploads"]
    assert sum(r[1] for r in table) == pytest.approx(
        st[f"{phase}_call_s"], abs=1e-4)
    assert sum(r[3] for r in table) == pytest.approx(
        st[f"{phase}_upload_s"], abs=1e-4)


def test_call_and_upload_are_overlays_of_the_open_phase():
    clock = spans.PhaseClock("rid")
    with clock.phase("account"):
        with clock.phase("dispatch", level=2):
            with clock.upload("ptt_a", 3):
                time.sleep(0.01)
            with clock.call("ptt_a"):
                time.sleep(0.02)
            time.sleep(0.01)
            with clock.phase("spill"):
                with clock.call("ptt_b"):
                    time.sleep(0.01)
            with clock.call("ptt_a"):
                pass
    with clock.call("ptt_c"):  # under no phase: kept, under ""
        pass
    st = clock.stats()
    for k in SPLIT_KEYS:
        assert k in st, k
    assert st["dispatch_calls"] == 2 and st["dispatch_uploads"] == 3
    assert 0.018 <= st["dispatch_call_s"] < 0.04
    assert 0.008 <= st["dispatch_upload_s"] < 0.03
    assert 0.008 <= st["dispatch_python_s"] < 0.03
    assert st["dispatch_jit_s"] == 0.0
    assert_split_adds_up(st)
    a = st["dispatch_by_program"]["ptt_a"]
    assert a[0] == 2 and a[2] == 3 and list(st["dispatch_by_program"]) == [
        "ptt_a"]
    # a call under another phase is that phase's, and no phase's seconds
    # moved: the overlays add nothing to the clock
    assert st["calls_by_phase"]["spill"][0] == 1
    assert st["calls_by_phase"][""][0] == 1
    assert st["programs_by_phase"]["spill"]["ptt_b"][0] == 1
    assert 0.008 <= st["host_spill_s"] < 0.03
    assert 0.035 <= st["host_dispatch_s"] < 0.08
    total = sum(v for k, v in st.items()
                if k.startswith("host_") and k.endswith("_s"))
    assert abs(total - clock.elapsed()) < 0.005


def test_a_call_counts_even_where_the_program_raises():
    clock = spans.PhaseClock()
    with clock.phase("dispatch"):
        with pytest.raises(ValueError):
            with clock.call("ptt_a"):
                raise ValueError("in a call")
    st = clock.call_stats()
    assert st["dispatch_calls"] == 1 and clock._stack == []


def test_call_charges_the_meters_seconds_inside_it():
    """A program's first call traces, lowers and compiles inside the
    call: ``jit_s``.  Its second is JAX's cache's: none."""
    fn = jax.jit(lambda x: (x * 5 + 2).sum())
    x = jnp.arange(11)
    clock = spans.PhaseClock()
    with clock.phase("dispatch"):
        with clock.call("first"):
            fn(x)
        with clock.call("second"):
            fn(x)
    by = clock.call_stats()["dispatch_by_program"]
    assert 0.0 < by["first"][4] <= by["first"][1]
    assert by["second"][4] == 0.0 and by["second"][1] < by["first"][1]


def test_the_meters_running_total_is_jit_host_s():
    meter = spans.compile_meter()
    before = meter.snapshot()
    jax.jit(lambda x: x * 7 - 1)(jnp.arange(5)).block_until_ready()
    d = meter.since(before)
    total = meter.snapshot()["host_s"] - before["host_s"]
    assert total > 0.0
    # a compile request spans its cache load; else the sums are one
    assert total == pytest.approx(d["jit_host_s"], rel=1e-6) or (
        d["jit_cache_load_s"] > 0.0 and total <= d["jit_host_s"])


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_dispatch_parts_add_up_and_name_every_program(fuse, tmp_path):
    stream = str(tmp_path / f"split_{fuse}.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], fuse=fuse, telemetry=stream)
    r = ck.run()
    st = ck.last_stats
    for k in SPLIT_KEYS:
        assert k in st, k
    assert_split_adds_up(st)
    by = st["dispatch_by_program"]
    want = {"ptt_init", "ptt_fpflush2", "ptt_compact", "ptt_append"}
    want |= ({"ptt_level2"} if fuse == "level"
             else {"ptt_slice", "ptt_expand"})
    assert set(by) == want
    # the two counts of a dispatch: the clock's calls of the stage
    # programs are the stage counters dispatches_per_level sums
    stages = {k[len("stage_"):-len("_n")]: v for k, v in st.items()
              if k.startswith("stage_") and k.endswith("_n")}
    assert stages and set(stages) <= set(STAGE_PROGRAMS)
    for stage, n in stages.items():
        assert sum(calls_of(st, p) for p in STAGE_PROGRAMS[stage]) == n
    assert st["dispatches_per_level"] == round(
        sum(stages.values()) / r.diameter, 2)
    # what a "dispatch" does not count: the init program, the slice
    # inside an expand, the stats program of a fetch, the growers
    assert by["ptt_init"][0] == 1
    if fuse == "stage":
        assert by["ptt_slice"][0] == by["ptt_expand"][0]
        assert by["ptt_expand"][2] == 4 * by["ptt_expand"][0]
        assert by["ptt_append"][2] == 5 * by["ptt_append"][0]
    else:
        assert by["ptt_level2"][2] == 7 * by["ptt_level2"][0]
    others = st["programs_by_phase"]
    assert others["fetch"]["ptt_stats"][0] == (
        st["stats_fetches"] - stages.get("fused", 0))
    assert set(others["grow"]) <= {"ptt_rehash2", "ptt_grow"}
    assert others["grow"]["ptt_rehash2"][0] == st["grow_rehashes"]
    assert st["calls_by_phase"]["grow"][0] == sum(
        r_[0] for r_ in others["grow"].values())
    # what a first run traced and compiled, it did inside its calls (the
    # units are traced once a process: maybe by a test before this one)
    assert 0.0 <= st["dispatch_jit_s"] <= st["jit_host_s"] + 1e-6
    with open(stream, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    (res,) = [e for e in events if e["event"] == "result"]
    for k in SPLIT_KEYS:
        assert k in res["stats"], k
    assert_split_adds_up(res["stats"])
    assert res["stats"]["dispatch_by_program"].keys() == by.keys()
    assert _checker_mod().validate_stream(stream) == []


class _RecordedSpan:
    def __init__(self, rec, name, fields):
        self.rec, self.name, self.fields = rec, name, fields

    def __enter__(self):
        self.rec.seen.append((self.name, tuple(self.rec.open), self.fields))
        self.rec.open.append(self.name)
        return self

    def __exit__(self, *exc):
        assert self.rec.open.pop() == self.name
        return False


class _Recorder:
    """Stands in for ``spans.span``: the spans a run opens, in order,
    each with the names open around it."""

    def __init__(self):
        self.open, self.seen = [], []

    def __call__(self, name, **fields):
        return _RecordedSpan(self, name, fields)


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_every_call_and_upload_span_lies_inside_a_phase(fuse, monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(spans, "span", rec)
    ck = _mk(SMALL_CONFIGS["producer_on"], fuse=fuse)
    ck.run()
    assert rec.open == []
    st = ck.last_stats
    overlays = [s for s in rec.seen
                if s[0].startswith("call.") or s[0] == "upload"]
    assert overlays
    for name, around, fields in overlays:
        # the innermost span around it is a phase of the clock, and the
        # run's span is around that
        assert around and around[-1] in spans.PHASES, (name, around)
        assert around[0] == "run"
        if name == "upload":
            assert fields["program"].startswith("ptt_")
    # one span a call, one an upload group: none per row, lane or round
    programs = {p for t in (st["dispatch_by_program"],
                            *st["programs_by_phase"].values()) for p in t}
    for p in programs:
        assert len([s for s in overlays if s[0] == "call." + p]) == (
            calls_of(st, p)), p
    groups = len([s for s in overlays if s[0] == "upload"])
    n_up = st["dispatch_uploads"] + sum(
        v[2] for v in st["calls_by_phase"].values())
    assert 0 < groups <= n_up
    assert groups <= st["dispatch_calls"] + sum(
        v[0] for v in st["calls_by_phase"].values())


def test_spans_py_alone_constructs_a_trace_annotation():
    pkg = os.path.join(ROOT, "pulsar_tlaplus_tpu")
    hits = []
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            if "TraceAnnotation" in f.read():
                hits.append(os.path.relpath(path, pkg))
    assert hits == [os.path.join("obs", "spans.py")]


def test_profiler_trace_shows_call_spans_inside_the_dispatch_span(tmp_path):
    ck = _mk(SMALL_CONFIGS["producer_on"], fuse="stage")
    ck.run()  # compiled, so the traced run is short
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=po)
    try:
        ck.run()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    st = ck.last_stats
    phases = [e for e in ev if e[0] == "ptt:dispatch"]
    calls = [e for e in ev if e[0] == "ptt:call.ptt_fpflush2"]
    ups = [e for e in ev if e[0] == "ptt:upload"]
    assert len(calls) == st["dispatch_by_program"]["ptt_fpflush2"][0]
    assert {e[3]["program"] for e in ups} >= {"ptt_fpflush2", "ptt_append"}
    # an untiered run makes every flush's calls and uploads under dispatch
    for _n, s, e, stats in calls + ups:
        if stats.get("program", "ptt_fpflush2") in ("ptt_fpflush2",
                                                    "ptt_append"):
            assert any(p[1] <= s and e <= p[2] for p in phases)
