"""Scopes, spans and counters of the sharded engine
(``engine/sharded_device.py``, ISSUE 29), on the virtual CPU mesh.

What is held here: ``cli check -workers 4`` reaches the reference's
verdict, count, diameter and every level size; the host phases of one
``ShardedDeviceChecker.run()`` are exclusive and sum to its wall; the
``result`` event carries them with the compile meter and the route
counters; a lane sent through the key exchange meets its owner's table
once; an overflowing exchange is retried and counted; the counters add no
stats fetch (the counts below were read off the parent commit); every
program the engine builds is named ``ptt_shard_*`` and carries its stage
scope.
"""

import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.engine import sharded_device
from pulsar_tlaplus_tpu.engine.sharded_device import ShardedDeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS
from tests.test_spans import JIT_KEYS, ROOT, _checker_mod, _host_events

SPEC = f"{ROOT}/specs/compaction.tla"
LEVEL_LINE = re.compile(r"^\s*level (\d+): \+(\d+) \(total (\d+),", re.M)
ROUTE_KEYS = (
    "route_lanes", "route_rounds", "route_capacity_lanes",
    "route_rounds_by_capacity", "route_overflows", "shard_imbalance_pct",
    "producer_imbalance_pct",
)


def _mk(c, n=4, **kw):
    kw.setdefault("sub_batch", 128)
    kw.setdefault("visited_cap", 1 << 10)
    return ShardedDeviceChecker(
        CompactionModel(c), n_devices=n, invariants=(), **kw
    )


def _reference_levels(c):
    """Level sizes by the oracle's own breadth-first search."""
    seen = set(pe.initial_states(c))
    frontier, sizes = list(seen), [len(seen)]
    while frontier:
        new = []
        for s in frontier:
            for _a, t in pe.successors(c, s):
                if t not in seen:
                    seen.add(t)
                    new.append(t)
        if new:
            sizes.append(len(new))
        frontier = new
    return sizes


def _cfg_text(c):
    def tf(b):
        return "TRUE" if b else "FALSE"

    def ints(n):
        return "{" + ", ".join(str(i) for i in range(1, n + 1)) + "}"

    with open(f"{ROOT}/specs/compaction_9m.cfg", encoding="utf-8") as f:
        text = f.read()
    for name, value in (
        ("MessageSentLimit", c.message_sent_limit),
        ("CompactionTimesLimit", c.compaction_times_limit),
        ("ModelConsumer", tf(c.model_consumer)),
        ("ConsumeTimesLimit", c.consume_times_limit),
        ("KeySpace", ints(c.num_keys)),
        ("ValueSpace", ints(c.num_values)),
        ("RetainNullKey", tf(c.retain_null_key)),
        ("MaxCrashTimes", c.max_crash_times),
        ("ModelProducer", tf(c.model_producer)),
    ):
        text, n = re.subn(
            rf"\b{name} = (\{{[^}}]*\}}|\w+)", f"{name} = {value}", text
        )
        assert n == 1, name
    return text


# ---- the path a user takes: cli check -workers 4, to a verdict ---------


@pytest.mark.parametrize("name", ["producer_on", "two_crashes"])
def test_cli_workers4_reaches_the_reference_verdict(name, tmp_path, capsys):
    c = SMALL_CONFIGS[name]
    cfg = tmp_path / f"{name}.cfg"
    cfg.write_text(_cfg_text(c))
    stream = tmp_path / "tel.jsonl"
    rc = cli.main([
        "check", SPEC, "-config", str(cfg), "-workers", "4",
        "-chunk", "256", "-telemetry", str(stream),
    ])
    out, err = capsys.readouterr()
    want = _reference_levels(c)
    assert rc == 0
    assert "maps to -sharded 4" in out
    assert (
        f"{sum(want)} distinct states found, search depth (diameter) "
        f"{len(want)}." in out
    )
    rows = [tuple(int(x) for x in m.groups())
            for m in LEVEL_LINE.finditer(err)]
    assert [r[0] for r in rows] == list(range(2, len(want) + 1))
    assert [rows[0][2] - rows[0][1]] + [r[1] for r in rows] == want
    events = [json.loads(x) for x in stream.read_text().splitlines() if x]
    (hdr,) = [e for e in events if e["event"] == "run_header"]
    (res,) = [e for e in events if e["event"] == "result"]
    assert hdr["engine"] == "sharded_device" and hdr["n_devices"] == 4
    assert res["level_sizes"] == want and not res["truncated"]
    st = res["stats"]
    assert st["fpset_failures"] == 0 and st["hbm_recovered"] == 0
    assert st["route_lanes"] == st["fpset_valid_lanes"] > sum(want)
    assert st["route_overflows"] == 0


# ---- the keys, their sum, and the stream -------------------------------


def test_phases_sum_to_the_wall_and_ride_the_result_event(tmp_path):
    stream = str(tmp_path / "sharded.jsonl")
    ck = _mk(SMALL_CONFIGS["producer_on"], telemetry=stream)
    t0 = time.perf_counter()
    r = ck.run()
    wall = time.perf_counter() - t0
    st = ck.last_stats
    phase_keys = [f"host_{p}_s" for p in spans.PHASES]
    for k in (*phase_keys, "host_unaccounted_s", "level_wall_max_s",
              "level_wall_max_at", "dispatches_per_level", *JIT_KEYS,
              *ROUTE_KEYS):
        assert k in st, k
    total = sum(st[k] for k in phase_keys)
    assert abs(total + st["host_unaccounted_s"] - wall) <= 0.01 * wall
    assert abs(total - r.wall_s) <= 0.02 * wall
    assert abs(st["host_unaccounted_s"]) <= 0.01 * wall
    assert st["host_wait_s"] == st["host_fetch_s"] > 0.0
    for p in ("init", "grow", "dispatch", "account", "result"):
        assert st[f"host_{p}_s"] > 0.0, p
    assert st["host_spill_s"] == st["host_seed_load_s"] == 0.0
    assert 2 <= st["level_wall_max_at"] <= r.diameter
    assert 0.0 < st["level_wall_max_s"] <= wall
    assert st["jit_traces"] > 0 and st["jit_backend_compiles"] > 0
    assert st["jit_host_s"] == pytest.approx(
        st["jit_trace_s"] + st["jit_lower_s"] + st["jit_compile_s"]
        + st["jit_cache_load_s"]
    )
    with open(stream, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    (res,) = [e for e in events if e["event"] == "result"]
    for k in (*phase_keys, "host_unaccounted_s", "host_wait_s",
              "level_wall_max_s", "dispatches_per_level", *JIT_KEYS,
              *ROUTE_KEYS):
        assert k in res["stats"], k
    assert _checker_mod().validate_stream(stream) == []


def test_a_second_run_starts_its_counters_again():
    ck = _mk(SMALL_CONFIGS["producer_on"])
    ck.run()
    first = dict(ck.last_stats)
    ck.run()
    second = dict(ck.last_stats)
    for k in ("route_lanes", "route_rounds"):
        assert second[k] == first[k], k
    # the tables keep the size they grew to: no rehash the second time
    assert 0 < second["dispatches_per_level"] < first["dispatches_per_level"]
    assert second["jit_traces"] < first["jit_traces"]
    assert second["jit_host_s"] < first["jit_host_s"]


def test_host_seeded_run_charges_the_seed_load():
    c = SMALL_CONFIGS["producer_on"]
    seed = CompactionModel(c).host_seed(max_level_states=40, max_total=120)
    ck = _mk(c)
    r = ck.run(seed=seed)
    assert r.distinct_states == 1654
    st = ck.last_stats
    assert st["host_seed_load_s"] > 0.0
    # seed keys reach their owners through the same exchange
    assert st["route_lanes"] == st["fpset_valid_lanes"]


# ---- what is new in it: the exchange and the owner map -----------------

# read off the parent commit (2b38b08): ``stats_fetches`` of the same
# constructor arguments.  The route state rides the one stats matrix.
PARENT_LEVELS = {
    "producer_on": [1, 5, 24, 56, 76, 108, 124, 128, 156, 156, 160, 192,
                    212, 56, 88, 112],
    "two_crashes": [36, 72, 108, 144, 180, 180, 252, 252, 216, 324, 324,
                    288, 360, 360, 180, 324, 324, 72, 72, 72],
}
PARENT = [
    ("producer_on", dict(n=4), dict(fetches=18, valid=2598)),
    ("producer_on", dict(n=4, route_slack=0.03),
     dict(fetches=25, valid=2839)),
    ("two_crashes", dict(n=2, flush_factor=3),
     dict(fetches=22, valid=5976)),
    ("producer_on", dict(n=8, n_slices=2), dict(fetches=18, valid=2598)),
]


@pytest.mark.parametrize(
    "name,kw,want", PARENT,
    ids=["1d", "1d-overflow", "flush-factor", "2d"],
)
def test_route_counters_ride_the_parents_fetches(name, kw, want):
    ck = _mk(SMALL_CONFIGS[name], **kw)
    r = ck.run()
    st = ck.last_stats
    assert [int(x) for x in r.level_sizes] == PARENT_LEVELS[name]
    assert st["stats_fetches"] == ck._fetch_n == want["fetches"]
    # each lane sent meets its owner's table once: no padding lane is
    # counted on either side (an empty slot of a plane is all-sentinel)
    assert st["route_lanes"] == st["fpset_valid_lanes"] == want["valid"]
    by_cap = st["route_rounds_by_capacity"]
    assert sum(by_cap.values()) == st["route_rounds"] > 0
    assert st["route_capacity_lanes"] == ck.route_cap
    assert str(ck.route_cap) in by_cap
    if "route_slack" in kw:
        # starved: levels were retried at doubled capacities, and the
        # rounds of every attempt are counted at the capacity they ran
        assert st["route_overflows"] >= 1 and len(by_cap) > 1
        assert ck.route_slack == 0.03 * 2 ** st["route_overflows"]
    else:
        assert st["route_overflows"] == 0 and len(by_cap) == 1
    owned = ck.last_stats_matrix[:, 1].astype(float)
    assert owned.sum() == r.distinct_states
    assert st["shard_imbalance_pct"] == pytest.approx(
        100.0 * (owned.max() / owned.mean() - 1.0), abs=1e-3
    )
    # discovery stays on the producing shard: producer_on has one
    # initial state, so one shard stores and expands every state;
    # two_crashes has 36, striped over the shards
    stored = ck.last_stats_matrix[:, 0]
    if name == "producer_on":
        assert sorted(stored)[-2:] == [0, r.distinct_states]
        assert st["producer_imbalance_pct"] == 100.0 * (ck.N - 1)
    else:
        assert stored.min() > 0 and st["producer_imbalance_pct"] < 50.0


def test_one_shard_exchanges_nothing():
    ck = _mk(SMALL_CONFIGS["producer_on"], n=1)
    r = ck.run()
    st = ck.last_stats
    assert r.distinct_states == 1654
    assert st["route_lanes"] == st["route_rounds"] == 0
    assert st["route_capacity_lanes"] == 0
    assert st["route_rounds_by_capacity"] == {}
    assert st["shard_imbalance_pct"] == st["producer_imbalance_pct"] == 0.0
    assert st["dispatches_per_level"] > 0


def test_dispatches_per_level_counts_every_program_of_the_run():
    ck = _mk(SMALL_CONFIGS["producer_on"])
    calls = []
    real = ck._program

    def counting(fn, donate=(), exchange=False):
        call = real(fn, donate, exchange)

        def counted(*args):
            calls.append(fn.__name__)
            return call(*args)

        return counted

    ck._program = counting
    r = ck.run()
    st = ck.last_stats
    assert st["dispatches_per_level"] == round(len(calls) / r.diameter, 2)
    assert set(calls) == {
        "ptt_shard_init", "ptt_shard_round", "ptt_shard_flush",
        "ptt_shard_compact", "ptt_shard_append", "ptt_shard_stats",
        "ptt_shard_rehash",
    }
    # a round, an initial round: each holds one key exchange
    assert st["route_rounds"] == sum(
        c in ("ptt_shard_init", "ptt_shard_round") for c in calls
    )
    assert calls.count("ptt_shard_stats") == st["stats_fetches"]
    # the doublings' counters ride the fetch that reads their failure
    # counts: keys moved, and the lanes the ladder presented for them
    assert calls.count("ptt_shard_rehash") >= 1
    assert 0 < st["grow_rehash_keys"] <= st["grow_rehash_lane_rounds"]


# ---- the scopes are in what is compiled --------------------------------


def _shape(a):
    sh = getattr(a, "sharding", None)
    return jax.ShapeDtypeStruct(
        jnp.shape(a), jnp.result_type(a),
        sharding=sh if isinstance(sh, NamedSharding) else None,
    )


def _lowered_texts(monkeypatch, run):
    """``{jitted function name: lowered text}`` of every program the
    engine builds in ``run``, re-lowered at the shapes it was called
    with."""
    real = jax.jit
    seen = {}

    def recording_jit(fn, **kw):
        j = real(fn, **kw)

        def call(*args):
            seen.setdefault(fn.__name__, (j, jax.tree.map(_shape, args)))
            return j(*args)

        return call

    monkeypatch.setattr(sharded_device.jax, "jit", recording_jit)
    run()
    monkeypatch.undo()
    assert {n for n in seen if not n.startswith("ptt_")} == {
        "<lambda>"}  # the buffer fill: no scope, so no name to guard
    return {
        name: j.lower(*shapes).as_text(debug_info=True)
        for name, (j, shapes) in seen.items() if name.startswith("ptt_")
    }


def test_every_program_is_named_and_carries_its_scopes(monkeypatch):
    c = SMALL_CONFIGS["producer_on"]
    seed = CompactionModel(c).host_seed(max_level_states=40, max_total=120)

    def run():
        _mk(c).run()
        _mk(c).run(seed=seed)

    texts = _lowered_texts(monkeypatch, run)
    assert set(texts) == {
        "ptt_shard_init", "ptt_shard_round", "ptt_shard_flush",
        "ptt_shard_compact", "ptt_shard_append", "ptt_shard_stats",
        "ptt_shard_rehash", "ptt_shard_seed_write",
        "ptt_shard_seed_round",
    }
    for name, scopes in (
        ("ptt_shard_init", {"ptt.init", "ptt.route"}),
        ("ptt_shard_round", {"ptt.expand", "ptt.route"}),
        ("ptt_shard_flush", {"ptt.probe", "ptt.route"}),
        ("ptt_shard_compact", {"ptt.compact"}),
        ("ptt_shard_append", {"ptt.append"}),
        ("ptt_shard_stats", {"ptt.levelctl"}),
        ("ptt_shard_rehash", {"ptt.rehash"}),
        ("ptt_shard_seed_write", {"ptt.seed"}),
        ("ptt_shard_seed_round", {"ptt.seed", "ptt.route"}),
    ):
        assert set(re.findall(r"ptt\.[a-z]+", texts[name])) == scopes, name
    # both exchanges lie under the route scope, innermost
    rnd, fl = texts["ptt_shard_round"], texts["ptt_shard_flush"]
    assert re.search(r"ptt\.expand/ptt\.route/all_to_all", rnd)
    assert re.search(r"ptt\.probe/ptt\.route/all_to_all", fl)
    assert not re.search(r"ptt\.route/[^\"]*ptt\.(expand|probe)", rnd + fl)


# ---- the spans are in a profiler trace ---------------------------------


def test_profiler_trace_shows_phase_spans_inside_the_run_span(tmp_path):
    ck = _mk(SMALL_CONFIGS["producer_on"])
    ck.run()  # compiled, so the traced run is short
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=po)
    try:
        r = ck.run()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    (run,) = [e for e in ev if e[0] == "ptt:run"]
    rid = run[3]["run_id"]
    assert rid == ck._clock.run_id and len(rid) == 12
    for name in ("ptt:dispatch", "ptt:fetch", "ptt:grow"):
        inside = [e for e in ev if e[0] == name]
        assert inside, name
        for _n, s, e, stats in inside:
            assert run[1] <= s and e <= run[2]
            assert stats["run_id"] == rid
    levels = {int(e[3]["level"]) for e in ev
              if e[0] == "ptt:dispatch" and "level" in e[3]}
    assert levels == set(range(1, r.diameter + 2))
    assert {"ptt:init", "ptt:account", "ptt:result"} <= {e[0] for e in ev}
    assert np.isclose(
        sum(e[2] - e[1] for e in ev if e[0] == "ptt:run") / 1e9,
        r.wall_s, rtol=0.05,
    )


# ---- what a sharded run's dispatches are made of (ISSUE 54) -------------

# read off the parent commit (6085604), same constructor arguments: every
# shard's parent and lane logs up to its count (the discovery order), and
# the counterexample of the shipped binding's published bug
PARENT_LOGS_SHA256 = (
    "0aac45d6414e0fe345229043e0639224ec53ad34776b76baddc313db8cdab7c1"
)
PARENT_BUG = dict(
    n=5832, gid=915, levels=[729, 1458, 1458, 2187],
    actions=["CompactorPhaseOne", "CompactorPhaseTwoWrite",
             "CompactorPhaseTwoUpdateContext"],
)
# the programs ``_dispatch_n`` counts: those built by ``_program``
COUNTED = (
    "ptt_shard_round", "ptt_shard_init", "ptt_shard_flush",
    "ptt_shard_compact", "ptt_shard_append", "ptt_shard_stats",
    "ptt_shard_rehash", "ptt_shard_seed_write", "ptt_shard_seed_round",
)


@pytest.mark.parametrize("seeded", [False, True], ids=["fresh", "seeded"])
def test_dispatch_parts_add_up_on_the_mesh(seeded, tmp_path):
    from tests.test_spans import (
        SPLIT_KEYS, assert_split_adds_up, calls_of,
    )

    c = SMALL_CONFIGS["producer_on"]
    stream = str(tmp_path / "split.jsonl")
    ck = _mk(c, telemetry=stream)
    seed = (CompactionModel(c).host_seed(max_level_states=40, max_total=120)
            if seeded else None)
    r = ck.run(seed=seed)
    assert r.distinct_states == 1654
    st = ck.last_stats
    for k in SPLIT_KEYS:
        assert k in st, k
    assert_split_adds_up(st)
    by = st["dispatch_by_program"]
    want = {"ptt_shard_round", "ptt_shard_flush", "ptt_shard_compact",
            "ptt_shard_append"} | (set() if seeded else {"ptt_shard_init"})
    assert set(by) == want
    assert by["ptt_shard_compact"][0] == st["stage_compact_n"]
    assert by["ptt_shard_round"][2] == 2 * by["ptt_shard_round"][0]
    # the two counts of a dispatch: every call of a program built by
    # ``_program``, under whatever phase, is one of ``_dispatch_n``
    assert sum(calls_of(st, p) for p in COUNTED) == ck._dispatch_n
    assert st["dispatches_per_level"] == round(
        ck._dispatch_n / r.diameter, 2)
    others = st["programs_by_phase"]
    assert others["fetch"]["ptt_shard_stats"][0] == st["stats_fetches"]
    assert others["grow"]["ptt_shard_rehash"][0] > 0
    # the fills of a growth and of the first buffers count on the clock
    # and are no dispatch of the engine's
    assert others["grow"]["fill"][0] > 0 and others["init"]["fill"][0] > 0
    # a level's two bounds are made device arrays once for its rounds
    bounds = others["account"]["ptt_shard_round"]
    assert bounds[0] == 0 and bounds[2] % 2 == 0
    assert 0 < bounds[2] // 2 <= r.diameter
    if seeded:
        assert others["seed_load"]["ptt_shard_seed_write"][0] > 0
        assert others["seed_load"]["ptt_shard_seed_round"][0] > 0
        assert others["seed_load"]["ptt_shard_flush"][0] > 0
    with open(stream, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    (res,) = [e for e in events if e["event"] == "result"]
    for k in SPLIT_KEYS:
        assert k in res["stats"], k
    assert _checker_mod().validate_stream(stream) == []


def test_hoisting_the_scalars_changed_no_state_on_the_mesh():
    import hashlib

    ck = _mk(SMALL_CONFIGS["two_crashes"])
    r = ck.run()
    assert [int(x) for x in r.level_sizes] == PARENT_LEVELS["two_crashes"]
    counts = ck.last_stats_matrix[:, 0]
    assert [int(x) for x in counts] == [1035] * 4
    h = hashlib.sha256()
    for s in range(ck.N):
        for k in ("parent", "lane"):
            h.update(np.asarray(
                ck.last_bufs[k][s, : counts[s]]).astype(np.int32).tobytes())
    assert h.hexdigest() == PARENT_LOGS_SHA256


def test_the_sharded_counterexample_is_the_parents():
    from tests.helpers import assert_valid_counterexample

    inv = "DuplicateNullKeyMessage"
    ck = ShardedDeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), n_devices=4, invariants=(inv,),
        sub_batch=128, visited_cap=1 << 10,
    )
    r = ck.run()
    assert r.violation == inv and r.violation_gid == PARENT_BUG["gid"]
    assert r.distinct_states == PARENT_BUG["n"]
    assert [int(x) for x in r.level_sizes] == PARENT_BUG["levels"]
    assert [str(a) for a in r.trace_actions] == PARENT_BUG["actions"]
    assert_valid_counterexample(pe.SHIPPED_CFG, r.trace, r.trace_actions, inv)
