"""The growth counters of ``DeviceChecker`` (ISSUE 34).

A run that starts on small tables crosses its growth tiers lazily: table
doublings with an on-device rehash, row-store and log doublings by
``bodies.ptt_grow``.  What is held here: such a run finds what the plain
reference (``ref/pyeval.py``) finds; ``grow_*`` in ``last_stats`` equal
what the tier arithmetic (``_next_table`` / ``_next_cap`` /
``_fused_tier_triples``) gives for the needs the run presented; counting
costs the device nothing (fetches and dispatches as the parent commit's);
and a second ``cli check`` of one binding crosses the same tiers on the
programs the first one built.
"""

import json
import time

import jax.numpy as jnp
import pytest

from pulsar_tlaplus_tpu.engine import bodies
from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.test_units import VERDICT, _cli_check
from tests.test_units_programs import CFG_253K

# read off the parent commit (3e0f0bf), same constructor arguments
PARENT = {
    "level": dict(fetches=21, dpl=1.3),
    "stage": dict(fetches=221, dpl=143.5),
}
GROW_KEYS = (
    "grow_events", "grow_rehashes", "grow_rehash_slots",
    "grow_copy_bytes", "grow_tiers_final", "grow_wall_max_s",
    "grow_wall_max_at", "grow_rehash_keys", "grow_rehash_lane_rounds",
)


def _mk(fuse):
    # the smallest tables the engine takes (2^11 slots, 2^10 rows): the
    # shipped binding's 45,198 states cross six tiers of each
    return DeviceChecker(
        CompactionModel(pe.SHIPPED_CFG), sub_batch=64, fuse=fuse,
        visited_cap=1 << 8, frontier_cap=1 << 6,
    )


@pytest.fixture(scope="module")
def reference_levels():
    """Level sizes of the shipped binding by the plain reference."""
    c = pe.SHIPPED_CFG
    seen = set(pe.initial_states(c))
    frontier, sizes = list(seen), []
    while frontier:
        sizes.append(len(frontier))
        nxt = []
        for s in frontier:
            for _a, t in pe.successors(c, s):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return sizes


def _record_growth_calls(ck):
    """``[(grower, need)]`` of the outermost growth calls of the runs to
    come (a grower called by another is part of that one's event)."""
    calls = []

    def recording(name, grower):
        def call(bufs, need):
            if not ck._clock.open("grow"):
                calls.append((name, need))
            return grower(bufs, need)

        return call

    for name in ("_grow_fused", "_grow_visited", "_grow_store",
                 "_grow_logs"):
        setattr(ck, name, recording(name, getattr(ck, name)))
    return calls


def _record_doublings(ck):
    """``[(old slots, occupied among them)]`` of the table doublings of
    the runs to come, read off each old table before it is rehashed."""
    seen = []
    rehash_jit = ck._rehash_jit

    def recording():
        fn = rehash_jit()

        def call(vk):
            occ = int(fpset.occupied_mask(vk).sum())
            seen.append((vk[0].shape[0] - 1, occ))
            return fn(vk)

        return call

    ck._rehash_jit = recording
    return seen


def _doublings(cur, target):
    """The old sizes the growers' loops copy on the way to ``target``."""
    while cur < target:
        yield cur
        cur += min(cur, target - cur)


def _copy_bytes(ck, tiers0, tiers1):
    (_t0, l0, p0), (_t1, l1, p1) = tiers0, tiers1
    return 4 * (
        ck.W * sum(_doublings(l0, l1)) + 2 * sum(_doublings(p0, p1))
    )


@pytest.mark.parametrize("fuse", ["level", "stage"])
def test_a_run_from_small_tables_counts_the_tiers_it_crossed(
        fuse, reference_levels):
    ck = _mk(fuse)
    tiers0 = (ck.TCAP, ck.LCAP, ck.PCAP)
    assert tiers0 == (1 << 11, 1 << 10, 1 << 10)
    staircase = ck._fused_tier_triples()
    calls = _record_growth_calls(ck)
    doubled = _record_doublings(ck)
    t0 = time.perf_counter()
    r = ck.run()
    wall = time.perf_counter() - t0
    st = ck.last_stats

    # what the reference finds
    assert r.distinct_states == sum(reference_levels) == 45198
    assert r.diameter == len(reference_levels) == 20
    assert [int(x) for x in r.level_sizes] == reference_levels
    assert st["fpset_failures"] == 0 and not r.truncated

    # the tiers the run ended on, and the way there
    tiers1 = (ck.TCAP, ck.LCAP, ck.PCAP)
    assert st["grow_tiers_final"] == list(tiers1)
    assert tiers1 == (1 << 17, 1 << 16, 1 << 16)
    assert st["grow_rehashes"] == 6
    assert st["grow_rehash_slots"] == tiers1[0] - tiers0[0]
    # the keys the doublings moved: each old table's occupancy, read
    # in the sync that reads the failure count (the fetch pin below);
    # the lanes presented follow them, not the slots walked
    assert [s for s, _ in doubled] == [tiers0[0] << i for i in range(6)]
    assert st["grow_rehash_keys"] == sum(k for _, k in doubled) > 0
    assert st["grow_rehash_keys"] <= st["grow_rehash_lane_rounds"]
    assert st["grow_rehash_lane_rounds"] < st["grow_rehash_slots"] * 3
    assert st["grow_copy_bytes"] == _copy_bytes(ck, tiers0, tiers1)
    # every outermost growth call, replayed through the arithmetic:
    # the initial level's two, then the fused path's one a dispatch
    # (``level``) or the stage loop's own sites (``stage``)
    tiers, events = tiers0, 0
    for name, need in calls:
        t, l, p = tiers
        if name == "_grow_fused":
            need_t, need_l, need_p = (
                need + ck.ACAP, need + ck.APAD, need + ck.APAD)
        else:
            need_t = need if name == "_grow_visited" else 0
            need_l = need if name == "_grow_store" else 0
            need_p = need if name != "_grow_visited" else 0
        nxt = (
            ck._next_table(t, need_t, ck._capv()),
            ck._next_cap(l, need_l, ck._capl()),
            ck._next_cap(p, need_p, ck._capp()),
        )
        events += nxt != tiers
        tiers = nxt
        if name == "_grow_fused":
            assert (tiers[0], tiers[0] // 2, *tiers[1:]) in staircase
    assert tiers == tiers1
    assert st["grow_events"] == events == {"level": 7, "stage": 11}[fuse]
    assert ("_grow_fused" in dict(calls)) == (fuse == "level")

    # the longest stay in ``grow``, and the phases' sum
    assert 0.0 < st["grow_wall_max_s"] <= st["host_grow_s"]
    assert 2 <= st["grow_wall_max_at"] <= r.diameter
    total = sum(st[f"host_{p}_s"] for p in spans.PHASES)
    assert abs(total - wall) <= 0.05 * wall
    assert abs(total + st["host_unaccounted_s"] - r.wall_s) <= 0.01 * wall

    # counting costs the device nothing
    assert st["stats_fetches"] == PARENT[fuse]["fetches"]
    assert st["dispatches_per_level"] == PARENT[fuse]["dpl"]


def test_grow_wall_max_is_one_stay_and_names_its_level():
    clock = spans.PhaseClock()
    clock.level_boundary(1)
    with clock.phase("grow"):
        with clock.phase("grow"):  # _grow_store > _grow_logs
            time.sleep(0.02)
        time.sleep(0.02)
    with clock.phase("grow"):
        time.sleep(0.01)
    clock.level_boundary(2)
    clock.level_boundary(3)
    with clock.phase("grow"):
        time.sleep(0.005)
    st = clock.stats()
    assert st["grow_wall_max_at"] == 2
    assert 0.035 <= st["grow_wall_max_s"] < 0.2
    assert st["host_grow_s"] >= st["grow_wall_max_s"] + 0.012
    late = spans.PhaseClock()  # a stay no boundary record follows
    late.level_boundary(4)
    with late.phase("grow"):
        time.sleep(0.005)
    assert late.stats()["grow_wall_max_at"] == 5


@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.int32])
def test_ptt_grow_appends_zeros_under_its_scope(dtype):
    buf = jnp.arange(1, 6, dtype=dtype)
    out = bodies.ptt_grow(buf, pad=3)
    assert out.dtype == dtype
    assert out.tolist() == [1, 2, 3, 4, 5, 0, 0, 0]
    txt = bodies.ptt_grow.lower(buf, pad=3).as_text(debug_info=True)
    assert "ptt.grow" in txt


def test_a_second_cli_check_crosses_the_same_tiers_on_the_same_programs(
        tmp_path):
    first = _cli_check(tmp_path, 0, "-config", CFG_253K)
    second = _cli_check(tmp_path, 1, "-config", CFG_253K)
    assert first[0] == second[0] == 0
    assert VERDICT.search(second[1]).groups() == ("253361", "23")
    for k in GROW_KEYS:
        assert k in second[3], k  # on the -telemetry result event
    assert second[3]["grow_tiers_final"] == [1 << 21, 1 << 20, 1 << 20]
    assert second[3]["grow_rehashes"] == 4  # 2^17 -> 2^21 slots
    for k in GROW_KEYS[:5] + GROW_KEYS[7:]:
        assert second[3][k] == first[3][k], k
    assert 0 < second[3]["grow_rehash_keys"] <= 253361
    assert second[3]["jit_body_traces"] == 0
    # no unit compiles again; the one small jit a checker still builds
    # for itself does under the suite's cache threshold (conftest.py),
    # and is a cache load where the threshold is 0, as on the chip
    assert second[3]["jit_backend_compiles"] <= second[3]["jit_traces"] <= 1
    assert second[3]["stats_fetches"] == first[3]["stats_fetches"]
    with open(tmp_path / "tel_1.jsonl", encoding="utf-8") as f:
        (res,) = [e for e in map(json.loads, f) if e["event"] == "result"]
    assert res["stats"]["grow_events"] == second[3]["grow_events"] > 0
