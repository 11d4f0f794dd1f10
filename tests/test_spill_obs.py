"""The tiered store names, counts and prints what a spilled run does
(ISSUE 41): the CLI's tiered line against the reference and the
engine's own counters; the spill programs' scopes as compiled; the
bucketed fetch; the new counters of ``last_stats``; the traced and the
timed run as one path; an untiered run untouched.
"""

import contextlib
import io
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference as bench_reference
from benchmark.lib import spill_bytes, tlafmt
from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.engine import device_bfs
from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, tight_hbm_budget

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "specs", "compaction.tla")
CFG = os.path.join(ROOT, "specs", "compaction.cfg")
LEVEL = re.compile(r"^\s*level (\d+): \+(\d+) \(total (\d+),", re.M)

NEW_KEYS = (
    "spill_lookup_s", "spill_blocked_s", "spill_fetch_s", "spill_d2h_bytes",
    "spill_d2h_padded_bytes", "spill_hot_keys_max",
    "spill_hot_share_max_pct", "spill_cold_runs", "spill_budget_overridden",
    "spill_tier_ceilings", "spill_evict_slots", "spill_joins",
    "spill_fetches", "spill_fetch_planes",
    "spill_merge_s", "spill_merges", "spill_index_keys",
)
COUNTS = (
    "spill_evictions", "spill_keys_evicted", "spill_rows_evicted",
    "spill_misses_resolved", "spill_miss_hits", "spill_syncs",
    "spill_hot_keys", "spill_hot_keys_max", "spill_cold_runs",
    "spill_d2h_bytes", "spill_d2h_padded_bytes", "spill_evict_slots",
    "spill_bytes_raw", "spill_bytes_comp", "spill_joins",
    "spill_budget_overridden", "spill_tier_ceilings", "stage_sieve_n",
    "stage_unflag_n", "stage_evict_n", "stage_flush_n", "fpset_flushes",
    "fpset_probe_rounds", "spill_merges", "spill_index_keys",
)


def _mk(c=None, **kw):
    kw.setdefault("invariants", ())
    kw.setdefault("check_deadlock", False)
    kw.setdefault("sub_batch", 64)
    kw.setdefault("visited_cap", 1 << 9)
    kw.setdefault("frontier_cap", 1 << 9)
    return DeviceChecker(
        CompactionModel(c or SMALL_CONFIGS["producer_on"]), **kw
    )


def _tight(**kw):
    return tight_hbm_budget(lambda b: _mk(hbm_budget=b, **kw))


@pytest.fixture(scope="module")
def tiered():
    """One tiered run of the 1,654-state binding at a tight budget."""
    ck = _mk(hbm_budget=_tight())
    return ck, ck.run()


# ---- the CLI's line -----------------------------------------------------


def _cli(*extra):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.main(["check", SPEC, "-config", CFG, *extra])
    rows = [tuple(int(x) for x in m.groups())
            for m in LEVEL.finditer(err.getvalue())]
    sizes = [rows[0][2] - rows[0][1]] + [r[1] for r in rows]
    return rc, out.getvalue(), sizes


def test_cli_prints_what_it_spilled_and_the_reference_agrees(tmp_path):
    """``-hbm-budget 4M`` on the shipped binding: the reference's
    count, diameter and level sizes, and a tiered line that the
    comparison's parser reads back number for number, each the
    engine's own counter."""
    tel = str(tmp_path / "t.jsonl")
    rc, text, sizes = _cli("-hbm-budget", "4M", "-telemetry", tel)
    want, _seen = bench_reference.bfs_levels(tlafmt.constants_from_cfg(CFG))
    assert rc == 0 and sizes == want
    assert tlafmt.parse_counts(text) == (45198, 20) == (sum(want), len(want))
    assert "device-memory budget 4M" in text  # the banner names it
    line = spill_bytes.parse_tiered_line(text)
    st = [json.loads(x) for x in open(tel) if '"result"' in x][-1]["stats"]
    assert line == {
        "budget": st["hbm_budget"],
        "table": st["spill_tier_ceilings"][0],
        "rows": st["spill_tier_ceilings"][1],
        "logs": st["spill_tier_ceilings"][2],
        "hot_peak": st["spill_hot_keys_max"],
        "hot_pct": round(100.0 * st["spill_hot_keys_max"] / 45198, 1),
        "states": 45198,
        "evictions": st["spill_evictions"],
        "keys_evicted": st["spill_keys_evicted"],
        "lookups": st["spill_misses_resolved"],
        "hits": st["spill_miss_hits"],
        "rows_spilled": st["spill_rows_evicted"],
        "overridden": False,
    }
    assert line["budget"] == 4 << 20
    assert line["hot_peak"] <= line["table"] // 2
    assert min(line["keys_evicted"], line["lookups"], line["hits"],
               line["rows_spilled"]) > 0
    assert st["spill_degraded"] is False


def test_cli_without_a_budget_prints_no_tiered_line():
    rc, text, _sizes = _cli()
    assert rc == 0 and "Tiered store" not in text
    assert "budget" not in text


def test_cli_says_when_the_budget_was_overridden(monkeypatch):
    """Tiers too small for the binding's widest levels: the run ends
    exact, says ``budget overridden: yes``, and the benchmark's
    comparison reads that as not correct."""
    from benchmark.lib import plug

    tiers = dict(sub_batch=64, visited_cap=1 << 9, frontier_cap=1 << 9)
    monkeypatch.setattr(cli, "_explorer_tiers", lambda args: dict(tiers))
    budget = tight_hbm_budget(lambda b: _mk(
        pe.SHIPPED_CFG, hbm_budget=b, **tiers))
    rc, text, sizes = _cli("-hbm-budget", str(budget))
    line = spill_bytes.parse_tiered_line(text)
    assert rc == 0 and sum(sizes) == 45198
    assert line["overridden"] is True and "overridden: yes." in text
    config = {
        "budget": {"bytes": line["budget"], "table_slots": line["table"],
                   "rows": line["rows"], "logs": line["logs"],
                   "hot_keys_max": line["table"] // 2},
        "reference": {"prefix_levels": 20, "pinned_level_sizes": {}},
    }
    answers = [{"rc": rc, "text": text, "level_sizes": sizes}]
    wrong = [c["name"] for c in plug.load_file(
        "comparisons", "pyeval-prefix-plus-pinned-tiered").compare(
            config, {"cfg_path": CFG}, answers, 1) if not c["ok"]]
    assert "budget_overridden" in wrong


# ---- the device scopes --------------------------------------------------


def _struct(args):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        args,
    )


@pytest.fixture(scope="module")
def spill_programs():
    """``{program name: compiled HLO}`` of every per-checker program a
    tiered run calls, compiled again at the shapes it was called with
    (the pattern of tests/test_spans.py)."""
    real, seen = jax.jit, {}

    def recording_jit(fn, **kw):
        j = real(fn, **kw)

        def call(*args):
            shapes = _struct(args)
            seen.setdefault(fn.__name__, lambda: j.lower(*shapes))
            return j(*args)

        return call

    mp = pytest.MonkeyPatch()
    mp.setattr(device_bfs.jax, "jit", recording_jit)
    try:
        _mk(hbm_budget=_tight()).run()
    finally:
        mp.undo()
    return {
        name: lower().compile().as_text() for name, lower in seen.items()
        if name.startswith("ptt_spill")
    }


@pytest.mark.parametrize("name, scope", [
    ("ptt_spill_tag", "ptt.spill_tag"),
    ("ptt_spill_evict", "ptt.spill_evict"),
    ("ptt_spill_rehash", "ptt.rehash"),
    ("ptt_spill_sieve", "ptt.spill_sieve"),
    ("ptt_spill_unflag", "ptt.spill_unflag"),
    ("ptt_spill_shift", "ptt.spill_shift"),
    ("ptt_spill_logshift", "ptt.spill_shift"),
])
def test_every_spill_program_carries_its_scope_as_compiled(
    spill_programs, name, scope
):
    hlo = spill_programs[name]
    names = re.findall(r'op_name="([^"]*)"', hlo)
    under = [n for n in names if f"jit({name})/{scope}/" in n]
    assert under and len(under) >= len(names) // 2, (len(under), len(names))
    assert set(re.findall(r"ptt\.[a-z_]+", hlo)) == {scope}


def test_the_same_size_rehash_keeps_the_probes_parts(spill_programs):
    hlo = spill_programs["ptt_spill_rehash"]
    assert re.search(r"ptt\.rehash/.*part\.(gather|write)", hlo)


@pytest.mark.parametrize("name, args", [
    ("ptt_spill_fetch", lambda u, i: u),
    ("ptt_spill_fetch_cols", lambda u, i: (u, u, i)),
    ("ptt_spill_fetch_cols", lambda u, i: (i, i)),
], ids=["one", "keys_and_lanes", "logs"])
def test_the_fetch_program_carries_its_scope(name, args):
    u = jax.ShapeDtypeStruct((1 << 14,), jnp.uint32)
    i = jax.ShapeDtypeStruct((1 << 14,), jnp.int32)
    hlo = getattr(device_bfs, name).lower(
        args(u, i), jnp.int32(0), size=1 << 12
    ).compile().as_text()
    assert f"jit({name})/ptt.spill_fetch/" in hlo
    assert set(re.findall(r"ptt\.[a-z_]+", hlo)) == {"ptt.spill_fetch"}


def test_an_untiered_shift_is_the_parents_program(monkeypatch):
    """Frontier-window mode slides its rows under ``ptt.levelctl`` as
    the program named ``ptt_shift``, as before the tiered store had a
    scope of its own."""
    named = []
    real = jax.jit

    def recording_jit(fn, **kw):
        named.append(fn.__name__)
        return real(fn, **kw)

    monkeypatch.setattr(device_bfs.jax, "jit", recording_jit)
    ck = _mk(rows_window="frontier")
    fn = ck._shift_jit()
    assert named == ["ptt_shift"]
    txt = fn.lower(
        jax.ShapeDtypeStruct((ck._rows_len(),), jnp.uint32),
        jnp.int32(0), jnp.int32(0),
    ).as_text(debug_info=True)
    assert set(re.findall(r"ptt\.[a-z_]+", txt)) == {"ptt.levelctl"}


# ---- the bucketed fetch -------------------------------------------------


def test_fetch_sizes_are_powers_of_two_up_to_the_buffer():
    size = DeviceChecker._spill_fetch_size
    lo = device_bfs.SPILL_FETCH_MIN
    assert [size(n, 100000) for n in (0, 1, lo, lo + 1, 70000, 100000)] == [
        lo, lo, lo, 2 * lo, 100000, 100000]
    assert size(5, 1000) == 1000  # a buffer under the least bucket
    assert len({size(n, 1 << 20) for n in range(1, 1 << 20, 997)}) == 9


def test_fetch_returns_the_unpadded_slice_through_few_shapes(monkeypatch):
    """Fetches of many lengths and offsets, the last ones clamped to
    the buffer's end: each returns exactly the slice, and the device
    program meets at most its bucket count of shapes."""
    ck = _mk(hbm_budget=_tight())
    ck._mk_tstore()
    length = 3 * device_bfs.SPILL_FETCH_MIN + 17
    host = np.arange(length, dtype=np.uint32) * 7 + 1
    buf = jnp.asarray(host)
    shapes = set()
    real = device_bfs.ptt_spill_fetch

    def counting(b, start, *, size):
        shapes.add((b.shape, str(b.dtype), size))
        return real(b, start, size=size)

    monkeypatch.setattr(device_bfs, "ptt_spill_fetch", counting)
    rng = np.random.default_rng(41)
    needed = 0
    for _ in range(200):
        n = int(rng.integers(0, length + 1))
        off = int(rng.integers(0, length - n + 1))
        got = ck._spill_fetch(buf, n, off)
        assert got.shape == (n,) and (got == host[off: off + n]).all()
        needed += got.nbytes
    assert len(shapes) <= 2  # 2^12 and 2^13; over that the whole buffer
    assert ck._spill_d2h_bytes == needed
    assert ck._spill_d2h_padded_bytes >= needed
    assert ck._spill_fetch_s > 0
    ck.tstore.close()


def _same_slices(got, want):
    """``got`` is ``want`` array for array: values, dtype, shape, and
    C-contiguous memory of its own."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert isinstance(g, np.ndarray) and g.dtype == w.dtype
        assert g.shape == w.shape and (g == w).all()
        assert g.flags["C_CONTIGUOUS"]
    return True


FETCH_LENGTH = 3 * device_bfs.SPILL_FETCH_MIN + 17
# (n, off): nothing, one, a bucket's edge and its two neighbours, the
# whole buffer, and offsets that push ``start`` back to length - size
FETCH_CASES = {
    "none": (0, 5),
    "one": (1, 0),
    "edge_less_1": (device_bfs.SPILL_FETCH_MIN - 1, 3),
    "edge": (device_bfs.SPILL_FETCH_MIN, 3),
    "edge_plus_1": (device_bfs.SPILL_FETCH_MIN + 1, 3),
    "whole": (FETCH_LENGTH, 0),
    "clamped": (100, FETCH_LENGTH - 100),
    "clamped_2_buckets": (
        device_bfs.SPILL_FETCH_MIN + 9,
        FETCH_LENGTH - device_bfs.SPILL_FETCH_MIN - 9,
    ),
}


@pytest.fixture(scope="module")
def fetcher():
    ck = _mk(hbm_budget=_tight())
    ck._mk_tstore()
    yield ck
    ck.tstore.close()


@pytest.mark.parametrize("case", sorted(FETCH_CASES))
@pytest.mark.parametrize("dtypes", [
    "u", "i", "ui", "uui", "ii", "iuiu",
])
def test_columns_come_back_as_their_slices_in_one_fetch(
    fetcher, dtypes, case
):
    """1 to 4 columns of one length, ``uint32`` and ``int32`` mixed:
    exactly ``[np.asarray(c)[off: off + n] for c in cols]``, dtypes
    kept, in ONE round trip accounted as the planes one by one."""
    n, off = FETCH_CASES[case]
    rng = np.random.default_rng(47)
    host = [
        rng.integers(0, 1 << 32, FETCH_LENGTH, np.uint64).astype(
            np.uint32
        ).view(np.uint32 if d == "u" else np.int32)
        for d in dtypes
    ]
    cols = [jnp.asarray(h) for h in host]
    ck = fetcher
    before = (
        ck._spill_fetches, ck._spill_fetch_planes, ck._spill_d2h_bytes,
        ck._spill_d2h_padded_bytes, ck._spill_fetch_s,
    )
    got = ck._spill_fetch_cols(cols, n, off)
    assert _same_slices(got, [h[off: off + n] for h in host])
    assert [g.dtype for g in got] == [c.dtype for c in cols]
    size = DeviceChecker._spill_fetch_size(n, FETCH_LENGTH)
    # what is kept holds no padding: a copy wherever the bucket is wider
    assert all(g.base is None for g in got) or n == size
    assert ck._spill_fetches == before[0] + 1
    assert ck._spill_fetch_planes == before[1] + len(cols)
    assert ck._spill_d2h_bytes == before[2] + 4 * n * len(cols)
    assert ck._spill_d2h_padded_bytes == before[3] + 4 * size * len(cols)
    assert ck._spill_fetch_s > before[4]
    # plane by plane, the parent's way, accounts the same bytes
    one = [ck._spill_fetch(c, n, off) for c in cols]
    assert _same_slices(one, got)
    assert ck._spill_d2h_bytes == before[2] + 8 * n * len(cols)
    assert ck._spill_d2h_padded_bytes == before[3] + 8 * size * len(cols)
    assert ck._spill_fetches == before[0] + 1 + len(cols)


def _recorded_fetches(monkeypatch):
    """Patch both fetch methods and both programs to record what a run
    asks of them: ``(lengths asked for, shapes the programs met)``."""
    lengths, shapes = set(), set()
    real = {
        k: getattr(DeviceChecker, k)
        for k in ("_spill_fetch", "_spill_fetch_cols")
    }

    def fetch(self, buf, n, off=0):
        lengths.add(n)
        return real["_spill_fetch"](self, buf, n, off)

    def fetch_cols(self, cols, n, off=0):
        lengths.add(n)
        return real["_spill_fetch_cols"](self, cols, n, off)

    def recording(name):
        prog = getattr(device_bfs, name)

        def call(b, start, *, size):
            shapes.add((
                name,
                tuple((c.shape, str(c.dtype)) for c in jax.tree.leaves(b)),
                size,
            ))
            return prog(b, start, size=size)

        return call

    monkeypatch.setattr(DeviceChecker, "_spill_fetch", fetch)
    monkeypatch.setattr(DeviceChecker, "_spill_fetch_cols", fetch_cols)
    for name in ("ptt_spill_fetch", "ptt_spill_fetch_cols"):
        monkeypatch.setattr(device_bfs, name, recording(name))
    return lengths, shapes


def test_a_runs_fetches_meet_few_shapes_though_its_flushes_differ(
    monkeypatch
):
    lengths, shapes = _recorded_fetches(monkeypatch)
    ck = _mk(hbm_budget=_tight())
    r = ck.run()
    assert r.distinct_states == 1654
    assert len(lengths) > 20  # a length a flush, nearly
    # a program a (columns, bucket): the keys with their lanes, the
    # evicted keys, the two logs, the rows; none a length
    buckets = {
        (name, cols, DeviceChecker._spill_fetch_size(n, cols[0][0][0]))
        for name, cols, _size in shapes for n in range(1, cols[0][0][0])
    }
    assert len(shapes) <= len(buckets) <= 10
    assert {name for name, _cols, _size in shapes} >= {
        "ptt_spill_fetch_cols"}
    assert {len(cols) for name, cols, _size in shapes
            if name == "ptt_spill_fetch_cols"} == {ck.K, ck.K + 1, 2}


# ---- the counters -------------------------------------------------------


def test_new_counters_are_in_last_stats(tiered, monkeypatch):
    ck, r = tiered
    st = ck.last_stats
    for k in NEW_KEYS:
        assert k in st, k
    assert r.distinct_states == 1654
    assert st["spill_hot_keys_max"] >= st["spill_hot_keys"] > 0
    assert st["spill_hot_share_max_pct"] == round(
        100.0 * st["spill_hot_keys_max"] / 1654, 4)
    assert st["spill_cold_runs"] == st["spill_evictions"] >= 1
    assert st["spill_merges"] == st["spill_evictions"]
    assert 0 < st["spill_index_keys"] <= st["spill_keys_evicted"]
    assert st["spill_evict_slots"] >= st["spill_evictions"] * (1 << 10)
    assert st["spill_tier_ceilings"] == ck._tier_ceilings
    assert st["spill_budget_overridden"] is ck._budget_overridden
    assert st["spill_d2h_padded_bytes"] >= st["spill_d2h_bytes"] > 0
    assert 0 < st["spill_fetch_s"] <= st["spill_transfer_s"] + 1e-3
    assert st["spill_lookup_s"] > 0 and st["spill_blocked_s"] >= 0
    assert st["spill_joins"] == 1  # an in-RAM store: at the result
    # a flush's keys and lanes share a round trip: over 2 planes a fetch
    assert st["spill_fetch_planes"] > 2 * st["spill_fetches"] > 0
    assert st["fuse"] == "level" and st["fpset_slot_rounds"] > 0
    # host_<phase>_s still sum to the wall of run()
    phases = sum(st[f"host_{p}_s"] for p in spans.PHASES)
    assert st["host_spill_s"] > 0
    assert abs(phases + st["host_unaccounted_s"] - r.wall_s) < 0.05


def test_d2h_bytes_are_the_fetched_planes_summed(monkeypatch):
    kept, trips = [], []
    real = DeviceChecker._spill_fetch
    real_cols = DeviceChecker._spill_fetch_cols

    def fetch(self, buf, n, off=0):
        out = real(self, buf, n, off)
        kept.append(out.nbytes)
        trips.append(1)
        return out

    def fetch_cols(self, cols, n, off=0):
        outs = real_cols(self, cols, n, off)
        kept.extend(out.nbytes for out in outs)
        trips.append(len(outs))
        return outs

    monkeypatch.setattr(DeviceChecker, "_spill_fetch", fetch)
    monkeypatch.setattr(DeviceChecker, "_spill_fetch_cols", fetch_cols)
    ck = _mk(hbm_budget=_tight())
    ck.run()
    st = ck.last_stats
    assert st["spill_d2h_bytes"] == sum(kept) > 0
    assert st["spill_fetches"] == len(trips)
    assert st["spill_fetch_planes"] == sum(trips) == len(kept)


@pytest.fixture(scope="module")
def plane_by_plane():
    """The tiered run of ``tiered`` with every column fetched by a
    round trip of its own through ``_spill_fetch``: the parent's
    path."""
    mp = pytest.MonkeyPatch()
    mp.setattr(
        DeviceChecker, "_spill_fetch_cols",
        lambda self, cols, n, off=0: [
            self._spill_fetch(c, n, off) for c in cols
        ],
    )
    try:
        ck = _mk(hbm_budget=_tight())
        return ck, ck.run()
    finally:
        mp.undo()


def test_packed_fetches_change_no_level(tiered, plane_by_plane):
    (_ck, r), (_ck1, r1) = tiered, plane_by_plane
    assert r.level_sizes == r1.level_sizes
    assert r.distinct_states == r1.distinct_states == 1654
    assert r.diameter == r1.diameter


@pytest.mark.parametrize("key", COUNTS)
def test_packed_fetches_change_no_count(tiered, plane_by_plane, key):
    """Every count of the run, ``spill_d2h_bytes`` and
    ``spill_d2h_padded_bytes`` to the byte, is what fetching the planes
    one by one gives."""
    (ck, _r), (ck1, _r1) = tiered, plane_by_plane
    assert ck.last_stats[key] == ck1.last_stats[key], key


def test_packing_makes_fewer_round_trips_for_the_same_planes(
    tiered, plane_by_plane
):
    st, st1 = tiered[0].last_stats, plane_by_plane[0].last_stats
    assert st1["spill_fetches"] == st1["spill_fetch_planes"]  # 1.0 a fetch
    assert st["spill_fetch_planes"] == st1["spill_fetch_planes"]
    assert st["spill_evictions"] >= 1
    assert st["spill_fetch_planes"] > 2 * st["spill_fetches"]
    assert st["spill_fetches"] < 0.5 * st1["spill_fetches"]


def test_traced_and_timed_runs_are_one_path(tiered, tmp_path):
    """Telemetry on and off: the worker is joined the same number of
    times and every count agrees."""
    ck_off, r_off = tiered
    ck_on = _mk(hbm_budget=_tight(), telemetry=str(tmp_path / "t.jsonl"))
    r_on = ck_on.run()
    assert r_on.level_sizes == r_off.level_sizes
    for k in COUNTS:
        assert ck_on.last_stats[k] == ck_off.last_stats[k], k
    evs = [json.loads(x) for x in open(tmp_path / "t.jsonl")]
    spills = [e for e in evs if e["event"] == "spill"]
    assert spills and spills[-1]["bytes_raw"] == ck_on.last_stats[
        "spill_bytes_raw"]


def test_a_durable_store_joins_at_its_boundaries(tmp_path):
    ck = _mk(hbm_budget=_tight(), checkpoint_path=str(tmp_path / "ck.npz"))
    ck.run()
    assert ck.last_stats["spill_joins"] > 1


def test_an_untiered_run_has_no_new_key():
    ck = _mk()
    ck.run()
    st = ck.last_stats
    assert not [k for k in st if k.startswith("spill_")]
    assert st["host_spill_s"] == 0.0 and "hbm_budget" not in st


# ---- what a spilled run's dispatches are made of (ISSUE 54) -------------

# the stage counters of the spill programs, and the programs each counts
SPILL_STAGES = {
    "sieve": ("ptt_spill_sieve",), "unflag": ("ptt_spill_unflag",),
    "evict": ("ptt_spill_evict", "ptt_spill_rehash"),
}
# read off the parent commit (6085604), same constructor arguments: the
# parent and lane logs of every state in gid order (the discovery order),
# cold runs first, and the tiered counterexample
PARENT_LOGS_SHA256 = (
    "8ff482a8b833d1dcc8cdd6987496a5e1e6acfdb6620589a2f2f3842f836feedb"
)
PARENT_LEVELS = [1, 5, 24, 56, 76, 108, 124, 128, 156, 156, 160, 192,
                 212, 56, 88, 112]
PARENT_BUG = dict(
    n=3741, gid=3645, levels=[729, 1458, 1458, 96],
    actions=["CompactorPhaseOne", "CompactorPhaseTwoWrite",
             "CompactorPhaseTwoUpdateContext"],
)


def test_every_spill_program_dispatched_is_on_the_clock(tiered):
    from tests.test_spans import assert_split_adds_up, calls_of

    ck, _r = tiered
    st = ck.last_stats
    assert_split_adds_up(st)
    # a spill program is launched under ``spill`` (from a flush, from
    # the level boundary) or under ``grow``: in the other phases'
    # tables, not lost
    spill = st["programs_by_phase"]["spill"]
    for stage, programs in SPILL_STAGES.items():
        assert st[f"stage_{stage}_n"] > 0, stage
        assert sum(calls_of(st, p) for p in programs) == (
            st[f"stage_{stage}_n"]), stage
        assert set(programs) <= set(spill), stage
    for p in ("ptt_spill_tag", "ptt_spill_shift", "ptt_spill_logshift",
              "ptt_spill_fetch_cols"):
        assert spill[p][0] > 0, p
    # a shift and a log shift a spill of aged rows, on the same scalars
    assert spill["ptt_spill_shift"][0] == spill["ptt_spill_logshift"][0]
    assert spill["ptt_spill_shift"][2] == 2 * spill["ptt_spill_shift"][0]
    assert spill["ptt_spill_logshift"][2] == 0
    # a fetch that brings a buffer whole calls no program
    fetched = sum(calls_of(st, p)
                  for p in ("ptt_spill_fetch", "ptt_spill_fetch_cols"))
    assert 0 < fetched <= st["spill_fetches"]
    assert st["calls_by_phase"]["spill"][0] == sum(
        r[0] for r in spill.values())
    # the corrected count of a flush is an upload for its append, made
    # under ``spill``: uploads with no call there
    assert spill["ptt_append"][0] == 0 < spill["ptt_append"][2]
    assert spill["ptt_append"][2] == st["stage_sieve_n"]
    # the stage programs under ``dispatch`` are the stage counters too
    by = st["dispatch_by_program"]
    for stage, p in (("flush", "ptt_fpflush2"), ("compact", "ptt_compact"),
                     ("append", "ptt_append"), ("expand", "ptt_expand")):
        assert by[p][0] == st[f"stage_{stage}_n"], stage


def test_hoisting_the_scalars_changed_no_state_of_a_tiered_run(tiered):
    import hashlib

    ck, r = tiered
    assert [int(x) for x in r.level_sizes] == PARENT_LEVELS
    base = ck._last_rb["row_base"]
    assert base == 1542
    cold_par, cold_lan = ck.tstore.fetch_logs(0, base)
    n = r.distinct_states - base
    par = np.concatenate([cold_par, np.asarray(ck.last_bufs["parent"][:n])])
    lan = np.concatenate([cold_lan, np.asarray(ck.last_bufs["lane"][:n])])
    digest = hashlib.sha256(
        par.astype(np.int32).tobytes() + lan.astype(np.int32).tobytes()
    ).hexdigest()
    assert digest == PARENT_LOGS_SHA256


def test_the_tiered_counterexample_is_the_parents():
    from tests.helpers import assert_valid_counterexample

    inv = "DuplicateNullKeyMessage"
    kw = dict(invariants=(inv,), check_deadlock=True)
    ck = _mk(pe.SHIPPED_CFG, hbm_budget=tight_hbm_budget(
        lambda b: _mk(pe.SHIPPED_CFG, hbm_budget=b, **kw)), **kw)
    r = ck.run()
    assert r.violation == inv and r.violation_gid == PARENT_BUG["gid"]
    assert r.distinct_states == PARENT_BUG["n"]
    assert [int(x) for x in r.level_sizes] == PARENT_BUG["levels"]
    assert [str(a) for a in r.trace_actions] == PARENT_BUG["actions"]
    assert ck.last_stats["spill_evictions"] == 1
    assert_valid_counterexample(pe.SHIPPED_CFG, r.trace, r.trace_actions, inv)
