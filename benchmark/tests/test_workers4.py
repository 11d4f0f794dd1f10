"""The four-worker cell's own pieces, on the CPU: the tiny fixture cell
(``fixtures/BENCHMARK.workers4.test.json``: the real cell's driver,
comparison, control and per-layer readers on the shipped binding, through
``cli check -workers 4`` on four virtual devices) comes out correct as it
stands and not correct with one level size altered, its control comes out
not correct, and the route metrics' arithmetic is held to made-up
numbers."""

import os

# four virtual CPU devices for ``-workers 4``: read when JAX first
# starts its backend, which no module does while it is imported
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

from benchmark import control, run  # noqa: E402
from benchmark.lib import (plug, program_spans, route_bytes,  # noqa: E402
                           xplane_fast)

FIX = os.path.join(run.ROOT, "benchmark", "tests", "fixtures",
                   "BENCHMARK.workers4.test.json")
NEW_ON_CPU = (
    "compiles_in_window.workers4", "dispatches_per_level.workers4",
    "host_dispatch_s.workers4", "host_grow_s.workers4",
    "host_fetch_wait_s.workers4", "host_unaccounted_s.workers4",
    "jit_host_s.workers4", "shard_imbalance_pct", "route_bytes_per_state",
)


def cell(trace):
    return run.run_cell(FIX, "cli-workers4", 2147483659, 8.0, trace,
                        require_tpu=False)


def test_sound_four_worker_cell_is_correct_and_reads_its_counters():
    r = cell(trace=1)
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] >= 1
    for name in NEW_ON_CPU:
        assert isinstance(r["metrics"][name]["value"], (int, float)), name
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0.0 <= m["shard_imbalance_pct"] < 25.0
    assert abs(m["host_unaccounted_s.workers4"]) < 0.05
    # K = 2 key planes out and one flag plane back, 4 bytes a lane, at
    # the capacity of the CLI's tiers: 4 x ceil(4096 x 7 x 1.5 / 4)
    assert m["route_bytes_per_state"] > 3 * 4 * 43008 / 45198
    # the CPU's stand-in device plane carries no ptt. scope and no ICI
    assert "stage_device_s.route" not in m and "route_ici_pct" not in m


@pytest.mark.parametrize("level", [5, 15])
def test_one_level_size_altered_is_not_correct(monkeypatch, level):
    """Level 5 is one the reference searches in the run, level 15 one
    whose size the configuration stores (``pyeval-prefix-plus-pinned``,
    the fixture's prefix is 12 levels)."""
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )

    sound = ShardedDeviceChecker._log

    def log(self, msg):
        sound(self, msg.replace(f"level {level}: +", f"level {level}: +1"))

    monkeypatch.setattr(ShardedDeviceChecker, "_log", log)
    r = cell(trace=0)
    assert r["correct"] is False, r
    assert set(r["metrics"]) == {"verdict_s", "setup_s"}


def test_stored_levels_have_to_follow_the_prefix_without_a_gap():
    _man, _cell, config, traffic = run.load_cell(FIX, "cli-workers4")
    traffic["cfg_path"] = os.path.join(run.ROOT, traffic["cfg"])
    mod = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    prefix, stored = mod.wanted_sizes(config, traffic)
    assert len(prefix) == 12 and len(stored) == 8
    assert sum(prefix) + sum(stored) == 45198
    del config["reference"]["pinned_level_sizes"]["13"]
    with pytest.raises(ValueError, match="without a gap"):
        mod.wanted_sizes(config, traffic)


def test_control_narrow_fingerprint_is_not_correct():
    rs = control.run_control(FIX, "cli-workers4", [1, 2147483659], 40.0,
                             False)
    assert [r["correct"] for r in rs] == [False, False], rs


# ---- the route metrics' arithmetic, on a made-up context ---------------

CHECK = {"route_rounds_by_capacity": {"98304": 1000, "196608": 10}}
TEXT = "1000000 distinct states found, search depth (diameter) 24.\n"
SENT = 3 * 4 * (98304 * 1000 + 196608 * 10)


def made_up_ctx(route_s):
    return {
        "out": {"answers": [{"stats": CHECK, "text": TEXT},
                            {"stats": {}, "text": TEXT}], "stats": {}},
        "config": {"shapes": {"key_columns": 2}, "layout": {"chips": 4}},
        "peaks": {"hbm_bytes_per_s": 1},
        program_spans.CACHE_KEY: {
            "device_planes": 4, "scoped": True,
            "scope_s": {"route": route_s, "probe": 9.0}},
    }


def test_route_bytes_are_planes_times_capacity_times_rounds():
    assert route_bytes.sent_bytes(CHECK, 2) == SENT
    assert route_bytes.sent_bytes({}, 2) is None  # a parent's result
    assert route_bytes.crossing(SENT, 4) == SENT * 3 / 4
    assert route_bytes.per_check(made_up_ctx(2.0)) == [(SENT, 1000000)]
    read = plug.load_file("layer_metrics", "route_bytes_per_state").read
    assert read(made_up_ctx(2.0), {}) == SENT / 1000000
    assert read({**made_up_ctx(2.0), "out": {"answers": []}}, {}) is None


def test_route_ici_pct_arithmetic():
    mod = plug.load_file("layer_metrics", "route_ici_pct")
    assert mod.ici_peak("TPU v5 lite") == 200e9  # 1,600 Gbps a chip
    with pytest.raises(KeyError, match="ici_peaks"):
        mod.ici_peak("cpu")
    # three quarters of the bytes cross, over 2 s, against 200 GB/s
    want = 100.0 * (SENT * 3 / 4) / 2.0 / 200e9
    assert mod.share_pct(SENT, 4, 2.0, 200e9) == pytest.approx(want)
    assert 0.0 < want < 100.0
    # nothing to read: no exchange counted, no route scope, or no chip
    assert mod.read({**made_up_ctx(2.0), "out": {"answers": []}}, {}) is None
    assert mod.read(made_up_ctx(0.0), {}) is None
    assert mod.read({**made_up_ctx(2.0), "peaks": {}}, {}) is None


# ---- the fast scope table against program_spans' own reduction ---------


def write_planes(path, planes):
    """An ``XSpace`` of ``planes``: field 1, length-delimited."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        for pl in planes:
            raw = pl.SerializeToString()
            size, varint = len(raw), b""
            while size >= 0x80:
                varint += bytes([size & 0x7F | 0x80])
                size >>= 7
            f.write(b"\x0a" + varint + bytes([size]) + raw)


def made_up_xplane(path):
    """A small ``.xplane.pb``: two device planes (a scope as a string and
    as a reference to a stat's name, an operation with no scope and one
    with no metadata, nested operations, one that outlasts the window, a
    line that is not the operations'), and a host plane with ``ptt:``
    spans and the window."""
    plane = xplane_fast.plane_class()
    planes = []
    for d in range(2):
        pl = plane(name=f"/device:TPU:{d}".encode())
        for k, n in ((1, b"tf_op"), (2, b"jit(f)/ptt.route/all_to_all"),
                     (3, b"hlo_category")):
            pl.stat_metadata.add(key=k).value.name = n
        for k, name, stat in (
                (1, b"%fusion.1 = u32[8] fusion()",
                 (1, b"jit(f)/ptt.probe/ptt.expand/add", 0)),
                (2, b"%all-to-all.2 = u32[4] all-to-all()", (1, b"", 2)),
                (3, b"%copy.3 = u32[8] copy()", (3, b"ptt.seed", 0)),
                (4, b"%while.4 = (u32[8]) while()", None)):
            e = pl.event_metadata.add(key=k)
            e.value.name = name
            if stat:
                e.value.stats.add(metadata_id=stat[0], str_value=stat[1],
                                  ref_value=stat[2])
        mods = pl.lines.add(name=b"XLA Modules", timestamp_ns=1000)
        mods.events.add(metadata_id=1, offset_ps=0, duration_ps=9_000_000)
        ops = pl.lines.add(name=b"XLA Ops", timestamp_ns=1000 + d)
        for mid, off, dur in ((4, 0, 5_000_000), (1, 1_000_000, 1_500_000),
                              (2, 3_000_000, 1_000_000 + d),
                              (3, 6_000_000, 2_000_000),
                              (9, 8_500_000, 250_000),
                              (2, 19_999_000_000, 9_000_000)):
            ops.events.add(metadata_id=mid, offset_ps=off, duration_ps=dur)
        planes.append(pl)
    host = plane(name=b"/host:CPU")
    for k, n in enumerate((b"bench:trace-window", b"ptt:check",
                           b"ptt:dispatch", b"PjitFunction(f)"), 1):
        host.event_metadata.add(key=k).value.name = n
    ln = host.lines.add(name=b"main", timestamp_ns=500)
    for mid, off, dur in ((1, 0, 20_000_000_000), (2, 2_000, 18_000_000_000),
                          (3, 3_000, 4_000_000_000),
                          (4, 4_000, 2_000_000_000)):
        ln.events.add(metadata_id=mid, offset_ps=off, duration_ps=dur)
    planes.append(host)
    write_planes(path, planes)


def test_fast_scope_table_is_program_spans_reduction(tmp_path, monkeypatch):
    path = str(tmp_path / "plugins" / "profile" / "x" / "t.xplane.pb")
    made_up_xplane(path)
    want = program_spans.reduce(program_spans.walk_xplane(path))
    got = xplane_fast.scope_table(path)
    assert set(want["scope_s"]) == {"(no scope)", "expand", "route"}
    assert got["scope_s"] == pytest.approx(want["scope_s"], rel=1e-12)
    assert got["device_self_s"] == pytest.approx(want["device_self_s"])
    assert (got["device_planes"], got["scoped"]) == (2, True)
    # prime() leaves the table where every reader of program_spans finds
    # it, and reduces only once
    monkeypatch.setattr(program_spans, "trace_dir", lambda: str(tmp_path))
    ctx = {}
    xplane_fast.prime(ctx)
    assert ctx[program_spans.CACHE_KEY] == got
    assert program_spans.stage_seconds(ctx, "route") == pytest.approx(
        want["scope_s"]["route"])
    assert program_spans.unscoped_pct(ctx) == pytest.approx(
        100.0 * want["scope_s"]["(no scope)"] / want["device_self_s"])
    monkeypatch.setattr(xplane_fast, "scope_table", None)
    xplane_fast.prime(ctx)
    # no trace: nothing is cached, and program_spans sees to the rest
    monkeypatch.setattr(program_spans, "trace_dir",
                        lambda: str(tmp_path / "none"))
    ctx = {}
    xplane_fast.prime(ctx)
    assert ctx == {} and program_spans.load(ctx) is None


def one_op_plane(name, tf_op):
    """A device plane of one operation; ``tf_op`` None: no metadata."""
    pl = xplane_fast.plane_class()(name=name)
    if tf_op is not None:
        pl.stat_metadata.add(key=1).value.name = b"tf_op"
        e = pl.event_metadata.add(key=1)
        e.value.name = b"%fusion.1 = u32[8] fusion()"
        e.value.stats.add(metadata_id=1, str_value=tf_op)
    pl.lines.add(name=b"XLA Ops", timestamp_ns=5).events.add(
        metadata_id=1, offset_ps=0, duration_ps=1_000_000)
    return pl


def test_a_trace_without_scopes_gives_an_empty_table(tmp_path):
    """The parent's program on this engine: no ``ptt.`` scope in any
    metadata, so no event is summed and the readers report nothing."""
    path = str(tmp_path / "t.xplane.pb")
    write_planes(path, [one_op_plane(b"/device:TPU:0", b"jit(body)/add")])
    got = xplane_fast.scope_table(path)
    want = program_spans.reduce(program_spans.walk_xplane(path))
    assert (got["device_planes"], got["scoped"]) == (1, False)
    assert (want["device_planes"], want["scoped"]) == (1, False)
    ctx = {program_spans.CACHE_KEY: got}
    assert program_spans.stage_seconds(ctx, "route") is None
    assert program_spans.unscoped_pct(ctx) is None


def test_a_plane_without_metadata_beside_a_scoped_one(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    write_planes(path, [one_op_plane(b"/device:TPU:0", b"ptt.probe/add"),
                        one_op_plane(b"/device:TPU:1", None)])
    want = program_spans.reduce(program_spans.walk_xplane(path))
    assert want["scope_s"] == {"probe": 5e-7, "(no scope)": 5e-7}
    assert xplane_fast.scope_table(path)["scope_s"] == want["scope_s"]


def test_self_seconds_are_trace_reduce_self_times():
    """Nested, overhanging, tied and empty events, drawn at random."""
    import random

    import numpy as np

    from benchmark.lib import trace_reduce

    rng = random.Random(29)
    for _ in range(300):
        evs = [[str(rng.randint(0, 3)), float(rng.randint(0, 50)),
                rng.choice([0.0, 1.0, 2.0, 5.0, 20.0, 60.0])]
               for _ in range(rng.randint(0, 40))]
        want = trace_reduce._self_times(evs)
        got = xplane_fast.self_seconds(
            np.array([int(e[0]) for e in evs], dtype=np.int64),
            np.array([e[1] for e in evs], dtype=float),
            np.array([e[2] for e in evs], dtype=float), 4)
        assert [want.get(str(k), 0.0) for k in range(4)] == list(got)
