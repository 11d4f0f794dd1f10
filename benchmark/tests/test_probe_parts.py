"""The reader of part scopes (``benchmark/lib/probe_parts.py``) on a
hand-built plane: which stage and part a path gives, self time of nested
operations, clipping to the window, the split by result width, the table
held to ``xplane_fast.scope_table``'s own stage seconds, and None, said
aloud, where a trace has stages and no part."""

import os

import pytest

from benchmark.lib import probe_parts, program_spans, xplane_fast

LEVEL = "jit(ptt_level2)/ptt.levelctl/while/body/ptt.probe/"


@pytest.mark.parametrize("path, want", [
    # one scope: a stage and no part
    (LEVEL + "while/body/add", ("probe", probe_parts.NO_PART)),
    (LEVEL + "reduce_sum", ("probe", probe_parts.NO_PART)),
    # two: the part below the stage, whatever lies between and after
    (LEVEL + "while/body/part.gather/gather", ("probe", "gather")),
    (LEVEL + "part.narrow/while/body/select_n", ("probe", "narrow")),
    (LEVEL + "while/body/part.claims_bid/scatter-min",
     ("probe", "claims_bid")),
    # the rehash traces the same body: its stage, the body's part
    ("jit(ptt_rehash2)/ptt.rehash/while/body/while/body/part.claims_fill/"
     "broadcast_in_dim", ("rehash", "claims_fill")),
    # the innermost of each level wins
    (LEVEL + "part.narrow/while/body/part.write/scatter",
     ("probe", "write")),
    # a part ABOVE the innermost stage is not that stage's
    ("jit(f)/ptt.rehash/part.narrow/ptt.probe/add",
     ("probe", probe_parts.NO_PART)),
    # no stage: no part either, whatever the path says
    ("jit(step)/while/body/part.gather/gather",
     (program_spans.UNSCOPED, probe_parts.NO_PART)),
    ("", (program_spans.UNSCOPED, probe_parts.NO_PART)),
    # a name that only looks like a part
    (LEVEL + "part.Gather/gather", ("probe", probe_parts.NO_PART)),
    (LEVEL + "depart.gather/gather", ("probe", probe_parts.NO_PART)),
])
def test_stage_and_part_of_a_path(path, want):
    assert probe_parts.stage_and_part(path, "%x = u32[8] fusion()") == want
    # the stage is the existing readers' own, with parts in the path
    assert program_spans.scope_of(path, "") == want[0]


def test_the_path_is_read_from_the_name_where_the_stat_is_missing():
    assert probe_parts.stage_and_part(
        "", "ptt.probe/part.write/scatter") == ("probe", "write")


@pytest.mark.parametrize("name, width, table_sized", [
    ("%fusion.658 = s32[16777217]{0:T(1024)} fusion(%a, %b)", 16777217,
     True),
    ("%broadcast.4958 = s32[33554433]{0} broadcast(%c)", 33554433, True),
    ("%fusion.1 = (u32[131073]{0}, u32[4096]{0:T(1024)}) fusion(%a)",
     131073, True),
    ("%fusion.2 = u32[3,65536]{1,0:T(4,128)} fusion(%a)", 65536, False),
    ("%fusion.3 = u32[1025]{0} fusion(%a)", 1025, True),  # 2^10 + 1
    ("%fusion.3 = u32[513]{0} fusion(%a)", 513, False),  # under the floor
    ("%fusion.3 = u32[65538]{0} fusion(%a)", 65538, False),
    ("%gather.4 = u32[26738688]{0} gather(%t, %s)", 26738688, False),
    ("%add.5 = s32[] add(%a, %b)", 0, False),
    ("ptt:dispatch", 0, False),
])
def test_width_of_an_operation(name, width, table_sized):
    assert probe_parts.width_of(name) == width
    assert probe_parts.is_table_sized(width) is table_sized


OPS = (  # metadata id, HLO line, op_name path
    (1, b"%while.1 = (u32[131073]{0}, u32[4096]{0}) while(%t)",
     LEVEL.encode() + b"while"),
    (2, b"%gather.2 = u32[4096]{0} gather(%t, %s)",
     LEVEL.encode() + b"while/body/part.gather/gather"),
    (3, b"%broadcast.3 = s32[131073]{0} broadcast(%c)",
     LEVEL.encode() + b"while/body/part.claims_fill/broadcast_in_dim"),
    (4, b"%fusion.4 = s32[131073]{0} fusion(%b, %s)",
     LEVEL.encode() + b"while/body/part.claims_bid/scatter-min"),
    (5, b"%fusion.5 = u32[1024]{0} fusion(%k)",
     LEVEL.encode() + b"part.narrow/while/body/select_n"),
    (6, b"%fusion.6 = s32[131073]{0} fusion(%b, %s)",
     b"jit(ptt_rehash2)/ptt.rehash/while/body/while/body/part.claims_bid/"
     b"scatter-min"),
    (7, b"%copy.7 = u32[64]{0} copy(%r)", b"jit(ptt_level2)/ptt.append/copy"),
    (8, b"%fusion.8 = s32[4096]{0} fusion(%p)",
     LEVEL.encode() + b"while/body/reduce_sum"),
)
# metadata id, offset and duration in ps on a line that starts at 1,000
# ns, in a window of [1,000, 11,000) ns: the loop's shell holds four
# operations of its round; one operation starts before the window, one
# ends after it, one lies outside it
EVENTS = (
    (2, -500_000, 1_000_000),    # gather: 0.5 us inside the window
    (1, 1_000_000, 6_000_000),   # while: 6.0 less its children's 4.5
    (2, 1_000_000, 1_000_000),   # gather 1.0
    (3, 2_500_000, 1_500_000),   # claims_fill 1.5
    (4, 4_000_000, 1_500_000),   # claims_bid 1.5
    (8, 6_000_000, 500_000),     # no part 0.5
    (5, 7_500_000, 500_000),     # narrow 0.5
    (6, 8_000_000, 1_000_000),   # rehash / claims_bid 1.0
    (7, 9_500_000, 2_000_000),   # append: 0.5 us inside the window
    (2, 12_000_000, 1_000_000),  # gather, after the window
)


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


def made_up_trace(path, parts=True):
    plane = xplane_fast.plane_class()
    pl = plane(name=b"/device:TPU:0")
    pl.stat_metadata.add(key=1).value.name = b"tf_op"
    for mid, name, tf_op in OPS:
        e = pl.event_metadata.add(key=mid)
        e.value.name = name
        if not parts:
            tf_op = b"/".join(
                x for x in tf_op.split(b"/") if not x.startswith(b"part."))
        e.value.stats.add(metadata_id=1, str_value=tf_op)
    ops = pl.lines.add(name=b"XLA Ops", timestamp_ns=1000)
    for mid, off, dur in EVENTS:
        ops.events.add(metadata_id=mid, offset_ps=off, duration_ps=dur)
    host = plane(name=b"/host:CPU")
    host.event_metadata.add(key=1).value.name = b"bench:trace-window"
    host.lines.add(name=b"main", timestamp_ns=1000).events.add(
        metadata_id=1, offset_ps=0, duration_ps=10_000_000)
    write_planes(path, [pl, host])


def test_parts_of_a_made_up_trace(tmp_path, capsys):
    path = str(tmp_path / "plugins" / "profile" / "x" / "t.xplane.pb")
    made_up_trace(path)
    ctx = {}
    tab = probe_parts.load(ctx, path)
    us = 1e-6
    assert tab["planes"] == 1 and tab["staged"] and tab["parted"]
    assert tab["part_s"]["probe"] == pytest.approx({
        "gather": 1.5 * us, "claims_fill": 1.5 * us, "claims_bid": 1.5 * us,
        "narrow": 0.5 * us, probe_parts.NO_PART: 2.0 * us})
    assert tab["part_s"]["rehash"] == pytest.approx({"claims_bid": 1.0 * us})
    assert tab["part_s"]["append"] == pytest.approx(
        {probe_parts.NO_PART: 0.5 * us})
    # the stages are the existing reader's, to the last digit
    assert tab["stage_s"] == pytest.approx(
        xplane_fast.scope_table(path)["scope_s"], rel=1e-12)
    # by width: the table's 2^17 + 1 slots, the flush's 4,096 lanes (the
    # widest of the stage that is not a table), and the ladder's 1,024
    w = tab["width_s"]["probe"]
    assert w["claims_fill"] == pytest.approx(
        {"table-sized": 1.5 * us, "flush-wide": 0.0, "narrower": 0.0})
    assert w["gather"] == pytest.approx(
        {"table-sized": 0.0, "flush-wide": 1.5 * us, "narrower": 0.0})
    assert w["narrow"] == pytest.approx(
        {"table-sized": 0.0, "flush-wide": 0.0, "narrower": 0.5 * us})
    assert w[probe_parts.NO_PART] == pytest.approx(
        {"table-sized": 1.5 * us, "flush-wide": 0.5 * us, "narrower": 0.0})
    assert [(n.split(" ")[0], p.rsplit("/", 1)[-1])
            for n, p, _s in tab["unparted"]["probe"]] == [
        ("%while.1", "while"), ("%fusion.8", "reduce_sum")]
    # the metrics' readers: cached, and the parts and the rest of a
    # stage sum to the stage
    assert ctx[probe_parts.CACHE_KEY] is tab
    parts = ("gather", "claims_fill", "claims_bid", "write", "reread",
             "narrow")
    got = [probe_parts.part_seconds(ctx, "probe", p) for p in parts]
    assert got[3] == got[4] == 0.0  # parts exist, these two ran nothing
    loose = probe_parts.unparted_pct(ctx, "probe")
    assert loose == pytest.approx(100.0 * 2.0 / 7.0)
    assert sum(got) + loose / 100.0 * tab["stage_s"]["probe"] == (
        pytest.approx(tab["stage_s"]["probe"]))
    assert probe_parts.unparted_pct(ctx, "compact") is None
    said = capsys.readouterr().out
    assert "parts of ptt.probe" in said and "parts of ptt.rehash" in said
    assert "ptt.probe under no part, longest: %while.1" in said
    assert "[body/ptt.probe/while]" in said
    assert said.count("parts of ptt.probe") == 1  # printed once a run
    probe_parts.load(ctx)
    assert "parts of" not in capsys.readouterr().out


def test_stages_and_no_part_read_none_and_say_so(tmp_path, capsys):
    """The parent's programs, or executables a cache kept from before
    the parts: every metric is left out, none reads 0."""
    path = str(tmp_path / "plugins" / "profile" / "x" / "t.xplane.pb")
    made_up_trace(path, parts=False)
    ctx = {}
    tab = probe_parts.load(ctx, path)
    assert tab["staged"] and not tab["parted"]
    assert tab["stage_s"] == pytest.approx(
        xplane_fast.scope_table(path)["scope_s"], rel=1e-12)
    assert probe_parts.part_seconds(ctx, "probe", "gather") is None
    assert probe_parts.unparted_pct(ctx, "probe") is None
    said = capsys.readouterr().out
    assert "ptt. stages and no part. scope" in said
    assert "parts of" not in said


def test_no_trace_reads_none(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(program_spans, "trace_dir",
                        lambda: str(tmp_path / "none"))
    ctx = {}
    assert probe_parts.part_seconds(ctx, "probe", "gather") is None
    assert probe_parts.unparted_pct(ctx, "probe") is None
    assert ctx == {probe_parts.CACHE_KEY: None}
    assert "no table" in capsys.readouterr().out


@pytest.mark.parametrize("metric, part", [
    (f"probe_part_s.{p}{suffix}", p)
    for suffix in ("", ".cli9m")
    for p in ("gather", "claims_fill", "claims_bid", "write", "reread",
              "narrow")
] + [("probe_unparted_pct", None), ("probe_unparted_pct.cli9m", None)])
def test_each_metric_reads_its_part(metric, part, tmp_path):
    """Every new entry of the manifest has its reader, found by name, and
    reads its own part of the made-up trace."""
    from benchmark import run

    path = str(tmp_path / "plugins" / "profile" / "x" / "t.xplane.pb")
    made_up_trace(path)
    ctx = {}
    tab = probe_parts.load(ctx, path)
    got = run.read_layer_metric(metric, ctx)
    if part is None:
        assert got == pytest.approx(100.0 * 2.0 / 7.0)
    else:
        assert got == tab["part_s"]["probe"].get(part, 0.0)
    man = run.read_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in man["per_layer"] if m["name"] == metric]
    cell = "cli-complete-9m" if metric.endswith(".cli9m") else (
        "scaled-window")
    assert entry["workloads"] == [cell] and entry["layer"] == "kernels"
    assert entry["moves"] == ("verdict_s" if cell == "cli-complete-9m"
                              else "states_per_s")


@pytest.mark.parametrize("metric, key, source, cells", [
    ("fpset_slots_per_valid", "fpset_slots_per_valid", "program_counter",
     ["scaled-window"]),
    ("fpset_slots_per_valid.cli9m", "fpset_slots_per_valid",
     "program_counter", ["cli-complete-9m"]),
    ("host_account_s.cli", "host_account_s", "program_span",
     ["cli-complete", "cli-leak-trace"]),
])
def test_the_counters_are_read_from_the_programs_stats(metric, key, source,
                                                       cells):
    from benchmark import run

    one = {"out": {"stats": {key: 12.5}}}
    assert run.read_layer_metric(metric, one) == 12.5
    many = {"out": {"stats": {"checks": [{key: 3.0}, {key: 1.0},
                                         {key: 2.0}]}}}
    assert run.read_layer_metric(metric, many) == 2.0
    assert run.read_layer_metric(metric, {"out": {"stats": {}}}) is None
    man = run.read_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in man["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == cells
    assert entry["source"] == source
