"""The readers of the program's own names (``benchmark/lib/program_spans.py``
and the per-layer metrics of ISSUE 27 that use it).

A recorded TPU sample with scoped device operations and ``ptt:`` host
spans (``benchmark/selfcheck/spans_sample.json``, origin beside it)
stands in for a chip: the grouping reader has to return the per-stage
seconds stored with it, and nothing (not 0) once the scopes are taken
off.  The host-side metrics are read from real runs of the tiny cells on
the CPU.
"""

import copy
import json
import os

import pytest

from benchmark import run
from benchmark.lib import program_spans as ps

ROOT = run.ROOT
TINY = os.path.join(
    ROOT, "benchmark", "tests", "fixtures", "BENCHMARK.spans.test.json")
SELF = os.path.join(ROOT, "benchmark", "selfcheck")

HOST_SIDE_CLI = [
    "host_dispatch_s.cli", "host_grow_s.cli", "host_fetch_wait_s.cli",
    "host_unaccounted_s.cli", "level_wall_max_s.cli", "jit_host_s.cli",
    "jit_traces.cli",
]


def sample():
    with open(os.path.join(SELF, "spans_sample.json"), encoding="utf-8") as f:
        w = json.load(f)
    w["device"] = [[tuple(e) for e in plane] for plane in w["device"]]
    w["spans"] = [tuple(s) for s in w["spans"]]
    w["host"] = [tuple(h) for h in w["host"]]
    w["window"] = tuple(w["window"]) if w["window"] else None
    return w


def expected():
    with open(os.path.join(SELF, "spans_expected.json"),
              encoding="utf-8") as f:
        return json.load(f)


def close(a, b):
    return abs(a - b) <= 1e-9 * max(1.0, abs(b))


def test_grouping_reader_returns_the_stored_stage_seconds():
    want = expected()
    ctx = {}
    assert ps.load(ctx, walked=sample()) is ctx[ps.CACHE_KEY]
    for stage, secs in want["scope_s"].items():
        if stage != ps.UNSCOPED:
            assert close(ps.stage_seconds(ctx, stage), secs), stage
    assert close(ps.unscoped_pct(ctx), want["device_unscoped_pct"])
    assert close(ps.idle_unattributed_pct(ctx),
                 want["idle_unattributed_pct"])
    got = ctx[ps.CACHE_KEY]
    assert close(got["device_self_s"], want["device_self_s"])
    for k, v in want["idle_by_span_s"].items():
        assert close(got["idle_by_span_s"][k], v), k
    # the stages and what is under no scope add up to the busy time
    assert close(sum(got["scope_s"].values()), got["device_self_s"])


def test_a_trace_with_no_scope_reads_as_nothing_not_zero(capsys):
    w = sample()
    w["device"] = [[(ps.UNSCOPED, s, d) for _n, s, d in plane]
                   for plane in w["device"]]
    ctx = {}
    ps.load(ctx, walked=w)
    assert ps.stage_seconds(ctx, "probe") is None
    assert ps.unscoped_pct(ctx) is None
    assert "no ptt. scope" in capsys.readouterr().out


def test_a_trace_with_no_span_reads_as_nothing(capsys):
    w = sample()
    w["spans"] = []
    ctx = {}
    ps.load(ctx, walked=w)
    assert ps.idle_unattributed_pct(ctx) is None
    assert "no ptt: span" in capsys.readouterr().out
    # only the containers (a CLI with spans over an engine without):
    w2 = sample()
    w2["spans"] = [s for s in w2["spans"] if s[2] in ps.CONTAINERS]
    ctx2 = {}
    ps.load(ctx2, walked=w2)
    assert ps.idle_unattributed_pct(ctx2) is None


def test_innermost_segments_and_covered_arithmetic():
    segs = ps.innermost_segments([
        (0, 100, "ptt:check"), (10, 90, "ptt:run"), (20, 30, "ptt:dispatch"),
        (30, 35, "ptt:fetch"), (50, 60, "ptt:dispatch"), (52, 55, "ptt:grow"),
        (200, 210, "ptt:check"),
    ])
    assert segs == [
        (0, 10, "ptt:check"), (10, 20, "ptt:run"), (20, 30, "ptt:dispatch"),
        (30, 35, "ptt:fetch"), (35, 50, "ptt:run"), (50, 52, "ptt:dispatch"),
        (52, 55, "ptt:grow"), (55, 60, "ptt:dispatch"), (60, 90, "ptt:run"),
        (90, 100, "ptt:check"), (200, 210, "ptt:check"),
    ]
    union = [[5, 15], [20, 22], [40, 60]]
    starts = [u[0] for u in union]
    prefix = [0, 10, 12, 32]
    for s, e, want in [(0, 5, 0), (0, 100, 32), (10, 21, 6), (41, 59, 18),
                       (15, 20, 0), (22, 40, 0), (59, 70, 1)]:
        assert ps._covered(union, starts, prefix, s, e) == want, (s, e)


def test_scope_of_takes_the_innermost_scope_of_the_op_name_path():
    path = ("jit(ptt_level)/ptt.levelctl/while/body/ptt.probe/"
            "jit(_where)/select_n:")
    assert ps.scope_of(path, "%fusion.1 = s32[8] fusion()") == "probe"
    assert ps.scope_of("jit(step)/while:", "%copy.2") == ps.UNSCOPED
    assert ps.scope_of("", "%f = s32[] fusion(), op_name=\"a/ptt.append/b\""
                       ) == "append"


def test_wire_reader_reads_a_hand_made_xspace(tmp_path):
    """A two-plane XSpace written byte by byte: the device operation's
    scope comes from the tf_op stat of its event metadata."""

    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    def entry(key, msg):
        return field(1, key) + field(2, msg)

    stat_md = field(5, entry(7, field(1, 7) + field(2, b"tf_op")))
    tf_op = b"jit(ptt_level)/ptt.levelctl/while/body/ptt.expand/add:"
    ev_md = field(4, entry(3, field(1, 3) + field(2, b"%fusion.9 = u32[4]")
                           + field(5, field(1, 7) + field(5, tf_op))))
    line = field(3, field(2, b"XLA Ops") + field(3, 1000)
                 + field(4, field(1, 3) + field(2, 5_000_000)
                         + field(3, 2_000_000)))
    device = field(1, field(2, b"/device:TPU:0") + line + ev_md + stat_md)
    host_md = (field(4, entry(1, field(1, 1) + field(2, b"ptt:dispatch")))
               + field(4, entry(2, field(1, 2)
                                + field(2, b"bench:trace-window"))))
    host_line = field(3, field(2, b"python3") + field(3, 0) + field(
        4, field(1, 2) + field(2, 0) + field(3, 9_000_000_000)) + field(
        4, field(1, 1) + field(2, 4_000_000) + field(3, 3_000_000)))
    host = field(1, field(2, b"/host:CPU") + host_line + host_md)
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(device + host)
    w = ps.walk_xplane(str(path))
    assert w["device"] == [[("expand", 6000.0, 2000.0)]]
    assert w["spans"] == [(4000.0, 7000.0, "ptt:dispatch")]
    assert w["window"] == (0.0, 9_000_000.0)


@pytest.mark.parametrize("name", ["cli-complete", "cli-leak-trace"])
def test_tiny_cli_cells_report_every_host_side_metric(name):
    r = run.run_cell(TINY, name, 2147483659, 8.0, 1, require_tpu=False)
    assert r["correct"] is True, r
    for m in HOST_SIDE_CLI:
        assert m in r["metrics"], m
    v = {m: r["metrics"][m]["value"] for m in HOST_SIDE_CLI}
    assert v["host_dispatch_s.cli"] > 0 and v["jit_traces.cli"] > 0
    assert 0 <= v["host_unaccounted_s.cli"] < 0.01
    assert v["level_wall_max_s.cli"] > 0
    # the metrics the benchmark had are still there
    assert "cli_outside_engine_s" in r["metrics"]
    assert "dispatches_per_level.cli" in r["metrics"]


def test_tiny_scaled_cell_reports_its_host_side_metric():
    r = run.run_cell(TINY, "scaled-window", 2147483659, 60.0, 1,
                     require_tpu=False)
    assert r["correct"] is True, r
    assert r["metrics"]["host_fetch_wait_s.scaled"]["value"] > 0
    assert "work_units_per_state" in r["metrics"]


def test_every_new_metric_of_the_manifest_has_a_reader():
    from benchmark.lib import plug

    man = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in man["per_layer"]:
        assert (os.path.exists(plug.path_of("layer_metrics", m["name"]))
                or os.path.exists(
                    plug.path_of("layer_metrics", m["name"], ".json"))), m
    tiny = {m["name"] for m in run.read_json(TINY)["per_layer"]}
    assert tiny == {m["name"] for m in man["per_layer"]}
    assert copy.deepcopy(man)["per_layer"][-1]["name"] == (
        "idle_unattributed_pct.cli")
