"""A check through the spec->kernel compiler names, counts and prints
what it ran (ISSUE 49): through ``cli.main`` the compiled path, the
hand-written model and the benchmark's own reference
(``benchmark/ref/pyeval.py``, which knows nothing of the compiler) agree
on every level of ``specs/compaction.cfg``; the compiled line against
the constructor's own counters; the two host spans; seeded samples of
the 142-bit binding's states (the cell's first rung: 5 words, 19 lanes)
against the reference, successor set by successor set and row by row;
the compiled counterexample through the benchmark's own replay.
"""

import contextlib
import io
import json
import os
import random
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import plug, tlafmt
from benchmark.lib import reference as bench_reference
from benchmark.ref import pyeval as ref
from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.frontend import interp as I
from pulsar_tlaplus_tpu.frontend.codegen import ERR_VAR, CompiledSpec
from pulsar_tlaplus_tpu.frontend.codegen_ir import encode_value
from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
from pulsar_tlaplus_tpu.frontend.parser import parse_file
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.utils import cfg as cfgmod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "specs", "compaction.tla")
CFG = os.path.join(ROOT, "specs", "compaction.cfg")
CFG_4M = os.path.join(ROOT, "specs", "compaction_4m.cfg")

COMPILED = plug.load_file("comparisons", "pyeval-prefix-plus-pinned-compiled")
level_sizes_from_progress = plug.load_file(
    "drivers", "repeat-cli").level_sizes_from_progress


def _check(*flags, tel=None):
    """One ``cli.main`` of the shipped binding: ``(rc, stdout, level
    sizes from the progress lines, the result event's stats)``."""
    argv = ["check", SPEC, "-config", CFG, *flags]
    if tel is not None:
        if os.path.exists(tel):
            os.remove(tel)
        argv += ["-telemetry", tel]
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.main(argv)
    stats = {}
    if tel is not None:
        with open(tel, encoding="utf-8") as f:
            events = [json.loads(x) for x in f if x.strip()]
        stats = [e for e in events if e["event"] == "result"][-1]["stats"]
    return rc, out.getvalue(), level_sizes_from_progress(err.getvalue()), stats


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    return _check("-compile",
                  tel=str(tmp_path_factory.mktemp("tel") / "c.jsonl"))


@pytest.fixture(scope="module")
def hand(tmp_path_factory):
    return _check(tel=str(tmp_path_factory.mktemp("tel") / "h.jsonl"))


# ---- the three agree, and the line says what ran -------------------------

def test_compiled_hand_and_reference_agree_on_every_level(compiled, hand):
    want, seen = bench_reference.bfs_levels(tlafmt.constants_from_cfg(CFG))
    assert sum(want) == len(seen) == 45198 and len(want) == 20
    for rc, text, sizes, _st in (compiled, hand):
        assert rc == 0 and sizes == want
        assert tlafmt.parse_counts(text) == (45198, 20)


def test_the_compiled_line_is_printed_once_at_the_bindings_widths(compiled):
    _rc, text, _sizes, st = compiled
    assert text.count("Compiled from the .tla:") == 1
    ln = COMPILED.parse_compiled_line(text)
    assert COMPILED.widths_of(ln) == ["compaction", 111, 4, 19, False]
    assert ln["initial"] == 729
    # the line is the function's, from the engine's own counters
    assert cli.compiled_line("compaction", st) in text.splitlines()
    assert text.index("distinct states found") < text.index("Compiled from")
    assert "via the spec->kernel compiler" in text
    assert not COMPILED.HAND_BANNER.search(text)


def test_the_result_event_carries_the_seven_counters(compiled):
    _rc, text, _sizes, st = compiled
    ln = COMPILED.parse_compiled_line(text)
    assert all(k in st for k in COMPILED.COUNTERS)
    assert st["key_exact"] is False and st["fpset_failures"] == 0
    assert [st["codegen_state_bits"], st["codegen_state_words"],
            st["codegen_lanes"], st["codegen_initial_states"]] == [
        111, 4, 19, 729]
    assert st["codegen_s"] > 0 and st["codegen_parse_s"] > 0
    assert ln["codegen_s"] == pytest.approx(st["codegen_s"], abs=0.006)
    assert ln["parse_s"] == pytest.approx(st["codegen_parse_s"], abs=0.006)


def test_a_hand_model_check_prints_no_line_and_carries_no_counter(hand):
    _rc, text, _sizes, st = hand
    assert COMPILED.parse_compiled_line(text) is None
    assert COMPILED.HAND_BANNER.search(text)
    assert not [k for k in st if k.startswith("codegen_")]
    assert "key_exact" not in st


def test_parse_and_codegen_run_under_their_spans_inside_the_check(
        monkeypatch):
    """``ptt:cli.parse`` and ``ptt:cli.codegen`` open inside
    ``ptt:check``, before the engine's ``ptt:run``."""
    opened, real = [], spans.span

    @contextlib.contextmanager
    def recording(name, **fields):
        opened.append(name)
        with real(name, **fields):
            yield

    monkeypatch.setattr(spans, "span", recording)
    rc, _text, _sizes, _st = _check("-compile")
    assert rc == 0
    assert opened[0] == "check"
    codegen = opened.index("cli.codegen")
    assert "cli.parse" in opened[1:codegen]
    assert opened.index("cli.engine_init") > codegen
    assert "cli.build" not in opened  # the registry's factory never ran


def test_a_declined_spec_says_so_and_prints_no_line(monkeypatch, capsys):
    from pulsar_tlaplus_tpu.frontend import codegen
    from pulsar_tlaplus_tpu.frontend.codegen_ir import CodegenError

    def declines(self, spec, invariants=()):
        raise CodegenError("a construct outside the subset")

    monkeypatch.setattr(codegen.CompiledSpec, "__init__", declines)
    # the interpreter is a host search: a prefix of it is enough here
    rc = cli.main(["check", SPEC, "-config", CFG, "-compile",
                   "-maxstates", "3000"])
    text = capsys.readouterr().out
    assert rc == 3 and "via the generic interpreter" in text
    assert COMPILED.DECLINED_TEXT in text
    assert bench_reference.FALLBACK_TEXT in text
    assert COMPILED.parse_compiled_line(text) is None


def test_a_fresh_process_walks_each_kernel_once(tmp_path):
    """The first check of a fresh process (ISSUE 50) reads
    ``codegen_walks`` 4 (``successors``, ``TypeSafe``,
    ``CompactionHorizonCorrectness``, ``__EvalError__``: all in the
    constructor: no program hands a kernel a kind of state the
    constructor did not meet), the engine's seven calls of a kernel are
    replays, and ``jit_traces`` is under a ceiling
    20% above what the change reads at this binding: **6,323** (7,590
    the ceiling), where the tree before it, whose every program ran the
    code generator again, read **15,689**."""
    tel = tmp_path / "fresh.jsonl"
    done = subprocess.run(
        [sys.executable, "-m", "pulsar_tlaplus_tpu.cli", "check", SPEC,
         "-config", CFG, "-compile", "-telemetry", str(tel)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert tlafmt.parse_counts(done.stdout) == (45198, 20)
    with open(tel, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    st = [e for e in events if e["event"] == "result"][-1]["stats"]
    assert [st["codegen_walks"], st["codegen_replays"]] == [4, 7]
    assert st["jit_body_traces"] > 0  # a fresh process: the units ran
    assert 0 < st["jit_traces"] < 7590


# ---- seeded samples at the cell's first rung: 142 bits, 5 words, 19 lanes

def _interp_values(s):
    """A reference state as the interpreter's canon values, variable by
    variable (what ``encode_value`` takes)."""
    nil = I.MV("Nil")

    def msgs(seq):
        return tuple(I.FDict({"id": i, "key": k, "value": v})
                     for i, k, v in seq)

    return {
        "messages": msgs(s.messages),
        "compactedLedgers": tuple(
            nil if led is None else msgs(led) for led in s.ledgers),
        "cursor": nil if s.cursor is None else I.FDict({
            "compactionHorizon": s.cursor[0],
            "compactedTopicContext": s.cursor[1]}),
        "compactorState": I.MV(ref.PHASE_NAMES[s.cstate]),
        "phaseOneResult": nil if s.p1 is None else I.FDict({
            "readPosition": s.p1[0],
            "latestForKey": I.make_fn(dict(s.p1[1]))}),
        "compactionHorizon": s.horizon,
        "compactedTopicContext": s.context,
        "crashTimes": s.crash,
        "consumeTimes": s.consume,
    }


def _ref_state(dec):
    """``CompiledSpec.decode_state``'s mapping as a reference state."""
    def msgs(seq):
        return tuple((m["id"], m["key"], m["value"]) for m in seq)

    def fn(f):  # a function over 1..n is a tuple in the canon
        return dict(enumerate(f, 1)) if isinstance(f, tuple) else dict(f.items)

    cur, p1 = dec["cursor"], dec["phaseOneResult"]
    return ref.State(
        messages=msgs(dec["messages"]),
        ledgers=tuple(None if isinstance(led, I.MV) else msgs(led)
                      for led in dec["compactedLedgers"]),
        cursor=None if isinstance(cur, I.MV) else (
            cur["compactionHorizon"], cur["compactedTopicContext"]),
        cstate=ref.PHASE_NAMES.index(dec["compactorState"].name),
        p1=None if isinstance(p1, I.MV) else (
            p1["readPosition"],
            tuple(sorted(fn(p1["latestForKey"]).items()))),
        horizon=dec["compactionHorizon"],
        context=dec["compactedTopicContext"],
        crash=dec["crashTimes"],
        consume=dec["consumeTimes"],
    )


@pytest.fixture(scope="module")
def rung_4m():
    """The compiled model of the 142-bit binding, its jitted kernels
    over a batch of 256, and the reference's levels 1-8 (188,489
    states), each built once."""
    ast, tlc_cfg = parse_file(SPEC), cfgmod.load(CFG_4M)
    consts = bind_cfg(ast, tlc_cfg)
    consts.pop("__string_interning__", None)
    cs = CompiledSpec(I.Spec(ast, consts),
                      invariants=tuple(tlc_cfg.invariants))
    c = tlafmt.constants_from_cfg(CFG_4M)
    levels = [list(ref.initial_states(c))]
    seen = set(levels[0])
    while len(levels) < 8:
        new = []
        for s in levels[-1]:
            for _a, t in ref.successors(c, s):
                if t not in seen:
                    seen.add(t)
                    new.append(t)
        levels.append(new)
    step = jax.jit(jax.vmap(cs.successors))
    pack = jax.jit(jax.vmap(cs.layout.pack))
    unpack = jax.jit(jax.vmap(cs.layout.unpack))
    return cs, c, levels, step, pack, unpack


def test_the_rung_has_the_cells_widths(rung_4m):
    cs, _c, levels, *_ = rung_4m
    assert cs.codegen_stats == {
        "codegen_s": cs.codegen_stats["codegen_s"],
        "codegen_state_bits": 142, "codegen_state_words": 5,
        "codegen_lanes": 19, "codegen_initial_states": 1,
        "codegen_walks": 4,
        "codegen_replays": cs.codegen_stats["codegen_replays"]}
    assert [len(x) for x in levels] == [
        1, 10, 99, 990, 9828, 37665, 59130, 80766]


@pytest.mark.parametrize("seed", [0, 49, 2147483659])
def test_seeded_states_have_the_references_successors_and_rows(rung_4m, seed):
    """256 states drawn by the seed from the reference's levels 1-8:
    the successor SET of each by the generated kernel is the
    reference's, none with the error bit, and pack / unpack of its
    5-word row is the identity."""
    cs, c, levels, step, pack, unpack = rung_4m
    pool = [s for lv in levels for s in lv]
    sample = random.Random(seed).sample(pool, 256)
    enc = []
    for s in sample:
        vals = _interp_values(s)
        d = {v: encode_value(cs.var_descs[v], vals[v]) for v in cs.spec.vars}
        d[ERR_VAR] = np.bool_(False)
        enc.append(d)
    batch = jax.tree_util.tree_map(lambda *xs: jnp.asarray(np.stack(xs)), *enc)
    rows = pack(batch)
    assert rows.shape == (256, 5) and rows.dtype == jnp.uint32
    back = unpack(rows)
    # a row's slots past a sequence's length are the codec's to fill:
    # the identity is the row's, and the decoded state's (below)
    assert np.array_equal(np.asarray(pack(back)), np.asarray(rows))
    assert len({bytes(r) for r in np.asarray(rows)}) == 256
    back = jax.tree_util.tree_map(np.asarray, back)
    succ, valid = jax.tree_util.tree_map(np.asarray, step(batch))
    assert valid.shape == (256, 19)
    for i, s in enumerate(sample):
        assert _ref_state(cs.decode_state(
            jax.tree_util.tree_map(lambda x: x[i], back))) == s
        got = set()
        for k in np.flatnonzero(valid[i]):
            one = jax.tree_util.tree_map(lambda x: x[i][k], succ)
            assert not one[ERR_VAR]
            got.add(_ref_state(cs.decode_state(one)))
        assert got == {t for _a, t in ref.successors(c, s)}, s


# ---- the compiled counterexample, through the benchmark's own replay ----

def _as_the_hand_model_prints(text):
    """The compiled path renders values as TLC does (a function over
    1..n as a tuple, a record's fields in name order, the empty
    function as ``<<>>``); ``benchmark/lib/tlafmt.py`` reads the hand
    model's rendering.  Same values, rewritten line by line."""
    def ledgers(m):
        body, parts, depth, cur = m.group(1), [], 0, ""
        for tok in re.split(r"(<<|>>|, )", body):
            depth += (tok == "<<") - (tok == ">>")
            if tok == ", " and depth == 0:
                parts.append(cur)
                cur = ""
            else:
                cur += tok
        parts.append(cur)
        return "/\\ compactedLedgers = (" + ", ".join(
            f"{i} :> {p}" for i, p in enumerate(parts, 1)) + ")"

    def latest(m):
        f = m.group(1)
        if f.startswith("<<"):
            vals = [x for x in f[2:-2].split(", ") if x]
            f = "(" + ", ".join(f"{i} :> {x}"
                                for i, x in enumerate(vals, 1)) + ")"
        return (f"/\\ phaseOneResult = [readPosition |-> {m.group(2)}, "
                f"latestForKey |-> {f.replace(' @@ ', ', ')}]")

    text = re.sub(r"^/\\ compactedLedgers = <<(.*)>>$", ledgers, text,
                  flags=re.M)
    text = re.sub(
        r"^/\\ cursor = \[compactedTopicContext \|-> (\d+), "
        r"compactionHorizon \|-> (\d+)\]$",
        r"/\\ cursor = [compactionHorizon |-> \2, "
        r"compactedTopicContext |-> \1]", text, flags=re.M)
    return re.sub(
        r"^/\\ phaseOneResult = \[latestForKey \|-> (.*), "
        r"readPosition \|-> (\d+)\]$", latest, text, flags=re.M)


def test_the_compiled_leak_trace_passes_the_benchmarks_replay():
    rc, text, _sizes, _st = _check(
        "-compile", "-invariant", "CompactedLedgerLeak")
    assert rc == 1 and COMPILED.parse_compiled_line(text) is not None
    _man, _cell, config, traffic = bench_run.load_cell(
        os.path.join(ROOT, "BENCHMARK.json"), "cli-leak-trace")
    traffic["cfg_path"] = CFG
    checks = plug.load_file("comparisons", "trace-replay").compare(
        config, traffic,
        [{"rc": rc, "text": _as_the_hand_model_prints(text)}], 49)
    assert [c["name"] for c in checks if not c["ok"]] == []
    assert "trace_wrong_length_not_12" in [c["name"] for c in checks]
    # the rewriting is what made it readable: as printed, it is not
    raw = plug.load_file("comparisons", "trace-replay").compare(
        config, traffic, [{"rc": rc, "text": text}], 49)
    assert [c["name"] for c in raw if not c["ok"]]
