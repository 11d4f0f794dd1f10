"""Differential tests for the spec->kernel compiler (frontend/codegen.py):
the compiled path must reproduce the interpreter's (and oracle's) state
counts, diameters, verdicts, and traces — on the real reference spec
(/root/reference/compaction.tla) WITHOUT the hand-written model, and on
the original specs in specs/."""

import functools
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.frontend import interp as I
from pulsar_tlaplus_tpu.frontend.codegen import CompiledSpec
from pulsar_tlaplus_tpu.frontend.loader import compaction_constants
from pulsar_tlaplus_tpu.frontend.parser import parse_file
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, SPECS

from tests.helpers import REFERENCE_TLA  # specs/ first, /root/reference fallback


@pytest.fixture(scope="module")
def module():
    return parse_file(REFERENCE_TLA)


def _spec(module, c):
    return I.Spec(module, compaction_constants(c))


def _check(spec, invariants=(), **kw):
    cs = CompiledSpec(spec, invariants=invariants)
    kw.setdefault("sub_batch", 256)
    kw.setdefault("visited_cap", 1 << 12)
    kw.setdefault("frontier_cap", 1 << 12)
    return DeviceChecker(cs, **kw).run(), cs


@pytest.mark.parametrize(
    "name", ["producer_on", "two_crashes", "no_retain"]
)
def test_compiled_matches_oracle_small(module, name):
    c = SMALL_CONFIGS[name]
    want = pe.check(c, invariants=())
    got, _cs = _check(_spec(module, c))
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


def test_compiled_shipped_cfg_published_count(module):
    """45,198 distinct states (compaction.tla:23) on the compiled path,
    straight from the reference .tla text — no hand-written model."""
    got, cs = _check(
        _spec(module, pe.SHIPPED_CFG),
        sub_batch=1024, visited_cap=1 << 16, frontier_cap=1 << 14,
    )
    assert got.distinct_states == 45198
    assert got.diameter == 20
    assert got.violation is None and not got.deadlock


def test_compiled_leak_counterexample(module):
    got, cs = _check(
        _spec(module, pe.SHIPPED_CFG),
        invariants=("CompactedLedgerLeak",),
        sub_batch=1024, visited_cap=1 << 16, frontier_cap=1 << 14,
    )
    assert got.violation == "CompactedLedgerLeak"
    assert got.diameter == 12
    assert len(got.trace) == 12
    # rendered trace: every step labeled with a real action
    assert all(isinstance(a, str) and a for a in got.trace_actions)


def test_compiled_duplicate_null_key_counterexample(module):
    got, _cs = _check(
        _spec(module, pe.SHIPPED_CFG),
        invariants=("DuplicateNullKeyMessage",),
        sub_batch=1024, visited_cap=1 << 16, frontier_cap=1 << 14,
    )
    assert got.violation == "DuplicateNullKeyMessage"
    assert got.diameter == 4
    assert len(got.trace) == 4


def test_compiled_lane_order_matches_interpreter(module):
    """Per-state successor sets must match the interpreter exactly
    (in-set equality; lanes are a superset ordering of enabled succs)."""
    c = SMALL_CONFIGS["producer_on"]
    spec = _spec(module, c)
    I.install_defs(spec)
    cs = CompiledSpec(spec)
    import jax
    import numpy as np

    step = jax.jit(cs.successors)
    # walk a few BFS levels with the interpreter, compare per state
    frontier = spec.initial_states()
    seen = set(frontier)
    for _lvl in range(4):
        nxt = []
        for s in frontier[:40]:
            want = {t for _a, t in spec.successors(s)}
            enc = {
                v: jax.tree_util.tree_map(
                    jax.numpy.asarray,
                    __import__(
                        "pulsar_tlaplus_tpu.frontend.codegen_ir",
                        fromlist=["encode_value"],
                    ).encode_value(cs.var_descs[v], val),
                )
                for v, val in zip(spec.vars, s)
            }
            enc["__err__"] = jax.numpy.bool_(False)
            succ, valid = step(enc)
            got = set()
            for k in range(cs.A):
                if not bool(np.asarray(valid)[k]):
                    continue
                one = jax.tree_util.tree_map(lambda x: x[k], succ)
                dec = cs.decode_state(one)
                assert not bool(np.asarray(one["__err__"])), dec
                got.add(tuple(dec[v] for v in spec.vars))
            assert got == want, f"successor mismatch at {s}"
            for t in want:
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt


def test_factored_init_counting_kernel(module):
    """VERDICT r3 #6: a ModelProducer=FALSE Init (function-set draw) is
    generated by the mixed-radix counting kernel, not host enumeration —
    a ~43M-state init set must construct in seconds, and decoded states
    must be exactly the id-consistent sequences the interpreter draws
    (compaction.tla:191-194)."""
    import dataclasses
    import time

    big = dataclasses.replace(
        pe.SHIPPED_CFG, message_sent_limit=8
    )  # (3*3)^8 = 43,046,721 initial states
    t0 = time.time()
    cs = CompiledSpec(_spec(module, big))
    build_s = time.time() - t0
    assert cs._factored_init is not None
    assert cs.n_initial == (3 * 3) ** 8
    assert build_s < 60, f"factored init took {build_s:.1f}s"
    # decoded samples are valid initial states: full-length, id = pos
    for idx in (0, 12345, cs.n_initial - 1):
        dec = cs.decode_state(cs.gen_initial(idx))
        msgs = dec["messages"]
        assert len(msgs) == 8
        assert all(m["id"] == i + 1 for i, m in enumerate(msgs))
    # distinct indexes decode to distinct states
    a = cs.decode_state(cs.gen_initial(0))
    b = cs.decode_state(cs.gen_initial(1))
    assert a != b
    # and the shipped cfg's factored path reproduces the exact published
    # counts (test_compiled_shipped_cfg_published_count runs the BFS)
    cs_small = CompiledSpec(_spec(module, pe.SHIPPED_CFG))
    assert cs_small._factored_init is not None
    assert cs_small.n_initial == 729


def test_invariant_eval_poison_reports_eval_error():
    """An invariant whose evaluation errors on a reachable state (here:
    out-of-domain sequence index) must be reported as an evaluation
    error (__EvalError__), matching TLC's behavior, NOT as a violation
    of the invariant itself (ADVICE r2: codegen poison routing)."""
    from pulsar_tlaplus_tpu.frontend.parser import parse_module

    mod = parse_module(
        """---- MODULE poisoninv ----
EXTENDS Naturals, Sequences
VARIABLES x
Init == x = 0
Next == x < 2 /\\ x' = x + 1
BadInv == <<5, 6>>[x] > 0
====
"""
    )
    spec = I.Spec(mod, {})
    got, _cs = _check(
        spec, invariants=("BadInv",),
        sub_batch=8, visited_cap=1 << 10, frontier_cap=1 << 10,
    )
    # x = 0 is initial and indexes out of 1..2 -> eval error, not a
    # "BadInv is violated" report
    assert got.violation == "__EvalError__"


@pytest.mark.parametrize(
    "name", ["subscription", "bookkeeper", "georeplication"]
)
def test_compiled_original_specs(name):
    """Every original spec in specs/ compiles and matches its
    interpreter counts — structurally different protocols (cursor acks,
    BK write quorum, geo-replication) exercising nested functions,
    Cardinality, dynamic EXCEPT keys, and var-vs-var guard narrowing."""
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.utils.cfg import parse_cfg

    mod = parse_file(os.path.join(SPECS, f"{name}.tla"))
    cfg = parse_cfg(open(os.path.join(SPECS, f"{name}.cfg")).read())
    consts = bind_cfg(mod, cfg)
    spec = I.Spec(mod, consts)
    from pulsar_tlaplus_tpu.engine.interp_check import InterpChecker

    want = InterpChecker(spec, invariants=()).run()
    got, _cs = _check(spec)
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


# ---- a kernel is walked out of the AST once a model (ISSUE 50) -----------

SHIPPED_BINDINGS = ["compaction", "bookkeeper", "subscription", "georeplication"]


def _shipped(name):
    """The compiled model of ``specs/<name>.tla`` under its shipped
    ``.cfg``, invariants and all, and 256 of its reachable states (a
    breadth-first walk by the WALKED kernel from the first initial
    states, distinct by packed row) as one batch."""
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.utils.cfg import parse_cfg

    mod = parse_file(os.path.join(SPECS, f"{name}.tla"))
    with open(os.path.join(SPECS, f"{name}.cfg"), encoding="utf-8") as f:
        cfg = parse_cfg(f.read())
    consts = bind_cfg(mod, cfg)
    consts.pop("__string_interning__", None)
    cs = CompiledSpec(I.Spec(mod, consts), invariants=tuple(cfg.invariants))
    n = 256
    step = jax.jit(jax.vmap(cs._walk_successors))
    pack = jax.jit(jax.vmap(cs.layout.pack))
    tree = jax.tree_util
    init = jax.vmap(cs.gen_initial)(
        jnp.arange(min(cs.n_initial, n), dtype=jnp.int32))
    pool = tree.tree_map(np.asarray, init)
    seen = {bytes(r) for r in np.asarray(pack(init))}
    at = 0
    while len(seen) < n and at < len(seen):
        idx = np.arange(at, at + n) % len(seen)  # a full batch: one shape
        at = min(at + n, len(seen))
        succ, valid = step(tree.tree_map(lambda x: jnp.asarray(x[idx]), pool))
        flat = tree.tree_map(
            lambda x: np.asarray(x).reshape((-1,) + x.shape[2:]), succ)
        rows = np.asarray(pack(tree.tree_map(jnp.asarray, flat)))
        new = []
        for i in np.flatnonzero(np.asarray(valid).reshape(-1)):
            if bytes(rows[i]) not in seen:
                seen.add(bytes(rows[i]))
                new.append(i)
        pool = tree.tree_map(
            lambda p, x: np.concatenate([p, x[new]]), pool, flat)
    assert len(seen) >= n, (name, len(seen))
    return cs, tree.tree_map(lambda x: jnp.asarray(x[:n]), pool)


def _same_bits(got, want):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


@pytest.mark.parametrize("name", SHIPPED_BINDINGS)
def test_a_replayed_kernel_is_the_walked_one_bit_for_bit(name):
    """``successors`` and every invariant, answered from the trace the
    constructor kept, equal the code generator's own output on 256
    reachable states: under ``jax.vmap`` at two batch sizes and
    unbatched (``lax.map`` hands the kernel one state at its element
    shapes); no call walks the AST again.  ``invariants`` hands out the
    same functions on every access."""
    cs, states = _shipped(name)
    first, second = cs.invariants, cs.invariants
    assert list(first) == list(cs.requested_invariants) + ["__EvalError__"]
    assert all(first[k] is second[k] for k in first)
    walked = {"successors": cs._walk_successors,
              "__EvalError__": cs._walk_eval_error}
    for inv in cs.requested_invariants:
        walked[inv] = functools.partial(cs._walk_invariant, name=inv)
    kept = dict(first, successors=cs.successors)
    before = dict(cs.codegen_stats)
    quarters = jax.tree_util.tree_map(
        lambda x: x.reshape((4, 64) + x.shape[1:]), states)
    for kernel, walk in walked.items():
        want = jax.jit(jax.vmap(walk))(states)
        fn = kept[kernel]
        _same_bits(jax.jit(jax.vmap(fn))(states), want)
        by64 = jax.jit(lambda q, fn=fn: lax.map(jax.vmap(fn), q))(quarters)
        _same_bits(jax.tree_util.tree_map(
            lambda x: x.reshape((256,) + x.shape[2:]), by64), want)
        _same_bits(jax.jit(lambda s, fn=fn: lax.map(fn, s))(states), want)
    after = cs.codegen_stats
    assert after["codegen_walks"] == before["codegen_walks"] == len(walked)
    assert after["codegen_replays"] - before["codegen_replays"] == (
        3 * len(walked))


def test_kernels_are_walked_in_the_constructor_and_replayed_after(module):
    """After the constructor ``codegen_walks`` is the number of kernels
    (``successors``, the invariants asked for, ``__EvalError__``); a
    trace of ``jax.vmap(cs.successors)`` at two batch sizes and of a
    ``jax.jit`` that calls it twice walks nothing and counts four
    replays; a state of another dtype is walked ONCE, by the same path
    (one more ``codegen_walks``), gives the generator's own answer and
    is replayed from then on; a kernel that outlives its model answers
    the kinds of state it has met and refuses a new one by name."""
    cs = CompiledSpec(
        _spec(module, pe.SHIPPED_CFG),
        invariants=("TypeSafe", "CompactionHorizonCorrectness"))
    counters = ("codegen_walks", "codegen_replays")
    assert [cs.codegen_stats[k] for k in counters] == [4, 0]
    for n in (8, 32):
        batch = jax.vmap(cs.gen_initial)(jnp.arange(n, dtype=jnp.int32))
        jax.make_jaxpr(jax.vmap(cs.successors))(batch)
    one = cs.gen_initial(jnp.int32(3))
    twice = jax.jit(lambda s: (cs.successors(s), cs.successors(s)))
    (succ, valid), again = twice(one)
    assert [cs.codegen_stats[k] for k in counters] == [4, 4]
    _same_bits(again, (succ, valid))
    _same_bits((succ, valid), jax.jit(cs._walk_successors)(one))
    # the same state with its 32-bit integers held in 16: a kind of
    # state the constructor did not meet
    narrow = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.int16) if x.dtype == jnp.int32 else x, one)
    assert any(x.dtype == jnp.int16 for x in jax.tree_util.tree_leaves(narrow))
    got = jax.jit(cs.successors)(narrow)
    assert [cs.codegen_stats[k] for k in counters] == [5, 5]
    _same_bits(got, jax.jit(cs._walk_successors)(narrow))
    assert np.array_equal(np.asarray(got[1]), np.asarray(valid))
    jax.make_jaxpr(jax.vmap(cs.successors))(
        jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), narrow))
    assert [cs.codegen_stats[k] for k in counters] == [5, 6]
    # and the model's identity does not know about any of it
    same = CompiledSpec(
        _spec(module, pe.SHIPPED_CFG),
        invariants=("TypeSafe", "CompactionHorizonCorrectness"))
    assert same == cs and hash(same) == hash(cs)
    assert [same.codegen_stats[k] for k in counters] == [4, 0]
    # a kernel holds its model weakly: the last reference frees the
    # model (no collector), and the kernel left behind says so
    type_safe = same.invariants["TypeSafe"]
    gone = weakref.ref(same)
    del same
    assert gone() is None
    assert bool(jax.jit(type_safe)(one))
    with pytest.raises(ReferenceError, match="CompiledSpec is gone"):
        jax.jit(type_safe)(narrow)


def test_an_unsupported_construct_is_refused_by_the_constructor():
    """An invariant the generator cannot emit fails where
    ``_check_compiles`` walks it, as ``CodegenError``, before any
    check starts (the CLI then falls back to the interpreter)."""
    from pulsar_tlaplus_tpu.frontend.codegen_ir import CodegenError
    from pulsar_tlaplus_tpu.frontend.parser import parse_module

    mod = parse_module(
        """---- MODULE badinv ----
EXTENDS Naturals, Sequences
VARIABLES x
Init == x = 0
Next == x < 2 /\\ x' = x + 1
BadInv == Len(x) > 0
====
"""
    )
    spec = I.Spec(mod, {})
    assert CompiledSpec(spec).codegen_stats["codegen_walks"] == 2
    with pytest.raises(CodegenError, match="Len of"):
        CompiledSpec(spec, invariants=("BadInv",))
