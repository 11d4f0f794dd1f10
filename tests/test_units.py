"""A body is traced once a process (ISSUE 33).

The single-chip engine's programs are units (``engine/units.py``,
``engine/bodies.py``): module-level, closure-free functions that JAX's
own caches key on their argument list.  A second check of one binding
runs none of their Python (``jit_body_traces == 0``) and gives the
verdict of the first; a change to anything a body reads is a miss and
gives that configuration's own verdict; and the mechanism never changes
a program (the compile cache a run without it fills answers every
program of a run with it: tests/test_units_programs.py).
"""

import contextlib
import inspect
import io
import json
import os
import re

import jax
import pytest

from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.engine import bodies
from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.bookkeeper import (
    BookkeeperConstants,
    BookkeeperModel,
)
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.models.georeplication import (
    GeoConstants,
    GeoreplicationModel,
)
from pulsar_tlaplus_tpu.models.subscription import (
    SubscriptionConstants,
    SubscriptionModel,
)
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ops.dedup import KeySpec
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "specs", "compaction.tla")
CFG_45K = os.path.join(ROOT, "specs", "compaction.cfg")

LEVEL_LINE = re.compile(r"^\s*level (\d+): \+(\d+) \(total (\d+),", re.M)
VERDICT = re.compile(
    r"(\d+) distinct states found, search depth \(diameter\) (\d+)"
)


def _cli_check(tmp_path, n, *argv):
    """One ``cli check`` in this process: exit code, stdout, the level
    sizes of its progress lines, and the result event's stats."""
    tel = str(tmp_path / f"tel_{n}.jsonl")
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        try:
            rc = cli.main(["check", SPEC, *argv, "-telemetry", tel])
        except SystemExit as e:
            rc = e.code
    with open(tel, encoding="utf-8") as f:
        events = [json.loads(x) for x in f if x.strip()]
    stats = [e for e in events if e.get("event") == "result"][-1]["stats"]
    sizes = [int(m.group(2)) for m in LEVEL_LINE.finditer(err.getvalue())]
    return rc, out.getvalue(), sizes, stats


def _strip_timing(text):
    """The CLI's report less its one wall-clock line."""
    return "\n".join(
        ln for ln in text.splitlines() if not ln.startswith("Finished in")
    )


# ---- (a) a second check of one binding traces nothing ------------------


def test_second_check_of_a_binding_traces_no_body(tmp_path):
    first = _cli_check(tmp_path, 0, "-config", CFG_45K)
    second = _cli_check(tmp_path, 1, "-config", CFG_45K)
    assert first[0] == second[0] == 0
    assert VERDICT.search(first[1]).groups() == ("45198", "20")
    assert _strip_timing(second[1]) == _strip_timing(first[1])
    assert second[2] == first[2] and len(first[2]) == 19
    assert second[3]["jit_body_traces"] == 0
    assert second[3]["jit_traces"] < first[3]["jit_traces"] or (
        first[3]["jit_body_traces"] == 0  # an earlier test traced them
    )


def test_second_violation_prints_the_same_trace(tmp_path):
    argv = ("-config", CFG_45K, "-invariant", "CompactedLedgerLeak")
    first = _cli_check(tmp_path, 0, *argv)
    second = _cli_check(tmp_path, 1, *argv)
    assert first[0] == second[0] == 1
    assert "Invariant CompactedLedgerLeak is violated" in first[1]
    assert "State 12:" in first[1] and "State 13:" not in first[1]
    assert _strip_timing(second[1]) == _strip_timing(first[1])
    assert second[2] == first[2]
    assert second[3]["jit_body_traces"] == 0


# ---- (b) anything a body reads is in its key: a change is a miss --------

# a window size no other test uses, so that no earlier test of this
# process can have traced these bodies at these arguments
G0 = 96


def _run(c, **kw):
    kw.setdefault("invariants", ("TypeSafe",))
    kw.setdefault("sub_batch", G0)
    kw.setdefault("visited_cap", 1 << 12)
    kw.setdefault("frontier_cap", 1 << 12)
    ck = DeviceChecker(CompactionModel(c), **kw)
    r = ck.run()
    verdict = (
        r.distinct_states, r.diameter, tuple(r.level_sizes),
        r.violation, len(r.trace or ()), r.deadlock,
    )
    return verdict, ck.last_stats["jit_body_traces"]


def _assert_oracle(verdict, c, invariants):
    """The verdict a fresh process gives is the reference's."""
    ref = pe.check(c, invariants=invariants)
    if ref.violation is None:
        assert verdict[3] is None and not verdict[5]
        assert verdict[:2] == (ref.distinct_states, ref.diameter)
        assert sum(verdict[2]) == ref.distinct_states
    else:
        assert verdict[3] == ref.violation
        assert verdict[4] == len(ref.trace)


BASE = SMALL_CONFIGS["producer_on"]
# a binding wide enough (>= 96 bits) that fp_bits picks the key columns
WIDE = pe.Constants(
    message_sent_limit=10, compaction_times_limit=3, num_keys=3,
    num_values=3, max_crash_times=1, model_producer=True,
)


@pytest.fixture(scope="module")
def base_verdict():
    """The base binding, checked twice: every unit is traced for it."""
    first, _ = _run(BASE)
    second, traced = _run(BASE)
    assert second == first and traced == 0
    _assert_oracle(first, BASE, ("TypeSafe",))
    return first


STALE_CASES = {
    # case: (constants, constructor arguments) differing from the base
    # in exactly one input of the units
    "constant": (SMALL_CONFIGS["two_crashes"], {}),
    "invariants": (
        BASE, {"invariants": ("TypeSafe", "CompactedLedgerLeak")}
    ),
    "no_invariants": (BASE, {"invariants": ()}),
    "check_deadlock": (BASE, {"check_deadlock": False}),
    "sub_batch": (BASE, {"sub_batch": G0 // 2}),
    "expand_chunk": (BASE, {"expand_chunk": G0 // 3}),
    "growth_tier": (BASE, {"visited_cap": 1 << 5, "frontier_cap": 1 << 5}),
    "flush_factor": (BASE, {"flush_factor": 2}),
    "fuse_stage": (BASE, {"fuse": "stage"}),
    "frontier_window": (BASE, {"rows_window": "frontier"}),
    "probe_ladder": (BASE, {"fpset_dense_rounds": 2}),
}


@pytest.mark.parametrize("case", sorted(STALE_CASES))
def test_a_changed_input_is_a_miss_with_its_own_verdict(
    case, base_verdict
):
    c, kw = STALE_CASES[case]
    got, traced = _run(c, **kw)
    assert traced > 0, "a cached body answered a changed input"
    _assert_oracle(got, c, tuple(kw.get("invariants", ("TypeSafe",))))
    # and the base is still the base: its bodies were not replaced
    again, traced = _run(BASE)
    assert again == base_verdict and traced == 0


def test_fp_bits_is_in_the_key_of_a_wide_binding():
    kw = dict(
        invariants=(), visited_cap=1 << 10, frontier_cap=1 << 10,
        max_states=3000,
    )
    assert CompactionModel(WIDE).layout.total_bits >= 96
    a, _ = _run(WIDE, fp_bits=64, **kw)
    a2, traced = _run(WIDE, fp_bits=64, **kw)
    assert a2 == a and traced == 0
    b, traced = _run(WIDE, fp_bits=96, **kw)
    assert traced > 0
    # the verdict a fresh process gives: nothing cached to answer with
    jax.clear_caches()
    b_fresh, fresh = _run(WIDE, fp_bits=96, **kw)
    assert fresh > 0 and b == b_fresh


def test_the_materialization_is_in_the_key(monkeypatch, base_verdict):
    monkeypatch.setenv("PTT_COMPACT_MATERIALIZE", "gather")
    a, _ = _run(BASE)  # traced here, or by the fixture: either way
    monkeypatch.setenv("PTT_COMPACT_MATERIALIZE", "shift")
    b, traced = _run(BASE)
    assert traced > 0 and b == a == base_verdict
    monkeypatch.setenv("PTT_COMPACT_MATERIALIZE", "gather")
    again, traced = _run(BASE)
    assert traced == 0 and again == base_verdict


# ---- (c) structure: module-level, closure-free, no self -----------------

UNITS = (
    "ptt_level2", "ptt_expand", "ptt_init", "ptt_fpflush2", "ptt_rehash2",
    "ptt_compact", "ptt_append", "ptt_grow", "ptt_ckpt_fetch",
    "ptt_restore_pad",
)


def test_the_programs_of_bodies_are_the_units():
    found = {
        name for name, obj in vars(bodies).items()
        if callable(obj) and hasattr(obj, "body")
    }
    assert found == set(UNITS)


@pytest.mark.parametrize("name", UNITS)
def test_every_unit_is_a_module_level_closure_free_function(name):
    fn = inspect.unwrap(getattr(bodies, name).body)
    assert inspect.isfunction(fn)
    assert fn.__closure__ is None
    assert fn.__qualname__ == name  # no <locals>: module level
    assert fn.__module__ == bodies.__name__
    params = inspect.signature(fn).parameters
    assert "self" not in params
    # arrays by position, everything else by keyword
    assert {p.kind for p in params.values()} <= {
        inspect.Parameter.POSITIONAL_OR_KEYWORD,
        inspect.Parameter.KEYWORD_ONLY,
    }


def test_a_unit_counts_the_runs_of_its_body():
    import jax.numpy as jnp

    meter = spans.compile_meter()

    def compact(materialize):
        # the accumulator is donated: a new one every call
        arows = jnp.arange(2 * 7, dtype=jnp.uint32).reshape(2, 7)
        flag = jnp.asarray([1, 0, 1, 0, 0, 1, 1], jnp.uint32)
        crows, _ = bodies.ptt_compact(arows, flag, materialize=materialize)
        assert crows[0, :4].tolist() == [0, 2, 5, 6]

    before = meter.snapshot()
    compact("shift")
    first = meter.since(before)["jit_body_traces"]
    compact("shift")
    assert meter.since(before)["jit_body_traces"] == first <= 1
    compact("gather")
    assert meter.since(before)["jit_body_traces"] == first + 1


# ---- (d) a model has value identity -------------------------------------

MODELS = [
    (CompactionModel, SMALL_CONFIGS["producer_on"],
     SMALL_CONFIGS["two_crashes"]),
    (SubscriptionModel, SubscriptionConstants(2, 1),
     SubscriptionConstants(3, 1)),
    (BookkeeperModel, BookkeeperConstants(3, 2, 2, 2, 1),
     BookkeeperConstants(3, 2, 2, 3, 1)),
    (GeoreplicationModel, GeoConstants(2, 2, 1), GeoConstants(2, 3, 1)),
]


@pytest.mark.parametrize(
    "cls,c1,c2", MODELS, ids=[m[0].__name__ for m in MODELS]
)
def test_models_are_equal_by_class_and_constants(cls, c1, c2):
    a, b, other = cls(c1), cls(c1), cls(c2)
    assert a is not b and a == b and hash(a) == hash(b)
    assert a != other and {a, b, other} == {a, other}
    assert a != object() and a != CompactionModel(pe.SHIPPED_CFG)


def test_compiled_specs_are_equal_by_module_constants_and_invariants():
    from pulsar_tlaplus_tpu.frontend.codegen import CompiledSpec
    from pulsar_tlaplus_tpu.frontend.interp import Spec
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.frontend.parser import parse_file
    from pulsar_tlaplus_tpu.utils import cfg as cfgmod

    path = os.path.join(ROOT, "specs", "subscription.tla")

    def build(limit, invariants=("TypeOK",)):
        tlc = cfgmod.load(os.path.splitext(path)[0] + ".cfg")
        tlc.constants["MessageLimit"] = limit
        ast = parse_file(path)
        consts = bind_cfg(ast, tlc)
        consts.pop("__string_interning__", None)
        inv = tuple(n for n in invariants if n in Spec(ast, consts).defs)
        return CompiledSpec(Spec(ast, consts), invariants=inv)

    a, b = build(2), build(2)
    assert a is not b and a == b and hash(a) == hash(b)
    assert a != build(3)
    assert a != build(2, invariants=())


def test_key_specs_are_equal_by_layout():
    assert KeySpec(42, 2) == KeySpec(42, 2)
    assert hash(KeySpec(200, 7, 64)) == hash(KeySpec(200, 7, None))
    assert KeySpec(200, 7, 64) != KeySpec(200, 7, 96)
    assert KeySpec(42, 2) != KeySpec(70, 3)
