"""Differential tests for the subscription spec (specs/subscription.tla):
compiled TPU model vs the generic interpreter on the same .tla source —
state sets, counts, diameters, invariant verdicts, counterexample traces,
sharded parity, liveness, and simulation mode."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.bfs import Checker
from pulsar_tlaplus_tpu.engine.interp_check import InterpChecker
from pulsar_tlaplus_tpu.frontend.interp import Spec, install_defs
from pulsar_tlaplus_tpu.frontend.parser import parse_file
from pulsar_tlaplus_tpu.models.subscription import (
    SubscriptionConstants,
    SubscriptionModel,
)
from tests.helpers import tight_hbm_budget

SPEC_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "specs",
    "subscription.tla",
)

CONFIGS = {
    "tiny": SubscriptionConstants(message_limit=2, max_crash_times=1),
    "shipped": SubscriptionConstants(message_limit=3, max_crash_times=2),
    "no_crash": SubscriptionConstants(message_limit=3, max_crash_times=0),
}


@pytest.fixture(scope="module")
def module():
    return parse_file(SPEC_PATH)


def spec_for(module, c: SubscriptionConstants) -> Spec:
    return Spec(
        module,
        {"MessageLimit": c.message_limit, "MaxCrashTimes": c.max_crash_times},
    )


def run_model(c, **kw):
    m = SubscriptionModel(c)
    return m, Checker(m, frontier_chunk=256, keep_log=True, **kw).run()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_and_verdicts_match_interpreter(module, name):
    c = CONFIGS[name]
    spec = spec_for(module, c)
    ri = InterpChecker(
        spec, invariants=("TypeOK", "NoLostMessage", "AckedWasProcessed")
    ).run()
    m, rm = run_model(c)
    assert ri.violation is None and rm.violation is None
    assert not ri.deadlock and not rm.deadlock
    assert rm.distinct_states == ri.distinct_states
    assert rm.diameter == ri.diameter
    assert rm.level_sizes == ri.level_sizes


def test_exact_state_set_matches_interpreter(module):
    c = CONFIGS["tiny"]
    spec = spec_for(module, c)
    install_defs(spec)
    expected = {spec.initial_states()[0]}
    frontier = list(expected)
    while frontier:
        new = []
        for s in frontier:
            for _lab, t in spec.successors(s):
                if t not in expected:
                    expected.add(t)
                    new.append(t)
        frontier = new
    m = SubscriptionModel(c)
    ck = Checker(m, frontier_chunk=256, keep_log=True)
    ck.run()
    packed = ck.last_run_state.log.packed_matrix()
    unpack = jax.jit(m.layout.unpack)
    got = {
        m.to_interp_state(unpack(jnp.asarray(row))) for row in packed
    }
    assert got == expected


def test_golden_bug_duplicate_processing(module):
    """ExactlyOnceProcessing is violated (at-least-once delivery); both
    paths find the same shortest depth and the trace replays on the
    interpreter semantics."""
    c = CONFIGS["shipped"]
    spec = spec_for(module, c)
    install_defs(spec)
    ri = InterpChecker(spec, invariants=("ExactlyOnceProcessing",)).run()
    m, rm = run_model(c, invariants=("ExactlyOnceProcessing",))
    assert ri.violation == rm.violation == "ExactlyOnceProcessing"
    assert len(ri.trace) == len(rm.trace) == 7
    assert rm.trace_actions == [
        "Publish", "Deliver", "Process", "ConsumerCrash", "Deliver", "Process",
    ]
    # only the final state violates; duplicate visible only at the end
    assert rm.trace[0]["produced"] == 0
    assert rm.trace[-1]["duplicated"] != "{}"
    for st in rm.trace[:-1]:
        assert st["duplicated"] == "{}"
    # the compiled trace replays step by step on the interpreter semantics:
    # every consecutive rendered state must be a real labeled transition
    rendered = lambda t: m.to_pystate(m.from_interp_state(t))
    cur = spec.initial_states()[0]
    assert rendered(cur) == rm.trace[0]
    for act, want in zip(rm.trace_actions, rm.trace[1:]):
        nxt = [
            t
            for lab, t in spec.successors(cur)
            if lab == act and rendered(t) == want
        ]
        assert nxt, (act, want)
        cur = nxt[0]


def test_no_crash_config_is_exactly_once(module):
    """With MaxCrashTimes = 0 no duplicate is reachable: the bug invariant
    HOLDS, pinning that redelivery-after-crash is the only dup source."""
    c = CONFIGS["no_crash"]
    m, rm = run_model(c, invariants=("ExactlyOnceProcessing",))
    assert rm.violation is None
    spec = spec_for(module, c)
    ri = InterpChecker(spec, invariants=("ExactlyOnceProcessing",)).run()
    assert ri.violation is None
    assert ri.distinct_states == rm.distinct_states


def test_sharded_counts_match():
    from pulsar_tlaplus_tpu.engine.sharded import ShardedChecker

    c = CONFIGS["tiny"]
    m = SubscriptionModel(c)
    base = Checker(m, frontier_chunk=256).run()
    for nd in (2, 4, 8):
        r = ShardedChecker(
            m, n_devices=nd, frontier_chunk=64, visited_cap=1 << 10
        ).run()
        assert r.distinct_states == base.distinct_states, nd
        assert r.diameter == base.diameter


def test_liveness_termination():
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    c = CONFIGS["tiny"]
    m = SubscriptionModel(c)
    r = LivenessChecker(m, goal="Termination", fairness="wf_next").run()
    assert r.holds, r.reason
    r2 = LivenessChecker(m, goal="Termination", fairness="none").run()
    assert not r2.holds  # raw Spec admits infinite stuttering at Init


# Subscription becomes the FOURTH exact-parity pinned workload beside
# compaction (45,198 / 253,361), bookkeeper (297 / 2,257), and
# georeplication (6,400): the shipped binding (specs/subscription.cfg —
# MessageLimit 3, MaxCrashTimes 2) pins 2,272 states / diameter 24 on
# the interpreter, the host engine, AND the device engine.  Derived
# from the interpreter BFS on specs/subscription.tla; the tiny binding
# (122 / 16) re-derives inline as the cheap cross-check.  It is also
# the round-16 SPILL-PARITY differential workload: the same device run
# under a budget that forces key eviction + row/log spill must be
# state-for-state identical (tests below; docs/memory.md).

SHIPPED_STATES, SHIPPED_DIAMETER = 2272, 24  # specs/subscription.cfg
TINY_STATES, TINY_DIAMETER = 122, 16


def test_shipped_cfg_pinned_oracle_count(module):
    """Interpreter, host engine, and device engine all reproduce the
    pinned shipped-binding count — the exact-parity contract the
    other three registry workloads already carry."""
    c = CONFIGS["shipped"]
    ri = InterpChecker(
        spec_for(module, c),
        invariants=("TypeOK", "NoLostMessage", "AckedWasProcessed"),
    ).run()
    assert (ri.distinct_states, ri.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    rh = Checker(SubscriptionModel(c), frontier_chunk=256).run()
    assert (rh.distinct_states, rh.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    rd = DeviceChecker(
        SubscriptionModel(c), sub_batch=256, visited_cap=1 << 12,
        frontier_cap=1 << 10,
    ).run()
    assert (rd.distinct_states, rd.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    assert rd.violation is None and not rd.deadlock
    ti = InterpChecker(
        spec_for(module, CONFIGS["tiny"]),
        invariants=("TypeOK", "NoLostMessage", "AckedWasProcessed"),
    ).run()
    assert (ti.distinct_states, ti.diameter) == (
        TINY_STATES, TINY_DIAMETER,
    )


def test_shipped_cfg_spill_parity_differential():
    """The round-16 spill-parity workload: the shipped subscription
    run under a budget that forces eviction + row/log spill is
    state-for-state identical to the untiered run — level sizes,
    packed rows, and parent/lane logs (merged cold+device view)."""
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    c = CONFIGS["shipped"]
    kw = dict(
        invariants=(), check_deadlock=False, sub_batch=128,
        visited_cap=1 << 9, frontier_cap=1 << 9,
    )
    ck_u = DeviceChecker(SubscriptionModel(c), **kw)
    r_u = ck_u.run()
    assert (r_u.distinct_states, r_u.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    budget = tight_hbm_budget(
        lambda b: DeviceChecker(SubscriptionModel(c), hbm_budget=b, **kw)
    )
    ck_t = DeviceChecker(SubscriptionModel(c), hbm_budget=budget, **kw)
    r_t = ck_t.run()
    assert r_t.distinct_states == r_u.distinct_states
    assert r_t.level_sizes == r_u.level_sizes
    assert ck_t.last_stats["spill_evictions"] >= 1
    assert ck_t.last_stats["spill_rows_evicted"] > 0
    nv, W = r_u.distinct_states, ck_u.W
    base = ck_t._last_rb["row_base"]
    cp, cl = ck_t.tstore.fetch_logs(0, base)
    pt = np.concatenate(
        [cp, np.asarray(ck_t.last_bufs["parent"][: nv - base])]
    )
    lt = np.concatenate(
        [cl, np.asarray(ck_t.last_bufs["lane"][: nv - base])]
    )
    assert (np.asarray(ck_u.last_bufs["parent"][:nv]) == pt).all()
    assert (np.asarray(ck_u.last_bufs["lane"][:nv]) == lt).all()
    cold = ck_t.tstore.fetch_rows(0, base, W)
    rt = np.concatenate(
        [cold, np.asarray(ck_t.last_bufs["rows"][: (nv - base) * W])]
    )
    assert (np.asarray(ck_u.last_bufs["rows"][: nv * W]) == rt).all()


def test_simulation_finds_duplicate():
    from pulsar_tlaplus_tpu.engine.simulate import Simulator

    c = CONFIGS["shipped"]
    m = SubscriptionModel(c)
    sres = Simulator(
        m,
        invariants=("ExactlyOnceProcessing",),
        n_walkers=512,
        depth=32,
        seed=3,
    ).run()
    assert sres.violation == "ExactlyOnceProcessing"
    assert sres.trace[-1]["duplicated"] != "{}"
    for st in sres.trace[:-1]:
        assert st["duplicated"] == "{}"
