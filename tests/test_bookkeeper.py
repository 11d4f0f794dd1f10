"""Differential tests for the bookkeeper spec (specs/bookkeeper.tla):
compiled TPU model vs the generic interpreter on the same .tla source."""

import os

import jax
import jax.numpy as jnp
import pytest

from pulsar_tlaplus_tpu.engine.bfs import Checker
from pulsar_tlaplus_tpu.engine.interp_check import InterpChecker
from pulsar_tlaplus_tpu.frontend.interp import Spec, install_defs
from pulsar_tlaplus_tpu.frontend.parser import parse_file
from pulsar_tlaplus_tpu.models.bookkeeper import (
    BookkeeperConstants,
    BookkeeperModel,
)

SPEC_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "specs",
    "bookkeeper.tla",
)

CONFIGS = {
    "shipped": BookkeeperConstants(),  # E=3 Qw=2 Qa=2 L=2 crashes=1
    "crash2": BookkeeperConstants(max_bookie_crashes=2),
    "wide_quorum": BookkeeperConstants(
        num_bookies=4, write_quorum=3, ack_quorum=2, entry_limit=2,
        max_bookie_crashes=1,
    ),
    "qa1": BookkeeperConstants(
        num_bookies=2, write_quorum=2, ack_quorum=1, entry_limit=2,
        max_bookie_crashes=1,
    ),
}

SAFE = ("TypeOK", "LacIsConfirmed", "AckImpliesStoredOrCrashed")


@pytest.fixture(scope="module")
def module():
    return parse_file(SPEC_PATH)


def spec_for(module, c: BookkeeperConstants) -> Spec:
    return Spec(
        module,
        {
            "NumBookies": c.num_bookies,
            "WriteQuorum": c.write_quorum,
            "AckQuorum": c.ack_quorum,
            "EntryLimit": c.entry_limit,
            "MaxBookieCrashes": c.max_bookie_crashes,
        },
    )


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_and_verdicts_match_interpreter(module, name):
    c = CONFIGS[name]
    spec = spec_for(module, c)
    ri = InterpChecker(spec, invariants=SAFE).run()
    m = BookkeeperModel(c)
    rm = Checker(m, invariants=SAFE, frontier_chunk=256).run()
    assert ri.violation is None and rm.violation is None
    assert not ri.deadlock and not rm.deadlock
    assert rm.distinct_states == ri.distinct_states
    assert rm.diameter == ri.diameter
    assert rm.level_sizes == ri.level_sizes


def test_exact_state_set_matches_interpreter(module):
    c = CONFIGS["shipped"]
    spec = spec_for(module, c)
    install_defs(spec)
    expected = set(spec.initial_states())
    frontier = list(expected)
    while frontier:
        new = []
        for s in frontier:
            for _lab, t in spec.successors(s):
                if t not in expected:
                    expected.add(t)
                    new.append(t)
        frontier = new
    m = BookkeeperModel(c)
    ck = Checker(m, frontier_chunk=256, keep_log=True)
    ck.run()
    packed = ck.last_run_state.log.packed_matrix()
    unpack = jax.jit(m.layout.unpack)
    got = {m.to_interp_state(unpack(jnp.asarray(row))) for row in packed}
    assert got == expected


def test_durability_contract_boundary(module):
    """MaxBookieCrashes < AckQuorum: ConfirmedEntryReadable HOLDS (the
    BookKeeper durability contract); at >= AckQuorum it is VIOLATED, with
    the same shortest ack-then-crash counterexample on both paths."""
    m_ok = BookkeeperModel(CONFIGS["shipped"])
    r_ok = Checker(m_ok, invariants=("ConfirmedEntryReadable",)).run()
    assert r_ok.violation is None

    c = CONFIGS["crash2"]
    spec = spec_for(module, c)
    install_defs(spec)
    ri = InterpChecker(spec, invariants=("ConfirmedEntryReadable",)).run()
    m = BookkeeperModel(c)
    rm = Checker(m, invariants=("ConfirmedEntryReadable",)).run()
    assert ri.violation == rm.violation == "ConfirmedEntryReadable"
    assert len(ri.trace) == len(rm.trace) == 9
    assert rm.trace_actions == [
        "AddEntry", "WriteLand", "WriteLand", "AckArrive", "AckArrive",
        "AdvanceLAC", "BookieCrash", "BookieCrash",
    ]
    # replay the compiled trace on interpreter semantics via rendering
    rendered = lambda t: m.to_pystate(m.from_interp_state(t))
    cur = spec.initial_states()[0]
    assert rendered(cur) == rm.trace[0]
    for act, want in zip(rm.trace_actions, rm.trace[1:]):
        nxt = [
            t for lab, t in spec.successors(cur)
            if lab == act and rendered(t) == want
        ]
        assert nxt, (act, want)
        cur = nxt[0]


def test_sharded_counts_match():
    from pulsar_tlaplus_tpu.engine.sharded import ShardedChecker

    c = CONFIGS["shipped"]
    m = BookkeeperModel(c)
    base = Checker(m, frontier_chunk=256).run()
    for nd in (2, 8):
        r = ShardedChecker(
            m, n_devices=nd, frontier_chunk=64, visited_cap=1 << 10
        ).run()
        assert r.distinct_states == base.distinct_states, nd
        assert r.diameter == base.diameter


def test_liveness_termination():
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    m = BookkeeperModel(CONFIGS["shipped"])
    r = LivenessChecker(m, goal="Termination", fairness="wf_next").run()
    assert r.holds, r.reason
    r2 = LivenessChecker(m, goal="Termination", fairness="none").run()
    assert not r2.holds


def test_simulation_finds_durability_violation():
    """Random walks find the ack-then-crash durability violation.

    The jax PRNG stream is version/platform-dependent, so any SINGLE
    pinned seed is an environment lottery (one seed can miss under
    one jax version and hit under another).  Scan a small
    deterministic seed list instead: each attempt exercises the full
    rollout+replay path, ~60% of seeds hit at these walk parameters,
    and the union is robust on every environment."""
    from pulsar_tlaplus_tpu.engine.simulate import Simulator

    m = BookkeeperModel(CONFIGS["crash2"])
    sres = None
    for seed in range(8):
        s = Simulator(
            m,
            invariants=("ConfirmedEntryReadable",),
            n_walkers=1024,
            depth=32,
            seed=seed,
        ).run()
        if s.violation is not None:
            sres = s
            break
    assert sres is not None, (
        "no seed in range(8) found the durability violation "
        "(1024 walkers x depth 32 — a genuine simulation regression)"
    )
    assert sres.violation == "ConfirmedEntryReadable"
    # final state: some confirmed entry with no surviving replica
    final = sres.trace[-1]
    assert final["lac"] >= 1


# ---- pinned oracle counts (r11, checking-as-a-service) --------------
# The daemon's multi-spec registry needs a second exact-parity workload
# beside compaction's published 45,198/253,361 figures: pin the Python
# oracle's reachable-state counts for bookkeeper and hold every engine
# the registry dispatches to them.  Derived once from the interpreter
# BFS on specs/bookkeeper.tla (the "shipped" count is re-derived inline
# below; the meatier EntryLimit=3 run takes ~2 s and is asserted
# against the literal only).

ORACLE_CFG = BookkeeperConstants(entry_limit=3)
SHIPPED_STATES, SHIPPED_DIAMETER = 297, 14    # specs/bookkeeper.cfg
ORACLE_STATES, ORACLE_DIAMETER = 2257, 20     # EntryLimit = 3


def test_shipped_cfg_pinned_oracle_count(module):
    """The daemon's default bookkeeper binding (specs/bookkeeper.cfg):
    interpreter, host engine, and the service registry's device engine
    all reproduce the pinned count."""
    c = CONFIGS["shipped"]
    ri = InterpChecker(spec_for(module, c)).run()
    assert (ri.distinct_states, ri.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    rh = Checker(BookkeeperModel(c), frontier_chunk=256).run()
    assert (rh.distinct_states, rh.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    rd = DeviceChecker(
        BookkeeperModel(c), sub_batch=256, visited_cap=1 << 12,
        frontier_cap=1 << 10,
    ).run()
    assert (rd.distinct_states, rd.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    assert rd.violation is None and not rd.deadlock


def test_entry_limit3_pinned_oracle_count(module):
    """EntryLimit=3 is the meatier pinned workload (2,257 states,
    diameter 20 — the bookkeeper analog of compaction's 253k oracle
    regime, scaled to the CPU-mesh test budget)."""
    ri = InterpChecker(spec_for(module, ORACLE_CFG)).run()
    assert (ri.distinct_states, ri.diameter) == (
        ORACLE_STATES, ORACLE_DIAMETER,
    )
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    rd = DeviceChecker(
        BookkeeperModel(ORACLE_CFG), sub_batch=256,
        visited_cap=1 << 13, frontier_cap=1 << 11,
    ).run()
    assert (rd.distinct_states, rd.diameter) == (
        ORACLE_STATES, ORACLE_DIAMETER,
    )
