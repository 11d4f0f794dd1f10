"""Differential tests for the georeplication spec
(specs/georeplication.tla): compiled TPU model vs the generic interpreter
on the same .tla source, plus the safety+liveness+simulation trio this
spec headlines."""

import os

import jax
import jax.numpy as jnp
import pytest

from pulsar_tlaplus_tpu.engine.bfs import Checker
from pulsar_tlaplus_tpu.engine.interp_check import InterpChecker
from pulsar_tlaplus_tpu.frontend.interp import Spec, install_defs
from pulsar_tlaplus_tpu.frontend.parser import parse_file
from pulsar_tlaplus_tpu.models.georeplication import (
    GeoConstants,
    GeoreplicationModel,
)

SPEC_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "specs",
    "georeplication.tla",
)

CONFIGS = {
    "shipped": GeoConstants(),  # 3 clusters, 1 msg each, 1 crash
    "two_clusters": GeoConstants(
        num_clusters=2, publish_limit=2, max_replicator_crashes=1
    ),
    "no_crash": GeoConstants(max_replicator_crashes=0),
}

SAFE = ("TypeOK", "CursorWithinWatermark", "NoPhantomMessages")


@pytest.fixture(scope="module")
def module():
    return parse_file(SPEC_PATH)


def spec_for(module, c: GeoConstants) -> Spec:
    return Spec(
        module,
        {
            "NumClusters": c.num_clusters,
            "PublishLimit": c.publish_limit,
            "MaxReplicatorCrashes": c.max_replicator_crashes,
        },
    )


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_and_verdicts_match_interpreter(module, name):
    c = CONFIGS[name]
    spec = spec_for(module, c)
    ri = InterpChecker(spec, invariants=SAFE).run()
    m = GeoreplicationModel(c)
    rm = Checker(m, invariants=SAFE, frontier_chunk=512).run()
    assert ri.violation is None and rm.violation is None
    assert not ri.deadlock and not rm.deadlock
    assert rm.distinct_states == ri.distinct_states
    assert rm.diameter == ri.diameter
    assert rm.level_sizes == ri.level_sizes


def test_exact_state_set_matches_interpreter(module):
    c = CONFIGS["two_clusters"]
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
    m = GeoreplicationModel(c)
    ck = Checker(m, frontier_chunk=512, keep_log=True)
    ck.run()
    packed = ck.last_run_state.log.packed_matrix()
    unpack = jax.jit(m.layout.unpack)
    got = {m.to_interp_state(unpack(jnp.asarray(row))) for row in packed}
    assert got == expected


def test_golden_bug_duplicate_delivery(module):
    """NoDuplicateDelivery is violated at MaxReplicatorCrashes >= 1 with
    the shortest failover-redelivery trace, identical on both paths, and
    HOLDS at zero crashes (exactly-once without failover)."""
    m_ok = GeoreplicationModel(CONFIGS["no_crash"])
    r_ok = Checker(m_ok, invariants=("NoDuplicateDelivery",)).run()
    assert r_ok.violation is None

    c = CONFIGS["shipped"]
    spec = spec_for(module, c)
    install_defs(spec)
    ri = InterpChecker(spec, invariants=("NoDuplicateDelivery",)).run()
    m = GeoreplicationModel(c)
    rm = Checker(m, invariants=("NoDuplicateDelivery",)).run()
    assert ri.violation == rm.violation == "NoDuplicateDelivery"
    assert len(ri.trace) == len(rm.trace) == 5
    assert rm.trace_actions == [
        "Publish", "Replicate", "ReplicatorCrash", "Replicate",
    ]
    # replay the compiled trace on interpreter semantics
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
    m = GeoreplicationModel(c)
    base = Checker(m, frontier_chunk=512).run()
    for nd in (2, 8):
        r = ShardedChecker(
            m, n_devices=nd, frontier_chunk=128, visited_cap=1 << 12
        ).run()
        assert r.distinct_states == base.distinct_states, nd
        assert r.diameter == base.diameter


def test_liveness_termination():
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    m = GeoreplicationModel(CONFIGS["two_clusters"])
    r = LivenessChecker(m, goal="Termination", fairness="wf_next").run()
    assert r.holds, r.reason
    r2 = LivenessChecker(m, goal="Termination", fairness="none").run()
    assert not r2.holds


# ---- pinned oracle counts (r15, scenario diversity) -----------------
# Georeplication becomes the THIRD exact-parity pinned workload beside
# compaction (45,198 / 253,361) and bookkeeper (297 / 2,257): the
# shipped binding (specs/georeplication.cfg — 3 clusters, 1 msg, 1
# crash) pins 6,400 states / diameter 18 on the interpreter AND the
# device engine, making it a tuning target and a daemon registry
# workload with a ground truth.  Derived from the interpreter BFS on
# specs/georeplication.tla; the smaller two_clusters binding (460 /
# 14) re-derives inline as the cheap cross-check.

SHIPPED_STATES, SHIPPED_DIAMETER = 6400, 18   # specs/georeplication.cfg
TWO_CLUSTERS_STATES, TWO_CLUSTERS_DIAMETER = 460, 14


def test_shipped_cfg_pinned_oracle_count(module):
    """Interpreter, host engine, and device engine all reproduce the
    pinned shipped-binding count — the exact-parity contract the
    other two registry workloads already carry."""
    c = CONFIGS["shipped"]
    ri = InterpChecker(spec_for(module, c)).run()
    assert (ri.distinct_states, ri.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    rh = Checker(GeoreplicationModel(c), frontier_chunk=512).run()
    assert (rh.distinct_states, rh.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    rd = DeviceChecker(
        GeoreplicationModel(c), sub_batch=512, visited_cap=1 << 13,
        frontier_cap=1 << 11,
    ).run()
    assert (rd.distinct_states, rd.diameter) == (
        SHIPPED_STATES, SHIPPED_DIAMETER,
    )
    assert rd.violation is None and not rd.deadlock


def test_two_clusters_pinned_oracle_count(module):
    """The cheap binding's pinned count (re-derived on the
    interpreter + pinned on the device engine)."""
    c = CONFIGS["two_clusters"]
    ri = InterpChecker(spec_for(module, c)).run()
    assert (ri.distinct_states, ri.diameter) == (
        TWO_CLUSTERS_STATES, TWO_CLUSTERS_DIAMETER,
    )
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    rd = DeviceChecker(
        GeoreplicationModel(c), sub_batch=256, visited_cap=1 << 11,
        frontier_cap=1 << 9,
    ).run()
    assert (rd.distinct_states, rd.diameter) == (
        TWO_CLUSTERS_STATES, TWO_CLUSTERS_DIAMETER,
    )


def test_simulation_finds_duplicate():
    from pulsar_tlaplus_tpu.engine.simulate import Simulator

    m = GeoreplicationModel(CONFIGS["shipped"])
    sres = Simulator(
        m,
        invariants=("NoDuplicateDelivery",),
        n_walkers=1024,
        depth=24,
        seed=2,
    ).run()
    assert sres.violation == "NoDuplicateDelivery"
    final = sres.trace[-1]
    assert "{1" in final["duplicated"] or "{2" in final["duplicated"]
    for st in sres.trace[:-1]:
        assert "{1" not in st["duplicated"] and "{2" not in st["duplicated"]
