"""Compiled-spec feature parity (VERDICT r2 #4/#5): every engine that
the hand-compiled registry models run on must accept a ``CompiledSpec``
built from raw .tla text and produce identical counts/verdicts —
sharded checking, simulation, checkpoint/resume, and compiled temporal
properties (the ``<>(predicate)`` fragment)."""

import os

import pytest

from pulsar_tlaplus_tpu.engine.bfs import Checker
from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
from pulsar_tlaplus_tpu.engine.sharded_device import ShardedDeviceChecker
from pulsar_tlaplus_tpu.engine.simulate import Simulator
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


def _compiled(module, c, invariants=()):
    spec = I.Spec(module, compaction_constants(c))
    return CompiledSpec(spec, invariants=invariants)


def test_compiled_sharded_matches_oracle(module):
    """-compile -sharded: the device-resident sharded engine accepts a
    CompiledSpec and matches the oracle exactly on an 8-shard mesh."""
    c = SMALL_CONFIGS["producer_on"]
    want = pe.check(c, invariants=())
    got = ShardedDeviceChecker(
        _compiled(module, c), n_devices=8, invariants=(), sub_batch=128,
        visited_cap=1 << 10,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter
    assert got.violation is None and not got.deadlock


@pytest.mark.parametrize(
    "name", ["subscription", "bookkeeper", "georeplication"]
)
def test_compiled_sharded_original_specs(name):
    from pulsar_tlaplus_tpu.engine.interp_check import InterpChecker
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.utils.cfg import parse_cfg

    mod = parse_file(os.path.join(SPECS, f"{name}.tla"))
    cfg = parse_cfg(open(os.path.join(SPECS, f"{name}.cfg")).read())
    spec = I.Spec(mod, bind_cfg(mod, cfg))
    want = InterpChecker(spec, invariants=()).run()
    got = ShardedDeviceChecker(
        CompiledSpec(spec), n_devices=4, invariants=(), sub_batch=128,
        visited_cap=1 << 10,
    ).run()
    assert got.distinct_states == want.distinct_states
    assert got.diameter == want.diameter


def test_compiled_checkpoint_resume_exact_count(module, tmp_path):
    """Checkpoint/resume on the compiled path: a truncated run resumes
    to the exact published 45,198-state count."""
    cs = _compiled(module, pe.SHIPPED_CFG)
    path = str(tmp_path / "ck.npz")
    r1 = Checker(
        cs, visited_cap=1 << 16, checkpoint_path=path,
        checkpoint_every=3, max_states=10_000,
    ).run()
    assert r1.truncated and r1.distinct_states < 45198
    r2 = Checker(
        cs, visited_cap=1 << 16, checkpoint_path=path
    ).run(resume=True)
    assert r2.distinct_states == 45198
    assert r2.diameter == 20


def test_compiled_simulation_finds_duplicate_bug(module):
    """Simulation mode on the compiled path: random walkers find the
    depth-4 DuplicateNullKeyMessage violation from the raw .tla."""
    cs = _compiled(
        module, pe.SHIPPED_CFG, invariants=("DuplicateNullKeyMessage",)
    )
    res = Simulator(cs, n_walkers=512, depth=8, seed=3).run()
    assert res.violation == "DuplicateNullKeyMessage"
    assert res.trace is not None


def test_compiled_termination_goal_matches_oracle(module):
    """<>Termination compiled from the raw .tla: verdicts match the
    oracle's liveness semantics under both fairness modes."""
    c = SMALL_CONFIGS["producer_on"]
    cs = _compiled(module, c)
    assert "Termination" in cs.liveness_goals
    for fairness in ("none", "wf_next"):
        want_holds, _why = pe.check_eventually(c, fairness=fairness)
        got = LivenessChecker(
            cs, goal="Termination", fairness=fairness,
        ).run()
        assert got.holds == want_holds, fairness
