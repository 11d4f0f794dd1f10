"""Swarm simulation subsystem (round 18) — TLC's ``-simulate`` as a
production streaming workload (docs/simulation.md).

The exhaustive engines stop at the fpset/HBM ceiling; the walker swarm
never does.  :class:`~pulsar_tlaplus_tpu.sim.engine.StreamingSimulator`
runs thousands of vectorized random walks per dispatch, continuously,
under state/time budgets — resumable, deterministic given ``seed``,
wired through every platform layer (telemetry, metrics, traces,
checkpoints, the serve daemon, the bench/ledger loop).
``engine/simulate.py`` keeps the legacy one-shot API as a thin shim.
"""

from pulsar_tlaplus_tpu.sim.engine import (  # noqa: F401
    SimulationResult,
    StreamingSimulator,
)
