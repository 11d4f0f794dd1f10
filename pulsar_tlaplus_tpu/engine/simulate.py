"""Legacy one-shot simulation API — a thin shim over the streaming
swarm subsystem (``pulsar_tlaplus_tpu/sim/``, round 18).

The round-2 :class:`Simulator` rolled a fixed batch of walkers to a
fixed depth once and returned.  That exact contract — constructor
signature, :class:`SimulationResult` fields, one behavior round of
``n_walkers`` walkers at ``depth`` steps, earliest-violation replay —
is preserved here as a one-round budget on the streaming engine
(``max_rounds=1``), so existing callers and tests run unchanged while
every new capability (budgets, telemetry, checkpoints, the daemon,
the bench/ledger loop) lives in ``sim/engine.py``.

Note the r18 PRNG derivation is functional in ``(seed, step,
walker)`` (the resumability contract), so a given seed explores a
different — equally deterministic — walk stream than the pre-r18
carried-key rollout did.
"""

from __future__ import annotations

from typing import Optional, Tuple

from pulsar_tlaplus_tpu.sim.engine import (  # noqa: F401 — re-export
    SimulationResult,
    StreamingSimulator,
)


class Simulator:
    """One-round walker-batch simulation (the legacy API)."""

    def __init__(
        self,
        model,
        invariants: Optional[Tuple[str, ...]] = None,
        n_walkers: int = 4096,
        depth: int = 64,
        seed: int = 0,
    ):
        self._eng = StreamingSimulator(
            model,
            invariants=invariants,
            n_walkers=n_walkers,
            depth=depth,
            seed=seed,
            max_rounds=1,
        )
        self.model = model
        self.invariant_names = self._eng.invariant_names
        self.B = self._eng.B
        self.T = self._eng.T
        self.seed = seed

    def run(self) -> SimulationResult:
        return self._eng.run()
