"""The declared knob space the offline tuner searches.

Each knob names an engine constructor parameter, its candidate values,
and the validity constraints that prune impossible combinations (the
engine would reject them anyway — pruning here keeps the predict stage
honest about how many candidates were actually considered).  The space
is deliberately small and discrete: the cost model ranks the whole
cartesian product in microseconds, and only the top-K survivors ever
touch the device (docs/tuning.md).

Knob semantics (all scheduling/batching — NONE may change discovery
order; pinned by the differential tests in tests/test_tune.py):

- ``sub_batch``       frontier states per expand window (G)
- ``flush_factor``    accumulator windows merged per fpset flush
- ``group``           dispatch group-ahead between stats fetches
                      (growth headroom follows it: (group+1) * ACAP)
- ``fuse_group``      max ramp levels one fused dispatch may close
- ``fpset_dense_rounds``  full-width probe rounds before the staged
                      pending-compaction shrinks the batch

Tiered-store knobs (round 16, searched only for budgeted workloads —
``candidates(spill=True)``; they are no-ops untiered and would only
dilute the measure stage there):

- ``hbm_headroom``    budget fraction reserved against transients
- ``spill_compress``  delta+zlib the cold planes (link bytes vs CPU)
- ``miss_batch``      sieved keys per cold-lookup batch
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple


@dataclass(frozen=True)
class Knob:
    name: str
    values: Tuple
    doc: str


# the device-engine search space.  Values are multipliers-of-default
# where the default is shape-dependent (sub_batch) and absolute
# elsewhere; ``None`` means "engine default / auto".
DEVICE_KNOBS: Tuple[Knob, ...] = (
    Knob(
        "sub_batch", (None, 0.25, 0.5, 2.0),
        "expand window G (x default)",
    ),
    Knob("flush_factor", (None, 2, 3), "acc windows per flush"),
    Knob("group", (None, 2, 8), "dispatch group-ahead"),
    Knob("fuse_group", (None, 1, 4, 16), "ramp levels per dispatch"),
    Knob("fpset_dense_rounds", (None, 2, 8), "dense probe rounds"),
)

# tiered-store knobs (r16): searched only when the workload is
# budgeted (hbm_budget set) — predict prices the link-crossing bytes
# at the calibration's measured byte rate (tune/predict.py)
SPILL_KNOBS: Tuple[Knob, ...] = (
    Knob("hbm_headroom", (None, 0.05, 0.2), "budget headroom fraction"),
    Knob(
        "spill_compress", (None, False),
        "delta+zlib cold planes (None = on)",
    ),
    Knob(
        "miss_batch", (None, 1 << 14, 1 << 16),
        "sieved keys per cold-lookup batch",
    ),
)

# swarm-simulation knobs (round 18, sim/engine.py — searched by
# ``cli.py tune --mode simulate``): the swarm width trades per-step
# parallelism against per-dispatch latency; the segment length
# amortizes the dispatch+fetch round trip over more steps (it is
# clamped to a divisor of ``depth`` at construction).  Neither knob
# changes the walk stream's SEMANTICS — a different (n_walkers,
# segment_len) is a different deterministic stream, which is why sim
# profiles resolve by config signature exactly like engine profiles.
SIM_KNOBS: Tuple[Knob, ...] = (
    Knob(
        "n_walkers", (None, 1024, 4096, 16384),
        "walker swarm width (walks per dispatch)",
    ),
    Knob(
        "segment_len", (None, 8, 32, 128),
        "steps per dispatch (clamped to a depth divisor)",
    ),
)


def sim_candidates(limit: Optional[int] = None) -> List[Dict]:
    """The simulation knob space as sparse dicts (defaults first —
    the baseline the tuner must beat), mirroring :func:`candidates`."""
    out: List[Dict] = []
    for combo in itertools.product(*(k.values for k in SIM_KNOBS)):
        cand = {
            k.name: v for k, v in zip(SIM_KNOBS, combo) if v is not None
        }
        out.append(cand)
        if limit is not None and len(out) >= limit:
            break
    return out


# liveness-engine knobs carried by profiles (loaded by
# LivenessChecker; offline search over them is future work — the
# device engine dominates exploration wall)
LIVENESS_KNOBS: Tuple[Knob, ...] = (
    Knob("sweep_group", (None, 2, 8, 32), "sweep chunks per dispatch"),
)

# every knob name a profile may carry, per engine — the profile
# validator and the engine-side resolver both consult this table
PROFILE_KNOBS: Dict[str, Tuple[str, ...]] = {
    "device_bfs": (
        "sub_batch", "flush_factor", "group", "fuse_group",
        "fpset_dense_rounds", "fpset_stages", "adapt",
        "hbm_headroom", "spill_compress", "miss_batch",
    ),
    "liveness": ("sweep_group", "adapt"),
    "sim": ("n_walkers", "segment_len"),
}


def _valid(model, cand: Dict, base_sub_batch: int) -> bool:
    """The engine's own constructor constraints, pre-checked so the
    predict stage never ranks a config the engine would reject."""
    g = cand.get("sub_batch") or base_sub_batch
    ff = cand.get("flush_factor") or 1
    a, w = int(model.A), int(model.layout.W)
    if g < 64:
        return False
    # flat accumulator addressing: sub_batch * A * flush_factor * W
    # must stay below 2^31 (device_bfs.__init__)
    if g * a * ff * w >= 1 << 31:
        return False
    return True


def candidates(
    model,
    base_sub_batch: int = 8192,
    knobs: Iterable[Knob] = DEVICE_KNOBS,
    limit: Optional[int] = None,
    spill: bool = False,
) -> List[Dict]:
    """The cartesian product of the knob space, validity-pruned, as a
    list of sparse knob dicts (``None`` entries — engine defaults —
    are dropped; the all-default candidate comes first and IS the
    baseline the tuner must beat).  ``sub_batch`` multipliers resolve
    against ``base_sub_batch`` rounded to a power of two.
    ``spill=True`` (budgeted workloads) adds the tiered-store knobs
    to the product."""
    knobs = tuple(knobs)
    if spill:
        knobs = knobs + SPILL_KNOBS
    out: List[Dict] = []
    for combo in itertools.product(*(k.values for k in knobs)):
        cand: Dict = {}
        for k, v in zip(knobs, combo):
            if v is None:
                continue
            if k.name == "sub_batch":
                g = int(base_sub_batch * v)
                # power-of-two windows keep expand_chunk divisibility
                p = 1
                while p * 2 <= g:
                    p *= 2
                cand[k.name] = max(p, 64)
            else:
                cand[k.name] = v
        if not _valid(model, cand, base_sub_batch):
            continue
        out.append(cand)
        if limit is not None and len(out) >= limit:
            break
    return out


def describe(cand: Dict) -> str:
    """One-line render of a sparse candidate ("defaults" when empty)."""
    if not cand:
        return "defaults"
    return ",".join(f"{k}={v}" for k, v in sorted(cand.items()))
