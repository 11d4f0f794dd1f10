"""Shared helpers for differential tests: oracle BFS sampling and
counterexample-trace validation."""

import functools
import os
import random
import subprocess

import pytest

from pulsar_tlaplus_tpu.frontend.loader import reference_spec_path
from pulsar_tlaplus_tpu.ref import pyeval as pe

# The reference compaction module: the vendored specs/compaction.tla
# wins; /root/reference/ (the original retrieval mount) is the fallback
# on hosts that still carry it.
REFERENCE_TLA = reference_spec_path("compaction")

# the vendored specs/ directory, resolved from this file (a checkout
# need not sit at any particular path)
SPECS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "specs"
)

@functools.lru_cache(maxsize=1)
def _native_baseline_runnable() -> bool:
    """True when the native baseline checker builds from source here
    (``native.build_baseline()``; no binary is tracked) and the result
    runs a tiny config."""
    from pulsar_tlaplus_tpu import native

    try:
        binary = native.build_baseline()
        p = subprocess.run(
            [binary, "1", "1", "1", "1", "0", "0", "1", "5", "1", "10"],
            capture_output=True, text=True, timeout=60,
        )
    except (OSError, subprocess.SubprocessError):
        return False
    return p.returncode in (0, 1) and bool(p.stdout.strip())


# The native TLC-class baseline (BASELINE.md) needs a C++ toolchain:
# a host without one reports SKIPs, not failures.
needs_native_binary = pytest.mark.skipif(
    not _native_baseline_runnable(),
    reason="native baseline checker cannot be built or run here (no "
    "g++ toolchain)",
)


def assert_valid_counterexample(c, trace, trace_actions, invariant):
    """A counterexample must start at an initial state, follow real
    transitions (named actions must map to the oracle's successors), satisfy
    the invariant at every non-final state, and violate it at the end."""
    assert trace and trace[0] in set(pe.initial_states(c))
    inv = pe.INVARIANTS[invariant]
    for s, act, t in zip(trace, trace_actions, trace[1:]):
        act_name = act if isinstance(act, str) else pe.ACTION_NAMES[act]
        succ = {}
        for a, st in pe.successors(c, s):
            succ.setdefault(pe.ACTION_NAMES[a], []).append(st)
        assert t in succ.get(act_name, []), (act_name, s)
        assert inv(c, s), "only the final state may violate"
    assert not inv(c, trace[-1])


def oracle_sample(c, n_states=150, levels=8, seed=0):
    """A deterministic sample of reachable states, spread across BFS depth."""
    seen = {}
    frontier = []
    for s in pe.initial_states(c):
        if s not in seen:
            seen[s] = None
            frontier.append(s)
    for _ in range(levels):
        new = []
        for s in frontier:
            for _a, t in pe.successors(c, s):
                if t not in seen:
                    seen[t] = None
                    new.append(t)
        if not new:
            break
        frontier = new
    rng = random.Random(seed)
    pool = list(seen)
    return rng.sample(pool, min(n_states, len(pool)))


def tight_hbm_budget(checker_ctor, slack=4096):
    """A budget just above a checker shape's initial-tier minimum —
    tiers pinned at their smallest, so a tiered run MUST spill.
    ``checker_ctor(hbm_budget)`` builds a throwaway probe checker with
    the workload's exact shape knobs; the divisor is the
    engine's ``HBM_HEADROOM``.  One definition so every
    spill drill/test stays in lockstep with the engine's byte
    arithmetic (tests/test_store.py, tests/test_subscription.py,
    tests/_survivable_run.py)."""
    from pulsar_tlaplus_tpu.engine import device_bfs

    probe = checker_ctor("1G")
    return (
        int(
            probe._device_bytes_est(probe.TCAP, probe.LCAP, probe.PCAP)
            / (1.0 - device_bfs.HBM_HEADROOM)
        )
        + slack
    )


# Small configurations exercising distinct semantic corners (cheap enough
# for exhaustive engine-vs-oracle runs on the CPU backend).
SMALL_CONFIGS = {
    "shipped": pe.SHIPPED_CFG,
    "producer_on": pe.Constants(
        message_sent_limit=2,
        compaction_times_limit=2,
        num_keys=1,
        num_values=1,
        max_crash_times=1,
        model_producer=True,
    ),
    "no_retain": pe.Constants(
        message_sent_limit=3,
        compaction_times_limit=2,
        num_keys=2,
        num_values=1,
        retain_null_key=False,
        max_crash_times=1,
    ),
    "two_crashes": pe.Constants(
        message_sent_limit=2,
        compaction_times_limit=3,
        num_keys=1,
        num_values=2,
        max_crash_times=2,
    ),
    "wide_mask": pe.Constants(
        # message positions spill into a second 32-bit mask word only when
        # M > 32; keep a cheap variant that still crosses field boundaries.
        message_sent_limit=4,
        compaction_times_limit=2,
        num_keys=3,
        num_values=1,
        max_crash_times=1,
    ),
}
