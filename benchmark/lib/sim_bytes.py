"""The least bytes a simulation step has to move, and the step's share
of the device's memory roofline.

A step of one walker reads the walker's state and writes its successor:
``2 x shapes.state_bytes_unpacked`` bytes, whatever the model's lane
count (the successors a step does not take need never exist).  The
width is read from the configuration's ``shapes``; nothing here knows a
binding.  The share is those bytes over the device seconds of the step's
own scopes (``ptt.sim_expand`` + ``ptt.sim_choose`` + ``ptt.sim_inv``,
``STEP_SCOPES``) against the device's peak bandwidth
(``benchmark/lib/peaks.json``).  No per-layer metric reads it yet (the
manifest is at its ceiling of 128, PERF.md 7): the cell's comparison
prints it from a traced run.
"""

from __future__ import annotations

STEP_SCOPES = ("sim_expand", "sim_choose", "sim_inv")


def least_bytes(config, steps: int) -> int:
    """Bytes ``steps`` walker-steps cannot move less than."""
    return 2 * int(config["shapes"]["state_bytes_unpacked"]) * int(steps)


def step_seconds(scope_s: dict):
    """Device self seconds under the step's scopes, or None where the
    trace holds none of them."""
    found = [scope_s[s] for s in STEP_SCOPES if s in scope_s]
    return sum(found) if found else None


def step_hbm_pct(config, steps: int, scope_s: dict, peaks: dict):
    """The step's share of the memory roofline in percent, or None where
    the trace has no step scope or the device no row of peaks."""
    secs = step_seconds(scope_s)
    if not secs or not peaks.get("hbm_bytes_per_s"):
        return None
    return 100.0 * least_bytes(config, steps) / secs / peaks["hbm_bytes_per_s"]
