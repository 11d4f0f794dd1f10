"""Subprocess driver for crash-resume differential tests.

Runs one checker (device or sharded, CPU backend) on the shipped
compaction config with a checkpoint path, printing a one-line JSON
result on success.  Fault injection rides the PTT_FAULT env var set by
the calling test — ``kill@level:k`` hard-exits 137 mid-run, leaving
only the checkpoint frames behind, which is the whole point.

Not collected by pytest (no ``test_`` prefix); invoked as
``python -m tests._survivable_run`` from the repo root.
"""

import argparse
import json
import os
import sys


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=["device", "sharded", "liveness"],
                    default="device")
    ap.add_argument("--checkpoint", required=True)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--invariant", default=None)
    ap.add_argument("--every", type=int, default=2)
    ap.add_argument("--max-states", type=int, default=200_000_000)
    ap.add_argument("--telemetry", default=None)
    ap.add_argument("--progress", type=float, default=None)
    ap.add_argument("--goal", default="Termination")
    ap.add_argument("--fairness", default="wf_next")
    ap.add_argument("--sweep-chunk", type=int, default=1 << 12)
    ap.add_argument("--frontier-chunk", type=int, default=2048)
    ap.add_argument(
        "--hbm-budget", dest="hbm_budget", default=None,
        help="tiered-store byte budget (device engine; 'min+N' = the "
        "engine's initial-tier minimum plus N bytes, resolved here so "
        "drills stay shape-independent)",
    )
    ap.add_argument("--sub-batch", type=int, default=2048)
    ap.add_argument("--visited-cap", type=int, default=1 << 16)
    ap.add_argument(
        "--config", default="shipped",
        choices=["shipped", "producer_on", "consumer_on"],
        help="shipped = the published 45k oracle; producer_on / "
        "consumer_on = the small liveness oracles (no-lasso / lasso)",
    )
    args = ap.parse_args()

    import jax

    from pulsar_tlaplus_tpu.utils.device import setup_compile_cache

    jax.config.update("jax_platforms", "cpu")
    # share the suite's persistent compile cache (tests/conftest.py,
    # whose compile-time threshold arrives through the environment):
    # drill subprocesses otherwise pay the full cold compile of the
    # engine programs on every single drill
    setup_compile_cache()

    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ref import pyeval as pe

    if args.config == "shipped":
        c = pe.SHIPPED_CFG
    else:
        import dataclasses

        from tests.helpers import SMALL_CONFIGS

        c = SMALL_CONFIGS["producer_on"]
        if args.config == "consumer_on":
            c = dataclasses.replace(c, model_consumer=True)
    m = CompactionModel(c)
    inv = (args.invariant,) if args.invariant else ()
    if args.engine == "liveness":
        from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

        lck = LivenessChecker(
            m, goal=args.goal, fairness=args.fairness,
            frontier_chunk=args.frontier_chunk,
            sweep_chunk=args.sweep_chunk,
            visited_cap=1 << 13,
            checkpoint_path=args.checkpoint,
            checkpoint_every=args.every,
            telemetry=args.telemetry,
            heartbeat_s=args.progress,
        )
        lr = lck.run(resume=args.resume)
        print(
            json.dumps(
                {
                    "holds": lr.holds,
                    "reason": lr.reason,
                    "distinct_states": lr.distinct_states,
                    "truncated": lr.truncated,
                    "stop_reason": lr.stop_reason,
                    "lasso_prefix": lr.lasso_prefix,
                    "lasso_cycle": lr.lasso_cycle,
                }
            )
        )
        return 0
    if args.engine == "device":
        from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

        hbm_budget = args.hbm_budget
        if hbm_budget and hbm_budget.startswith("min+"):
            # resolve "minimum viable + N" against a throwaway probe so
            # the drill pins a TIGHT budget without hard-coding bytes
            # (the shared helpers.tight_hbm_budget recipe)
            from tests.helpers import tight_hbm_budget

            hbm_budget = tight_hbm_budget(
                lambda b: DeviceChecker(
                    m, invariants=inv, sub_batch=args.sub_batch,
                    visited_cap=args.visited_cap,
                    frontier_cap=args.visited_cap // 2,
                    max_states=args.max_states, hbm_budget=b,
                ),
                slack=int(hbm_budget[4:]),
            )
        ck = DeviceChecker(
            m, invariants=inv, sub_batch=args.sub_batch,
            visited_cap=args.visited_cap,
            frontier_cap=args.visited_cap // 2,
            max_states=args.max_states,
            hbm_budget=hbm_budget,
            checkpoint_path=args.checkpoint,
            checkpoint_every=args.every,
            telemetry=args.telemetry,
            heartbeat_s=args.progress,
        )
    else:
        from pulsar_tlaplus_tpu.engine.sharded_device import (
            ShardedDeviceChecker,
        )

        ck = ShardedDeviceChecker(
            m, n_devices=4, invariants=inv, sub_batch=512,
            visited_cap=1 << 13, max_states=args.max_states,
            checkpoint_path=args.checkpoint,
            checkpoint_every=args.every,
            telemetry=args.telemetry,
            heartbeat_s=args.progress,
        )
    r = ck.run(resume=args.resume)
    print(
        json.dumps(
            {
                "distinct_states": r.distinct_states,
                "diameter": r.diameter,
                "level_sizes": r.level_sizes,
                "truncated": r.truncated,
                "stop_reason": r.stop_reason,
                "violation": r.violation,
                "violation_gid": r.violation_gid,
                "trace": (
                    [repr(s) for s in r.trace]
                    if r.trace is not None
                    else None
                ),
                "trace_actions": (
                    list(r.trace_actions)
                    if r.trace_actions is not None
                    else None
                ),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
