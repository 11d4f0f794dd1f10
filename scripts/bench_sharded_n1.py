"""ShardedDeviceChecker at n=1 on the real chip vs the single-chip
engine (VERDICT r3 #4: `-workers N` routes users onto the sharded
engine, so its n=1 throughput must be within ~10% of device_bfs or the
mapping is a perf trap).

Runs the same scaled workload as bench.py with the same budget and
reports states/sec; compare against the device_bfs figure in
BENCH_r04.json / BASELINE.md.

Usage: python scripts/bench_sharded_n1.py [budget_s] [max_states]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import json  # noqa: E402

from pulsar_tlaplus_tpu.utils.device import setup_compile_cache  # noqa: E402

setup_compile_cache()


def main():
    budget_s = float(sys.argv[1]) if len(sys.argv) > 1 else 150.0
    max_states = int(sys.argv[2]) if len(sys.argv) > 2 else 32_000_000
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    c = Constants(
        message_sent_limit=64, compaction_times_limit=3, num_keys=8,
        num_values=2, retain_null_key=True, max_crash_times=3,
        model_producer=True, model_consumer=False,
    )
    print(f"device {jax.devices()[0]}", flush=True)
    model = CompactionModel(c)
    # n=1: routing degenerates to one all_to_all over a singleton mesh
    # plus the bucketing compaction — exactly the overhead the verdict
    # wants priced.  Shapes mirror bench.py (G=2^18, flush_factor=2).
    ck = ShardedDeviceChecker(
        model,
        n_devices=1,
        sub_batch=1 << 18,
        expand_chunk=1 << 13,
        visited_cap=1 << 26,  # presized: a mid-run VCAP growth would
                              # lazy-compile a new flush tier INSIDE the
                              # timed run (the warmup only covers the
                              # initial tier; measured 317s stall)
        max_states=max_states,
        time_budget_s=budget_s,
        progress=True,
        group=2,
        flush_factor=2,
        append_chunk=1 << 17,
    )
    # r5: host-seeded warm start (VERDICT r4 #4) — enumerate the seed
    # first so warmup can precompile the loader at its exact shape
    seed = model.host_seed(max_level_states=800_000, max_total=1_000_000)
    print(f"seed prefix: {len(seed[0])} states", flush=True)
    compile_s = ck.warmup(seed_states=len(seed[0]))
    print(f"warmup: {compile_s:.1f}s  {ck.last_stats}", flush=True)
    t0 = time.time()
    r = ck.run(seed=seed)
    wall = time.time() - t0
    print(
        json.dumps(
            {
                "engine": "sharded_device n=1 (r5 producer-local rows + host seed)",
                "states_per_sec": round(r.states_per_sec, 1),
                "distinct_states": r.distinct_states,
                "levels": r.diameter,
                "truncated": r.truncated,
                "wall_s_incl_compile": round(wall, 1),
                "run_wall_s": round(r.wall_s, 1),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
