"""wf_next Termination verdict at >=5M states on the real chip
(VERDICT r3 #5 "done" criterion: a multi-million-state liveness run in
minutes, not a toy).

Config: compaction with MessageSentLimit=4, |Keys|=2, |Vals|=2,
CompactionTimesLimit=3, MaxCrashTimes=2, producer modeled —
9,445,152 reachable states / 24 levels (counted by the native C++
baseline checker, which this script cross-checks against).

Pipeline timed separately: device BFS exploration, device edge sweep
(key->gid merge-join per chunk; only int32 dst lanes reach the host),
host vectorized graph analysis.

Round-5 tiers: ``--tier 9m`` (default; 9,445,152 states) and
``--tier 25m`` (MSL=4, |K|=3, |V|=2, CTL=3, MCT=2 — 29,379,399 states /
24 levels, counted complete by the native checker), the VERDICT r4 #6
"done" criterion (>=25M states, <10 min, sweep <40% of total).

Usage: python scripts/liveness_scale.py [frontier_chunk_log2] [--tier 25m]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from pulsar_tlaplus_tpu.utils.device import setup_compile_cache  # noqa: E402

setup_compile_cache()


def main():
    argv = sys.argv[1:]
    tier = "9m"
    args = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--tier":
            tier = argv[i + 1]
            i += 2
        elif a.startswith("--tier="):
            tier = a.split("=", 1)[1]
            i += 1
        else:
            args.append(a)
            i += 1
    if tier not in ("9m", "25m"):
        raise SystemExit(f"unknown tier {tier!r} (9m|25m)")
    f_log2 = int(args[0]) if args else 16
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    # the tiers differ ONLY in |KeySpace|; both are native-verified
    # complete state counts
    c = Constants(
        message_sent_limit=4, compaction_times_limit=3,
        num_keys=3 if tier == "25m" else 2,
        num_values=2, retain_null_key=True, max_crash_times=2,
        model_producer=True, model_consumer=False,
    )
    want_n, cap_states = (
        (29_379_399, 36_000_000) if tier == "25m"
        else (9_445_152, 12_000_000)
    )
    print(f"device {jax.devices()[0]}", flush=True)
    model = CompactionModel(c)
    print(
        f"state {model.layout.total_bits} bits ({model.layout.W} words), "
        f"{model.A} lanes",
        flush=True,
    )
    lc = LivenessChecker(
        model,
        goal="Termination",
        fairness="wf_next",
        frontier_chunk=1 << f_log2,
        visited_cap=1 << 24,
        max_states=cap_states,
        # sweep cost ~ (n/SF) * (n + SF*A) * passes: bigger chunks
        # amortize the full-table join until SF*A approaches n
        sweep_chunk=1 << 19,
        # bench-class explorer shapes (the r3-era 1-round accumulator
        # paid a full visited sort per ~1M lanes); expand_chunk must
        # divide sub_batch, so clamp it for small frontier_chunk args
        explorer_kw=dict(
            flush_factor=3,
            expand_chunk=min(1 << 13, max(256, 1 << f_log2)),
        ),
    )
    t0 = time.time()
    n, n_init = lc._explore()
    t_explore = time.time() - t0
    print(f"explored {n} states in {t_explore:.1f}s", flush=True)
    assert n == want_n, n  # native baseline cross-check
    t0 = time.time()
    src, dst, out_deg = lc._edges(n)
    t_edges = time.time() - t0
    print(
        f"edge sweep: {len(src)} <Next>_vars edges in {t_edges:.1f}s",
        flush=True,
    )
    t0 = time.time()
    res = lc.run()
    t_verdict = time.time() - t0
    print(
        f"wf_next Termination at {res.distinct_states} states: "
        f"holds={res.holds} ({res.reason}) — analysis {t_verdict:.1f}s",
        flush=True,
    )
    if res.lasso_cycle:
        print(
            f"  lasso: prefix len {len(res.lasso_prefix or [])}, "
            f"cycle len {len(res.lasso_cycle)}",
            flush=True,
        )
    total = t_explore + t_edges + t_verdict
    print(f"total {total:.1f}s (explore+sweep+analysis)", flush=True)


if __name__ == "__main__":
    main()
