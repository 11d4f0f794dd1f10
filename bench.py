"""Headline benchmark: distinct states/sec on the scaled compaction model.

Workload (BASELINE.md north star): ``compaction.tla`` scaled to
``|KeySpace|=8, MessageSentLimit=64`` with the producer modeled — the deep
BFS stress configuration.  The state space is astronomically large, so the
run is HBM-capacity-bounded: BFS proceeds level by level on the real chip
and the metric is sustained distinct-states/sec (discovery + dedup +
invariant checking all included).

Engine: the device-resident checker (engine/device_bfs.py) — everything
(visited set, frontier, trace log) stays in HBM; the host fetches one
small stats vector per group of sub-batches, so no per-chunk host
round trip sits on the hot path.

Baselines (BASELINE.md; the image has no JVM, so 8-worker CPU TLC — the
north-star comparison — cannot run here):

- ``native_baseline``: the tuned native C++ BFS checker of the same spec
  (native/compaction_bfs.cpp), ONE core — the TLC-class stand-in.
- ``native_8thr``: the same binary at threads=8, measured for the
  record.  The image exposes ONE CPU core (os.cpu_count() == 1), so
  this CANNOT show real 8-worker scaling; the honest 8-worker stand-in
  is the linear extrapolation ``8 x native_baseline`` (optimistic for
  the CPU — real TLC worker scaling is sublinear), reported as
  ``native_8w_extrapolated``.  ``vs_baseline`` is measured against THAT
  number: the toughest honest comparison available in-image.
- ``python_oracle``: the pure-Python reference evaluator, timed over a
  BFS slice reaching the deep-level regime.

Prints exactly ONE JSON line on stdout.
"""

import argparse
import json
import os
import re
import sys
import time

from pulsar_tlaplus_tpu.obs.telemetry import IMPL_FIELDS

BENCH_BUDGET_S = 150.0
BASELINE_SLICE_S = 30.0
# sentinel: resolved after parse to
# <--telemetry-path>/bench_telemetry_<pid>.jsonl
_DEFAULT_TELEMETRY = "__per_process__"
# Round 5 broke the HBM wall with the frontier-window row store; round
# 6 retires the flush sort, and with it the 150M cap that nulled the
# canonical sustained-60s metric (VERDICT r5: the bench's own cap
# truncated the run before the window existed).  230M states fit the
# fpset layout: 2^29-slot table (2 x u32 cols, 4.3 GB at load <= 1/2)
# + parent/lane logs (~2.1 GB) + 20M-state row window (1.6 GB) +
# accumulator (~2.4 GB) + append-sort transients (~1.3 GB) ~= 11.7 GB
# of the 15.75 GB chip — and 230M is past any plausible 60 s of
# sustained discovery (3.5M st/s x 60 s = 210M).  ``--max-states``
# overrides it without editing this file.
MAX_STATES = 230_000_000



def scaled_config():
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    return Constants(
        message_sent_limit=64,
        compaction_times_limit=3,
        num_keys=8,
        num_values=2,
        retain_null_key=True,
        max_crash_times=3,
        model_producer=True,
        model_consumer=False,
    )


# The checker tier the bench runs at — exported so chip_smoke.py and
# the profilers (scripts/profile.py stages --run) compile EXACTLY the
# programs the bench loads (the tier shapes the lowered HLO and thus
# the compile-cache key).
BENCH_CHECKER_KW = dict(
    sub_batch=1 << 18,          # 262144 states -> 8.9M candidate lanes
    expand_chunk=1 << 13,
    visited_cap=1 << 26,        # tiered: the table starts at 2^27
                                # slots and doubles as the run grows
    max_states=MAX_STATES,
    group=2,
    flush_factor=3,             # 26.7M-lane accumulator: ~1/3 fewer
                                # full-width flushes than r4's ff=2
    seed_cap=1 << 21,
    rows_window="frontier",
    row_cap_states=20_000_000,  # >= the deepest completable frontier
                                # (level 6: 17.2M); level 7's rows are
                                # kept until the window fills, then
                                # dropped — it can never complete at any
                                # feasible HBM (>=210.4M states, native
                                # ground truth)
)


def measure_native_baseline(c, threads: int):
    """The TLC-class stand-in: the native C++ BFS checker of the same
    spec (native/compaction_bfs.cpp), same workload, measured fresh
    each bench run.  Returns its JSON result dict."""
    from pulsar_tlaplus_tpu import native

    return native.run_baseline(
        c.message_sent_limit, c.num_keys, c.num_values,
        c.compaction_times_limit, c.max_crash_times, c.model_producer,
        c.retain_null_key, budget_s=75.0, threads=threads,
    )


def measure_python_baseline(c, budget_s: float):
    """Timed BFS slice of the reference evaluator; returns
    (states/sec, levels reached).  The whole slice is timed — including
    the deep levels where per-state cost peaks — so the figure is the
    amortized full-depth rate, not an early-level burst."""
    from pulsar_tlaplus_tpu.ref import pyeval as pe

    t0 = time.time()
    seen = set()
    frontier = []
    for s in pe.initial_states(c):
        seen.add(s)
        frontier.append(s)
    invs = [pe.INVARIANTS[n] for n in pe.DEFAULT_INVARIANTS]
    levels = 1
    cut = False
    while frontier and not cut:
        new = []
        for s in frontier:
            for _a, t in pe.successors(c, s):
                if t not in seen:
                    seen.add(t)
                    new.append(t)
                    for fn in invs:
                        fn(c, t)
            if time.time() - t0 > budget_s:
                cut = True
                break
        frontier = new
        if not cut:
            levels += 1  # only fully expanded levels count as reached
    return len(seen) / max(time.time() - t0, 1e-9), levels


def cleanup_stale_streams(dir_path: str) -> int:
    """Remove ``bench_telemetry_<pid>.jsonl`` streams whose pid is no
    longer alive (default-on telemetry otherwise leaks one file per
    bench run forever).  A pid we cannot signal but that exists
    (EPERM) is treated as alive; our own stream is never touched.
    Returns the number of files removed."""
    removed = 0
    try:
        names = os.listdir(dir_path)
    except OSError:
        return 0
    for name in names:
        m = re.fullmatch(r"bench_telemetry_(\d+)\.jsonl", name)
        if not m:
            continue
        pid = int(m.group(1))
        if pid == os.getpid():
            continue
        try:
            os.kill(pid, 0)
            continue  # alive: its stream is in use
        except ProcessLookupError:
            pass  # dead: the stream is stale
        except (PermissionError, OSError):
            continue  # exists (or unknowable): leave it alone
        try:
            os.remove(os.path.join(dir_path, name))
            removed += 1
        except OSError:
            pass
    return removed


def telemetry_level_records(events):
    """(wall_s, distinct_states) progress records of the LAST run among
    parsed telemetry ``events`` — the round-10 source of truth for the
    sustained rates (the stream exists on every bench run now that
    --telemetry defaults on; the per-level metrics JSONL remains the
    fallback)."""
    runs = [e.get("run_id") for e in events if e.get("event") == "level"]
    if not runs:
        return []
    last_run = runs[-1]
    return [
        {
            "wall_s": e["wall_s"],
            "distinct_states": e["distinct_states"],
        }
        for e in events
        if e.get("event") == "level"
        and e.get("run_id") == last_run
        and "wall_s" in e
        and "distinct_states" in e
    ]


def sustained_rates(recs, wall_s):
    """(last_level_sps, final_60s_sps or None) from progress records
    (telemetry ``level`` events, or the legacy per-level metrics
    JSONL): the last level's incremental rate is the deep-regime
    sustained figure (VERDICT r3 #3); the final-60s figure is measured
    over a GENUINE trailing >= 60 s window anchored in the records —
    a 60-70 s run whose records cannot span one reports None instead
    of relabeling the whole run (VERDICT r5 weak #2)."""
    if len(recs) < 2:
        return None, None
    # trailing records can repeat the final state count (e.g. the
    # level-boundary record after the stopping fetch) — the last-level
    # rate is measured over the last record pair with a real increase
    last = recs[-1]
    prev = None
    for r in reversed(recs[:-1]):
        if r["distinct_states"] < last["distinct_states"]:
            prev = r
            break
        last = r
    dt = last["wall_s"] - prev["wall_s"] if prev is not None else 0
    last_level = (
        (last["distinct_states"] - prev["distinct_states"]) / dt
        if prev is not None and dt > 0
        else None
    )
    last = recs[-1]
    final60 = None
    if wall_s >= 60.0:
        cut = last["wall_s"] - 60.0
        # last record AT OR BEFORE the cut, so the window is >= 60 s
        # (picking the first record after it could shrink the window
        # to a single level and mislabel a burst as "final 60s")
        base = recs[0]
        for r in recs:
            if r["wall_s"] <= cut:
                base = r
            else:
                break
        if last["wall_s"] - base["wall_s"] >= 60.0:
            final60 = (
                last["distinct_states"] - base["distinct_states"]
            ) / (last["wall_s"] - base["wall_s"])
        # no >= 60 s record window -> None.  (The pre-r10 fallback
        # counted a whole 60-70 s run as "the final 60 s", which
        # relabeled the warm-up-inclusive average as a sustained
        # figure — VERDICT r5 weak #2.)
    return last_level, final60


def load_metrics_records(metrics_path):
    """Legacy per-level metrics JSONL -> progress records (fallback
    when no telemetry stream exists)."""
    recs = []
    try:
        with open(metrics_path) as f:
            for line in f:
                recs.append(json.loads(line))
    except OSError:
        return []
    return recs


def artifact_skeleton() -> dict:
    """Every bench_schema-12 required key, None-filled — the
    simulate, matrix, and fleet paths fill what applies and stay
    validator-clean (scripts/check_telemetry_schema.py
    BENCH_KEYS_V12: keys are REQUIRED, values may be null where the
    mode has no measurement)."""
    keys = (
        "metric", "value", "unit", "vs_baseline",
        "vs_baseline_definition", "distinct_states", "levels",
        "compile_warmup_s", "stop_reason", "truncated",
        "hbm_recovered", "ckpt_frames", "ckpt_bytes", "ckpt_write_s",
        "ckpt_retries", "fpset_flushes", "fpset_probe_rounds",
        "fpset_avg_probe_rounds", "fpset_failures", "fpset_occupancy",
        "fpset_valid_lanes", "fpset_max_probe_rounds", "visited_impl",
        "max_states", "stats_fetches", "compact_impl", "fuse",
        "dispatches_per_level", "work_expand_rows", "work_probe_lanes",
        "work_compact_elems", "work_append_rows", "work_groups",
        "hbm_budget", "spill_bytes_per_state", "spill_overlap_ratio",
        "walks_per_sec", "steps_per_state",
        # fleet keys (r20, bench_schema 10): null on non-fleet runs
        "fleet_backends", "fleet_jobs_per_sec", "fleet_route_ms",
        "fleet_replicated_wire_bytes",
        # fleet survivability latencies (r21, bench_schema 11): null
        # on non-fleet runs and on drills that saw no drain/rejoin
        "fleet_failover_ms", "fleet_reconcile_ms",
        # bench_schema 12 (r23): the kernel fields (constants now:
        # obs/telemetry.IMPL_FIELDS) + the flush-stage throughput
        "probe_impl", "expand_impl", "sieve_impl",
        "probe_lanes_per_sec",
    )
    d = {k: None for k in keys}
    d["bench_schema"] = 12
    return d


# ---------------------------------------------------------- simulate

# the simulation bench shape: wide enough to keep the device busy,
# shallow enough that a CPU-mesh differential finishes in seconds
SIM_BENCH_KW = dict(n_walkers=4096, depth=64)


def run_sim_bench(args) -> None:
    """``--mode simulate``: the streaming walker swarm on the scaled
    compaction config under the time budget; one bench_schema-9 JSON
    line (walks_per_sec / steps_per_state are the headline keys the
    ledger gates — docs/simulation.md)."""
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.sim.engine import StreamingSimulator

    c = scaled_config()
    model = CompactionModel(c)
    cleanup_stale_streams(args.telemetry_path)
    if args.telemetry == _DEFAULT_TELEMETRY:
        args.telemetry = os.path.join(
            args.telemetry_path,
            f"bench_telemetry_{os.getpid()}.jsonl",
        )
        try:
            os.remove(args.telemetry)
        except OSError:
            pass
    sim = StreamingSimulator(
        model,
        n_walkers=args.walkers or SIM_BENCH_KW["n_walkers"],
        depth=args.depth or SIM_BENCH_KW["depth"],
        segment_len=args.segment,
        seed=args.sim_seed,
        max_steps=args.sim_steps,
        time_budget_s=None if args.sim_steps else args.budget_s,
        telemetry=args.telemetry,
        heartbeat_s=args.progress_every,
        progress=True,
        checkpoint_path=args.checkpoint,
    )
    compile_s = sim.warmup()
    print(f"compile warmup: {compile_s:.1f}s", file=sys.stderr)
    r = sim.run(resume=args.recover)
    print(
        f"sim: {r.steps} steps / {r.states_visited} states / "
        f"{r.walks} walks in {r.wall_s:.1f}s "
        f"({r.steps_per_sec:.0f} steps/s, {r.walks_per_sec:.1f} "
        f"walks/s)",
        file=sys.stderr,
    )
    d = artifact_skeleton()
    d.update(
        metric="simulation steps/sec on scaled compaction.tla "
        "(|Keys|=8, |Msgs|=64, producer modeled; streaming walker "
        "swarm, TypeSafe + CompactionHorizonCorrectness checked "
        "every step)",
        value=round(r.steps_per_sec, 1),
        unit="sim steps/sec/chip",
        vs_baseline_definition="none (simulation has no native "
        "baseline; walks_per_sec is the headline)",
        mode="simulate",
        engine="sim r18 (streaming walker swarm: segmented lax.scan "
        "rollouts, functional PRNG, in-kernel counters, sampled-"
        "duplicate estimator)",
        compile_warmup_s=round(compile_s, 1),
        stop_reason=r.stop_reason,
        truncated=r.truncated,
        telemetry=args.telemetry,
        checkpoint=args.checkpoint,
        walks_per_sec=r.walks_per_sec,
        steps_per_state=(
            round(r.steps / r.states_visited, 4)
            if r.states_visited
            else None
        ),
        steps_per_sec=r.steps_per_sec,
        states_per_sec=r.states_per_sec,
        sim_walkers=r.n_walkers,
        sim_depth=r.depth,
        sim_seed=args.sim_seed,
        sim_steps=r.steps,
        sim_states=r.states_visited,
        sim_walks=r.walks,
        sim_segments=r.segments,
        sim_violations=sim.last_stats.get("sim_violations"),
        sim_dup_ratio_est=r.dup_ratio_est,
        stats_fetches=sim.last_stats.get("stats_fetches"),
        ckpt_frames=sim.last_stats.get("ckpt_frames"),
        ckpt_bytes=sim.last_stats.get("ckpt_bytes"),
        ckpt_write_s=sim.last_stats.get("ckpt_write_s"),
        ckpt_retries=sim.last_stats.get("ckpt_retries"),
    )
    print(json.dumps(d))


# ------------------------------------------------------------- matrix

# Declared constant-scaling axes per registry spec (ISSUE 14 satellite:
# |Keys|, |Msgs|, EntryLimit, broker/cluster counts) at shapes small
# enough that every point exhausts on the CPU mesh in seconds.  Each
# point is one ledger-ingestable bench_schema-9 artifact; `cli.py
# ledger compare` renders the scaling table between any two points.
def matrix_axes():
    from pulsar_tlaplus_tpu.models.bookkeeper import BookkeeperConstants
    from pulsar_tlaplus_tpu.models.georeplication import GeoConstants
    from pulsar_tlaplus_tpu.models.subscription import (
        SubscriptionConstants,
    )
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    compaction_base = Constants(
        message_sent_limit=3, compaction_times_limit=2, num_keys=2,
        num_values=1, max_crash_times=1,
    )
    return {
        "compaction": (
            compaction_base,
            (
                ("num_keys", (1, 2, 3)),
                ("message_sent_limit", (2, 3, 4)),
            ),
        ),
        "bookkeeper": (
            BookkeeperConstants(),
            (
                ("entry_limit", (1, 2, 3)),
                ("num_bookies", (3, 4)),
            ),
        ),
        "georeplication": (
            GeoConstants(
                num_clusters=2, publish_limit=2,
                max_replicator_crashes=1,
            ),
            (
                ("num_clusters", (2, 3)),
                ("publish_limit", (1, 2)),
            ),
        ),
        "subscription": (
            SubscriptionConstants(message_limit=2, max_crash_times=1),
            (
                ("message_limit", (1, 2, 3)),
            ),
        ),
    }


def _matrix_model(spec: str, constants):
    from pulsar_tlaplus_tpu.models import bookkeeper as bk
    from pulsar_tlaplus_tpu.models import georeplication as geo
    from pulsar_tlaplus_tpu.models import subscription as subm
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel

    return {
        "compaction": CompactionModel,
        "bookkeeper": bk.BookkeeperModel,
        "georeplication": geo.GeoreplicationModel,
        "subscription": subm.SubscriptionModel,
    }[spec](constants)


def run_matrix(args) -> None:
    """``--matrix``: sweep the declared constant axes, one exhaustive
    device-engine run + one bench_schema-9 artifact per point, all
    ingested into ``--matrix-ledger`` when given.  Prints one JSON
    summary line."""
    import dataclasses

    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

    out_dir = args.matrix_out
    os.makedirs(out_dir, exist_ok=True)
    axes = matrix_axes()
    specs = args.matrix_spec or sorted(axes)
    points = []
    for spec in specs:
        if spec not in axes:
            sys.exit(
                f"bench: unknown --matrix-spec {spec!r} "
                f"(known: {sorted(axes)})"
            )
        base, spec_axes = axes[spec]
        for axis, values in spec_axes:
            for v in values:
                if args.matrix_limit and len(points) >= args.matrix_limit:
                    break
                points.append((spec, base, axis, v))
    results = []
    for spec, base, axis, v in points:
        constants = dataclasses.replace(base, **{axis: v})
        try:
            constants.validate()
        except (AttributeError, ValueError):
            pass  # models re-validate at construction
        try:
            model = _matrix_model(spec, constants)
        except ValueError as e:
            print(
                f"matrix: {spec} {axis}={v}: invalid binding ({e}); "
                "skipped", file=sys.stderr,
            )
            continue
        t0 = time.time()
        ck = DeviceChecker(
            model, sub_batch=256, visited_cap=1 << 13,
            frontier_cap=1 << 11, max_states=args.max_states,
        )
        r = ck.run()
        wall = time.time() - t0
        d = artifact_skeleton()
        d.update(
            metric=f"constant-scaling matrix point: {spec} {axis}={v} "
            "(exhaustive device BFS)",
            value=round(r.states_per_sec, 1),
            unit="states/sec/chip",
            mode="check",
            vs_baseline_definition="none (matrix point)",
            engine="device_bfs (matrix point)",
            visited_impl=IMPL_FIELDS["visited_impl"],
            compact_impl=IMPL_FIELDS["compact_impl"],
            fuse=ck.fuse,
            matrix_spec=spec,
            matrix_axis=axis,
            matrix_value=v,
            config_sig=repr(constants),
            distinct_states=r.distinct_states,
            levels=r.diameter,
            compile_warmup_s=0.0,
            stop_reason=r.stop_reason,
            truncated=r.truncated,
            hbm_recovered=getattr(r, "hbm_recovered", 0),
            max_states=args.max_states,
            wall_s=round(wall, 2),
            states_per_sec=round(r.states_per_sec, 1),
        )
        name = f"BENCH_matrix_{spec}_{axis}_{v}.json"
        path = os.path.join(out_dir, name)
        with open(path, "w") as f:
            json.dump(d, f)
            f.write("\n")
        print(
            f"matrix: {spec} {axis}={v}: {r.distinct_states} states, "
            f"diam {r.diameter}, {r.states_per_sec:.0f} st/s -> {path}",
            file=sys.stderr,
        )
        results.append(
            {
                "spec": spec, "axis": axis, "value": v,
                "distinct_states": r.distinct_states,
                "diameter": r.diameter,
                "states_per_sec": round(r.states_per_sec, 1),
                "artifact": path,
            }
        )
    if args.matrix_ledger:
        from pulsar_tlaplus_tpu.obs import ledger

        recs = [
            ledger.record_from_file(p["artifact"]) for p in results
        ]
        added = ledger.append(args.matrix_ledger, recs)
        print(
            f"matrix: ingested {added} point(s) into "
            f"{args.matrix_ledger}",
            file=sys.stderr,
        )
    print(json.dumps({"matrix": results, "bench_schema": 12}))


# -------------------------------------------------------------- fleet

# the fleet bench workload: the small compaction binding (1,654
# states) at the service-test geometry — small enough that an N-way
# batch exhausts on the CPU mesh in seconds, real enough that the
# dispatcher's routing, stickiness, and replication all fire
FLEET_BENCH_CFG = """
CONSTANTS
    MessageSentLimit = 2
    CompactionTimesLimit = 2
    ModelConsumer = FALSE
    ConsumeTimesLimit = 2
    KeySpace = {1}
    ValueSpace = {1}
    RetainNullKey = TRUE
    MaxCrashTimes = 1
    ModelProducer = TRUE
SPECIFICATION Spec
INVARIANTS
"""

FLEET_BENCH_GEOM = dict(
    sub_batch=64,
    visited_cap=1 << 10,
    frontier_cap=1 << 8,
    max_states=1 << 20,
    checkpoint_every=1,
)


def run_fleet_bench(args) -> None:
    """``--fleet N``: spin N local ``serve`` backends plus one
    dispatcher in-process (unix sockets under a scratch dir), push a
    replication probe and a mixed batch through the single endpoint,
    and emit ONE bench_schema-12 JSON line with the fleet keys —
    queue throughput (fleet_jobs_per_sec), mean route latency
    (fleet_route_ms), sieve replication economy
    (fleet_replicated_wire_bytes), and the r21 survivability
    latencies (fleet_failover_ms / fleet_reconcile_ms, null when the
    run saw no drain or rejoin) — ingestible by ``cli.py ledger
    add`` and gateable by ``ledger gate`` (docs/fleet.md)."""
    import shutil
    import tempfile

    from pulsar_tlaplus_tpu.fleet.dispatcher import (
        FleetConfig,
        FleetDispatcher,
    )
    from pulsar_tlaplus_tpu.service.client import ServiceClient
    from pulsar_tlaplus_tpu.service.scheduler import (
        CheckerPool,
        ServiceConfig,
    )
    from pulsar_tlaplus_tpu.service.server import ServiceDaemon

    n = int(args.fleet)
    if n < 1:
        sys.exit("bench: --fleet needs N >= 1 backends")
    root = tempfile.mkdtemp(prefix="ptt_fleet_bench_")
    cfg_path = os.path.join(root, "small_compaction.cfg")
    with open(cfg_path, "w") as f:
        f.write(FLEET_BENCH_CFG)
    daemons, disp = [], None
    try:
        configs = [
            ServiceConfig(
                state_dir=os.path.join(root, f"b{i}"),
                slice_s=0.3,
                **FLEET_BENCH_GEOM,
            )
            for i in range(n)
        ]
        # prewarm every backend OUTSIDE the timed window: the bench
        # measures the fleet's routing + queue economy, not N cold
        # compiles of the same program
        t_compile = time.time()
        for i, c in enumerate(configs):
            pool = CheckerPool(c)
            pool.warm("compaction", cfg_path)
            daemons.append(ServiceDaemon(c, pool=pool))
            daemons[-1].start()
            print(
                f"fleet bench: backend {i} warmed "
                f"({time.time() - t_compile:.1f}s cumulative)",
                file=sys.stderr,
            )
        compile_s = time.time() - t_compile
        disp = FleetDispatcher(FleetConfig(
            state_dir=os.path.join(root, "dispatch"),
            backends=tuple(c.socket_path for c in configs),
            health_interval_s=0.2,
            sticky_s=0.0,  # load shape: spread by live signal
        ))
        disp.start()
        cl = ServiceClient(disp.config.socket_path, timeout=240.0)

        # replication probe: a truncated run's artifact must cross
        # the fleet (the wire-byte economy the artifact records)
        repl_bytes = 0
        if n > 1:
            probe = cl.submit(
                "compaction", cfg_path, invariants=[],
                max_states=600, submit_id="fleet-bench-probe",
            )
            cl.wait(probe, timeout=float(args.budget_s) * 10 + 300)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                snap = disp.metrics_snapshot()
                repl_bytes = int(sum(snap["repl_bytes"].values()))
                if repl_bytes:
                    break
                time.sleep(0.1)
            print(
                f"fleet bench: replication probe shipped "
                f"{repl_bytes} wire bytes",
                file=sys.stderr,
            )

        # the timed batch: 2 jobs per backend through ONE endpoint
        n_jobs = 2 * n
        t0 = time.monotonic()
        jids = [
            cl.submit("compaction", cfg_path, invariants=[])
            for _ in range(n_jobs)
        ]
        states = None
        for jid in jids:
            r = cl.wait(jid, timeout=float(args.budget_s) * 10 + 600)
            if r["state"] != "done" or r["result"]["status"] not in (
                "ok", "violation"
            ):
                sys.exit(
                    f"bench: fleet job {jid} ended "
                    f"{r['state']}/{(r.get('result') or {}).get('status')}"
                )
            states = r["result"]["distinct_states"]
        elapsed = time.monotonic() - t0
        snap = disp.metrics_snapshot()
        routes = sum(snap["routes"].values())
        route_ms = 1e3 * float(snap["route_s"]) / max(routes, 1)
        jobs_per_sec = n_jobs / max(elapsed, 1e-9)
        print(
            f"fleet bench: {n_jobs} jobs over {n} backend(s) in "
            f"{elapsed:.1f}s ({jobs_per_sec:.2f} jobs/s, "
            f"{route_ms:.1f} ms/route)",
            file=sys.stderr,
        )
    finally:
        if disp is not None:
            disp.shutdown()
        for d in daemons:
            d.shutdown()
        shutil.rmtree(root, ignore_errors=True)

    d = artifact_skeleton()
    d.update(
        metric=f"fleet queue throughput: {n_jobs} small-compaction "
        f"jobs through one dispatcher over {n} backend(s) "
        "(routing + slicing + warm replication included)",
        value=round(jobs_per_sec, 3),
        unit="jobs/sec",
        mode="fleet",
        engine="fleet r20 (dispatcher + N serve backends, unix "
        "sockets, sieve replication)",
        vs_baseline_definition="none (fleet has no native baseline; "
        "fleet_jobs_per_sec is the headline)",
        compile_warmup_s=round(compile_s, 1),
        stop_reason="done",
        truncated=False,
        distinct_states=states,
        max_states=FLEET_BENCH_GEOM["max_states"],
        fleet_backends=n,
        fleet_jobs_per_sec=round(jobs_per_sec, 3),
        fleet_route_ms=round(route_ms, 3),
        fleet_replicated_wire_bytes=repl_bytes,
        fleet_failover_ms=(
            round(1e3 * float(snap["failover_s"]) / snap["failover_n"], 3)
            if snap.get("failover_n") else None
        ),
        fleet_reconcile_ms=(
            round(1e3 * float(snap["reconcile_s"]) / snap["reconcile_n"], 3)
            if snap.get("reconcile_n") else None
        ),
    )
    print(json.dumps(d))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="headline bench: distinct states/sec on the scaled "
        "compaction model (one JSON line on stdout)"
    )
    ap.add_argument(
        "--mode", choices=["check", "simulate"], default="check",
        help="workload: 'check' (exhaustive BFS, the headline bench) "
        "or 'simulate' (the streaming walker swarm — walks/s + "
        "steps/s under the time budget; docs/simulation.md)",
    )
    ap.add_argument(
        "--walkers", type=int, default=None,
        help="with --mode simulate: walker swarm width (default 4096)",
    )
    ap.add_argument(
        "--depth", type=int, default=None,
        help="with --mode simulate: steps per behavior (default 64)",
    )
    ap.add_argument(
        "--segment", type=int, default=None,
        help="with --mode simulate: steps per dispatch",
    )
    ap.add_argument(
        "--sim-seed", dest="sim_seed", type=int, default=0,
        help="with --mode simulate: PRNG seed",
    )
    ap.add_argument(
        "--sim-steps", dest="sim_steps", type=int, default=None,
        help="with --mode simulate: total step budget (overrides the "
        "time budget — the deterministic bench shape)",
    )
    ap.add_argument(
        "--fleet", type=int, default=None, metavar="N",
        help="fleet bench: spin N local serve backends + one "
        "dispatcher in-process and measure queue throughput / route "
        "latency / replication wire bytes through the single "
        "endpoint (bench_schema-12 fleet_* keys; docs/fleet.md)",
    )
    ap.add_argument(
        "--matrix", action="store_true",
        help="constant-scaling bench matrix: sweep the declared "
        "constant axes (|Keys|, |Msgs|, EntryLimit, broker counts) "
        "at small shapes, one ledger-ingestable artifact per point",
    )
    ap.add_argument(
        "--matrix-out", default="bench_matrix", metavar="DIR",
        help="with --matrix: artifact output directory",
    )
    ap.add_argument(
        "--matrix-spec", action="append", default=None,
        help="with --matrix: restrict to this spec (repeatable; "
        "default: all four registry specs)",
    )
    ap.add_argument(
        "--matrix-limit", type=int, default=None, metavar="N",
        help="with --matrix: cap the number of points (smoke runs)",
    )
    ap.add_argument(
        "--matrix-ledger", default=None, metavar="FILE",
        help="with --matrix: ingest every point into this ledger",
    )
    ap.add_argument(
        "--max-states", type=int, default=MAX_STATES,
        help="state cap (default past the sustained-60s mark so the "
        "canonical window is never nulled by the bench's own cap)",
    )
    ap.add_argument(
        "--budget-s", type=float, default=BENCH_BUDGET_S,
        help="device-run time budget in seconds",
    )
    ap.add_argument(
        "--fuse", choices=["level", "stage"], default="level",
        help="dispatch fusion: level (one fused megakernel dispatch "
        "per BFS level, ramp levels batched — default) or stage (the "
        "per-stage dispatch chain)",
    )
    ap.add_argument(
        "--fuse-group", dest="fuse_group", type=int, default=None,
        help="with --fuse level: max ramp levels batched per dispatch "
        "(default auto, up to 8; 1 disables batching)",
    )
    ap.add_argument(
        "--checkpoint", default=None,
        help="write level-boundary checkpoint frames to this .npz "
        "(survivable bench runs: SIGTERM/SIGINT exit resumably, HBM "
        "exhaustion recovers from the last frame instead of "
        "truncating)",
    )
    ap.add_argument(
        "--checkpoint-every", type=int, default=2,
        help="levels between checkpoint frames (with --checkpoint)",
    )
    ap.add_argument(
        "--recover", action="store_true",
        help="resume the device run from --checkpoint instead of "
        "starting fresh (skips the host seed)",
    )
    ap.add_argument(
        "--telemetry",
        default=_DEFAULT_TELEMETRY,
        metavar="FILE",
        help="write the structured run-event JSONL stream here "
        "(docs/observability.md; DEFAULT ON since round 10 — the "
        "artifact's per-stage/fpset/ckpt keys are derived from this "
        "stream via the scripts/telemetry_report.py --bench-keys "
        "layer; the default is bench_telemetry_<pid>.jsonl under "
        "--telemetry-path, per-process so concurrent benches never "
        "share a stream file); --no-telemetry disables",
    )
    ap.add_argument(
        "--no-telemetry", dest="telemetry",
        action="store_const", const=None,
        help="disable the telemetry stream",
    )
    ap.add_argument(
        "--telemetry-path", default="/tmp", metavar="DIR",
        help="directory for the default per-process telemetry stream "
        "(default /tmp).  Stale bench_telemetry_<pid>.jsonl files "
        "whose pid is dead are removed here at startup — default-on "
        "telemetry must not leak one file per bench run forever",
    )
    ap.add_argument(
        "--hbm-budget", dest="hbm_budget", default=None,
        metavar="BYTES",
        help="device-memory byte budget for the tiered state store "
        "(e.g. 7.5G; PTT_HBM_BUDGET works too): visited keys and "
        "aged rows/logs spill to host tiers past it — the artifact "
        "then carries spill_bytes_per_state/spill_overlap_ratio "
        "(docs/memory.md)",
    )
    ap.add_argument(
        "--no-spill-compress", dest="no_spill_compress",
        action="store_true",
        help="spill raw planes instead of delta+zlib",
    )
    ap.add_argument(
        "--progress-every", type=float, default=None, metavar="SEC",
        help="TLC-style heartbeat line every SEC seconds from the "
        "last fetched stats snapshot (zero extra device syncs)",
    )
    ap.add_argument(
        "--xprof", default=None, metavar="DIR",
        help="capture a JAX profiler trace into DIR around the "
        "--xprof-levels window (real-chip runs)",
    )
    ap.add_argument(
        "--xprof-levels", default=None, metavar="LO:HI",
        help="BFS level window for --xprof (e.g. 7:7 profiles the "
        "deep level; default: the whole run)",
    )
    return ap.parse_args(argv)


def main(argv=None):
    import jax

    from pulsar_tlaplus_tpu.utils.device import setup_compile_cache

    args = parse_args(argv)
    setup_compile_cache()
    if args.fleet:
        return run_fleet_bench(args)
    if args.matrix:
        return run_matrix(args)
    if args.mode == "simulate":
        return run_sim_bench(args)
    c = scaled_config()
    dev = jax.devices()[0]
    print(f"bench device: {dev}", file=sys.stderr)

    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel

    model = CompactionModel(c)
    print(
        f"scaled config: state width {model.layout.total_bits} bits "
        f"({model.layout.W} words), {model.A} action lanes",
        file=sys.stderr,
    )
    metrics_path = "/tmp/bench_levels.jsonl"
    try:
        os.remove(metrics_path)
    except OSError:
        pass
    # a USER-supplied telemetry stream is never wiped: it appends, and
    # resume chains link headers to prior frames (docs/observability.md
    # "Resume linking").  The per-process DEFAULT path gets the same
    # treatment as the metrics JSONL above — PID reuse must not append
    # this run onto a dead run's stream.
    cleanup_stale_streams(args.telemetry_path)
    if args.telemetry == _DEFAULT_TELEMETRY:
        args.telemetry = os.path.join(
            args.telemetry_path,
            f"bench_telemetry_{os.getpid()}.jsonl",
        )
        try:
            os.remove(args.telemetry)
        except OSError:
            pass
    # Tier sizing: pre-size every capacity so no growth of the visited
    # sort tier (= no re-jit of the big flush sort) happens inside the
    # timed budget; the run is HBM-capacity-bound, not time-bound.
    # HBM @16GB (round-4 layout, flush_factor=2 -> ACAP=17.8M):
    # rows (52M+17.8M)*80B = 5.6 GB, accumulator rows 1.43 GB, visited
    # keys 2*4B*69.8M = 0.56 GB, logs 0.56 GB, flush sort transients
    # ~1.7 GB, appcore chunked sorts + rows_flat ~2.3 GB -> ~12.5 GB
    # peak.  flush_factor=2 halves the dominant per-candidate flush
    # sort traffic vs round 3 (visited re-sorted once per 17.8M
    # candidates instead of per 8.9M).
    kw = dict(BENCH_CHECKER_KW)
    kw["max_states"] = args.max_states
    xprof_window = None
    if args.xprof_levels:
        from pulsar_tlaplus_tpu.obs.telemetry import parse_level_window

        try:
            xprof_window = parse_level_window(args.xprof_levels)
        except ValueError as e:
            sys.exit(f"bench: --xprof-levels: {e}")
    ck = DeviceChecker(
        model,
        time_budget_s=args.budget_s,
        progress=True,
        metrics_path=metrics_path,
        fuse=args.fuse,
        fuse_group=args.fuse_group,
        hbm_budget=args.hbm_budget,
        spill_compress=(False if args.no_spill_compress else None),
        checkpoint_path=args.checkpoint,
        checkpoint_every=args.checkpoint_every,
        telemetry=args.telemetry,
        heartbeat_s=args.progress_every,
        xprof_dir=args.xprof,
        xprof_levels=xprof_window,
        **kw,
    )
    t0 = time.time()
    if args.recover:
        # resume from the frame: no host seed (the frame IS the warm
        # start), warmup still hides the compiles
        compile_s = ck.warmup(seed=False)
        print(f"compile warmup: {compile_s:.1f}s", file=sys.stderr)
        r = ck.run(resume=True)
        return _emit(args, ck, c, r, compile_s, metrics_path)
    # the host-seeded warm start: the tiny early levels pay full-width
    # kernel latency on the device, so the Python oracle enumerates
    # them on a thread while the device programs compile
    import threading

    box = {}

    def _seed():
        try:
            box["seed"] = model.host_seed(
                max_level_states=800_000, max_total=1_000_000
            )
            # push the ~50 MB of seed arrays to the device NOW,
            # overlapping the compile warmup instead of the head of
            # the measured budget
            ck.prestage_seed(box["seed"])
        except Exception as e:  # noqa: BLE001
            box["err"] = e

    seed_t = threading.Thread(target=_seed)
    seed_t.start()
    compile_s = ck.warmup(seed=True)
    print(f"compile warmup: {compile_s:.1f}s", file=sys.stderr)
    print(f"  compile breakdown: {ck.last_stats}", file=sys.stderr)
    seed_t.join()
    if "err" in box:
        raise box["err"]
    seed = box["seed"]
    print(
        f"seed prefix: {len(seed[0])} states / {len(seed[3])} levels",
        file=sys.stderr,
    )
    r = ck.run(seed=seed)
    return _emit(args, ck, c, r, compile_s, metrics_path)


def _emit(args, ck, c, r, compile_s, metrics_path):
    # CPU baselines AFTER the device run: XLA compiles run in a LOCAL
    # helper subprocess (the round-4 try that measured them during
    # warmup saw the native baseline halved by CPU contention on this
    # 1-core image), and the run's host side is fetch-bound
    base = {
        "native": measure_native_baseline(c, threads=1),
        "native8": measure_native_baseline(c, threads=8),
        "py": measure_python_baseline(c, BASELINE_SLICE_S),
    }
    print(
        f"tpu: {r.distinct_states} states in {r.wall_s:.1f}s "
        f"({r.states_per_sec:.0f} st/s), {r.diameter} levels, "
        f"truncated={r.truncated}",
        file=sys.stderr,
    )

    base_sps, base_levels = base["py"]
    nat = base["native"]
    nat8 = base["native8"]
    print(
        f"python-oracle baseline: {base_sps:.0f} st/s "
        f"({base_levels} levels reached)",
        file=sys.stderr,
    )
    print(
        f"native C++ baseline: {nat['states_per_sec']:.0f} st/s (1 core); "
        f"{nat8['states_per_sec']:.0f} st/s (threads=8 on a 1-core "
        "image)",
        file=sys.stderr,
    )

    nat_sps = nat["states_per_sec"]
    nat8_sps = nat8["states_per_sec"]
    nat8_extrap = 8.0 * nat_sps  # see module docstring
    # one stream parse feeds both the sustained-rate records and the
    # artifact keys; a stream file shared with other processes (a
    # non-default --telemetry path) may interleave their runs, so the
    # events are held to THIS run's run_id before any aggregation
    tel_events = []
    if args.telemetry:
        from pulsar_tlaplus_tpu.obs import report

        try:
            tel_events, _errs = report.load_events(args.telemetry)
        except OSError:
            tel_events = []
        rid = getattr(ck, "_run_id", None)
        if rid:
            tel_events = [
                e for e in tel_events if e.get("run_id") == rid
            ]
    # sustained rates anchor in the telemetry level records (default on
    # since round 10; a genuine trailing >= 60 s window or None), with
    # the legacy per-level metrics JSONL as the fallback source
    recs = telemetry_level_records(tel_events) or load_metrics_records(
        metrics_path
    )
    last_level_sps, final60_sps = sustained_rates(recs, r.wall_s)
    host_wait = getattr(ck, "_host_wait_s", None)
    # the artifact's per-stage / fpset / ckpt keys come from the
    # telemetry stream through the SAME aggregation layer as
    # `scripts/telemetry_report.py --bench-keys` (ROADMAP round-8 ask:
    # no hand-copied numbers), falling back to the engine's last_stats
    # when the stream is disabled
    tel_keys, tel_stages = {}, None
    if tel_events:
        tel_keys = report.bench_keys(tel_events)
        split = report.stage_split(tel_events)
        if split:
            tel_stages = {
                name: d["n"] for name, d in sorted(split.items())
            }

    def stat(k, default=None):
        return tel_keys.get(k, ck.last_stats.get(k, default))
    print(
        json.dumps(
            {
                "metric": "distinct states/sec on scaled compaction.tla "
                "(|Keys|=8, |Msgs|=64, producer modeled; dedup + "
                "TypeSafe + CompactionHorizonCorrectness checked); "
                "vs_baseline = vs 8x-extrapolated 1-core native C++ "
                "BFS (image has 1 CPU core; see BASELINE.md)",
                "value": round(r.states_per_sec, 1),
                "unit": "states/sec/chip",
                # machine-visible schema versioning (ADVICE r4):
                # vs_baseline redefined in r4 to the 8x-extrapolated
                # native baseline (schema 2); schema 3 adds the
                # telemetry/survivability key set (fpset_*, ckpt_*,
                # stop_reason...); schema 4 adds ckpt_retries (the
                # frame writer's transient-failure retry breadcrumb);
                # schema 5 (r10) adds compact_impl and sources the
                # telemetry-derived keys from the stream itself
                # — validated by scripts/check_telemetry_schema.py;
                # schema 6 (r13) adds the level-fusion mode + the
                # run's dispatch economy (dispatches_per_level,
                # stage_fused_n, fuse_levels); schema 7 (r14) adds the
                # in-kernel work-unit totals (work_*) the
                # cost-attribution model prices and the ledger gates
                # (work-units/state is the machine-independent
                # efficiency signal); schema 8 (r16) adds the
                # tiered-store budget + spill economy keys
                # (hbm_budget, spill_bytes_per_state,
                # spill_overlap_ratio — null on untiered runs);
                # schema 9 (r18) adds the workload mode plus the
                # swarm-simulation throughput keys (walks_per_sec,
                # steps_per_state — null on check-mode runs);
                # schema 10 (r20) adds the fleet-dispatcher keys
                # (fleet_backends, fleet_jobs_per_sec, fleet_route_ms,
                # fleet_replicated_wire_bytes — null on solo runs);
                # schema 11 (r21) adds the fleet survivability
                # latencies (fleet_failover_ms, fleet_reconcile_ms —
                # null on solo runs and on drills without a
                # drain/rejoin); schema 12 (r23) adds probe_impl,
                # expand_impl, sieve_impl and probe_lanes_per_sec,
                # the flush-stage throughput
                "bench_schema": 12,
                "mode": "check",
                "walks_per_sec": None,
                "steps_per_state": None,
                "fleet_backends": None,
                "fleet_jobs_per_sec": None,
                "fleet_route_ms": None,
                "fleet_replicated_wire_bytes": None,
                "fleet_failover_ms": None,
                "fleet_reconcile_ms": None,
                "vs_baseline_definition": "native_8w_extrapolated",
                "vs_baseline": round(
                    r.states_per_sec / max(nat8_extrap, 1e-9), 2
                ),
                "vs_native_baseline": round(
                    r.states_per_sec / max(nat_sps, 1e-9), 2
                ),
                "vs_native_8thr_measured": round(
                    r.states_per_sec / max(nat8_sps, 1e-9), 2
                ),
                "vs_native_8w_extrapolated": round(
                    r.states_per_sec / max(nat8_extrap, 1e-9), 2
                ),
                "vs_python_oracle": round(
                    r.states_per_sec / max(base_sps, 1e-9), 2
                ),
                "native_baseline_states_per_sec": round(nat_sps, 1),
                "native_8thr_states_per_sec": round(nat8_sps, 1),
                "native_8w_extrapolated_states_per_sec": round(
                    nat8_extrap, 1
                ),
                "baseline_states_per_sec": round(base_sps, 1),
                "baseline_levels": base_levels,
                "compile_warmup_s": round(compile_s, 1),
                "compile_breakdown_s": ck.last_stats,
                "levels": r.diameter,
                "distinct_states": r.distinct_states,
                # survivability telemetry (ISSUE r7): the r06+
                # trajectory captures whether the run survived, not
                # just how fast it went
                "stop_reason": r.stop_reason,
                "truncated": r.truncated,
                "hbm_recovered": getattr(r, "hbm_recovered", 0),
                "ckpt_frames": stat("ckpt_frames", 0),
                "ckpt_bytes": stat("ckpt_bytes", 0),
                # frame-write stall seconds (BENCH_r07 ask): host time
                # the run loop spent blocked gathering + writing frames
                "ckpt_write_s": stat("ckpt_write_s", 0.0),
                # transient frame-write failures absorbed by the
                # retry/backoff path (nonzero = the disk hiccuped and
                # the run survived it; docs/robustness.md)
                "ckpt_retries": stat("ckpt_retries", 0),
                "checkpoint": args.checkpoint,
                "telemetry": args.telemetry,
                "stats_fetches": stat("stats_fetches"),
                "sustained_last_level_sps": (
                    round(last_level_sps, 1)
                    if last_level_sps is not None else None
                ),
                "sustained_final_60s_sps": (
                    round(final60_sps, 1)
                    if final60_sps is not None else None
                ),
                "host_wait_s": (
                    round(host_wait, 2) if host_wait is not None else None
                ),
                "fp_collision_prob": r.fp_collision_prob,
                # visited_impl, compact_impl, probe_impl, expand_impl,
                # sieve_impl: one value each since there is one
                # implementation of each stage
                **IMPL_FIELDS,
                # the flush-stage throughput (higher is better)
                "probe_lanes_per_sec": (
                    round(stat("work_probe_lanes") / r.wall_s, 1)
                    if stat("work_probe_lanes") and r.wall_s > 0
                    else None
                ),
                # level fusion (r13): the megakernel's dispatch
                # economy — total dispatches per BFS level, fused
                # dispatches, and levels the ramp batched
                "fuse": ck.fuse,
                "dispatches_per_level": stat("dispatches_per_level"),
                "stage_fused_n": stat("stage_fused_n"),
                "fuse_levels": stat("fuse_levels"),
                # in-kernel work-unit totals (r14, bench_schema 7):
                # the cost-attribution inputs and the ledger's
                # machine-independent efficiency signal
                # (work-units/state) — docs/observability.md
                # "Attribution"
                "work_expand_rows": stat("work_expand_rows"),
                "work_probe_lanes": stat("work_probe_lanes"),
                "work_compact_elems": stat("work_compact_elems"),
                "work_append_rows": stat("work_append_rows"),
                "work_groups": stat("work_groups"),
                # tiered-store economy (r16, bench_schema 8): the
                # budget the run was tiered under (null = untiered),
                # compressed spill bytes per distinct state (the
                # 1B-state byte-rate arithmetic's measured input),
                # and the async-transfer overlap ratio (1.0 = level
                # boundaries never waited on a spill transfer)
                "hbm_budget": stat("hbm_budget"),
                "spill_bytes_per_state": stat("spill_bytes_per_state"),
                "spill_overlap_ratio": stat("spill_overlap_ratio"),
                "spill_bytes_raw": stat("spill_bytes_raw"),
                "spill_bytes_comp": stat("spill_bytes_comp"),
                "spill_keys_evicted": stat("spill_keys_evicted"),
                "spill_rows_evicted": stat("spill_rows_evicted"),
                "spill_misses_resolved": stat("spill_misses_resolved"),
                # per-stage dispatch counts straight from the stream
                # (the telemetry_report --bench-keys layer; None when
                # --no-telemetry)
                "stages": tel_stages,
                "max_states": args.max_states,
                # per-flush fpset metrics (ISSUE r6 acceptance): flush
                # count, cumulative + average probe rounds, failures
                # (nonzero aborts the run), final table occupancy
                "fpset_flushes": stat("fpset_flushes"),
                "fpset_probe_rounds": stat("fpset_probe_rounds"),
                "fpset_avg_probe_rounds": stat("fpset_avg_probe_rounds"),
                "fpset_failures": stat("fpset_failures"),
                "fpset_occupancy": stat("fpset_occupancy"),
                # zero-sync device counters (r8): candidate lanes after
                # validity masking, duplicate ratio, worst flush depth
                "fpset_valid_lanes": stat("fpset_valid_lanes"),
                "fpset_duplicate_ratio": stat("fpset_duplicate_ratio"),
                "fpset_max_probe_rounds": stat("fpset_max_probe_rounds"),
                "engine": (
                    "device_bfs r13 (fused level megakernel — one "
                    "dispatch per BFS level, ramp batching; fpset HBM "
                    "hash-table visited set, frontier-window row "
                    "store, flush_factor=3, "
                    "64-bit fingerprints)"
                    if args.fuse == "level"
                    else "device_bfs r10-compat (--fuse stage: "
                    "per-stage dispatch chain)"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
