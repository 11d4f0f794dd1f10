#!/usr/bin/env python
"""Validate telemetry JSONL streams and BENCH_*.json artifacts against
the versioned schemas — wired as a tier-1 test so a bench-artifact or
stream regression fails fast instead of surfacing as a hand-transcribed
table that doesn't add up.

    python scripts/check_telemetry_schema.py run.jsonl BENCH_r06.json
    python scripts/check_telemetry_schema.py --all-bench   # repo BENCH_*.json

File kind is sniffed by extension: ``.jsonl`` = event stream, ``.json``
= bench artifact (the driver wrapper ``{"parsed": {...}}`` and the raw
bench line both work).

Stream rules (schema v4, ``obs/telemetry.py`` EVENTS is authoritative;
older records are held only to their own version's fields):
every line parses as an object; carries ``v``/``event``/``t``/
``run_id``; ``v`` <= the supported version; ``t`` is monotonically
non-decreasing per run_id; ``seq`` is STRICTLY increasing per run_id
(streams legitimately interleave several run_ids since r11 — one per
daemon scheduling slice or restart — but a torn/duplicated writer
within one run must fail); known event types carry their required
fields (r9 additions: ``ckpt_frame`` carries the frame writer's
``retries`` count, the liveness engine emits per-chunk ``sweep``
records, and the sharded engine's ``flush`` records carry the 5-wide
fpm keys — real ``valid_lanes`` + ``max_probe_rounds``; r10: the
device engines emit ``compact`` records — per-fetch deltas of the
stream-compaction dispatch counters with the active ``impl`` — held
to their fields only at v3 via FIELD_SINCE, so pre-r10 streams stay
validator-clean; r11: the checker daemon's ``job_*`` + ``serve``
lifecycle events, required fields gated at v4; r12: ``job_suspend``
carries ``slice_wall_s`` and ``job_resume`` carries ``restore_s`` —
the measured context-switch halves — gated at v5; r13: the device
engine's ``fuse`` megakernel records, gated at v6, and a fused-run
CROSS-CHECK — every run whose header declares ``fuse: "level"`` must
carry strictly increasing boundary ``level`` records whose per-level
sizes match the result's ``level_sizes`` and, on clean runs, sum to
its distinct-state count; r14: v7 ``fuse`` records carry per-dispatch
work-unit deltas, ``sweep`` records cumulative sweep work units, and
the new ``attribution`` record the per-stage work totals; r15: v8
run headers carry ``profile_sig`` (historic: the tuned profile that
shaped the run's knobs; null in every stream since PR 48 took the
tuner out) and streams of that time carry ``tune`` records (knob,
value) of its in-run controller; r16: v9 run headers carry
``hbm_budget`` — the tiered-store byte budget, null on untiered runs
— and tiered engines emit ``spill`` records whose counters
(keys/rows evicted, raw/compressed bytes, transfer seconds, misses
resolved) are CUMULATIVE per run: the validator cross-checks that
per-level spill bytes are monotone-cumulative, so a torn or re-based
spill writer fails loudly; r17: v10 run headers carry ``tenant`` —
the bearer-token-derived tenant, null on standalone runs — and the
hardened daemon emits ``admission`` (admit/reject/shed/dedup, with
tenant + reason), ``auth`` (TCP handshake), and ``deadline`` (the
deadline sweep cancelling an expired job) events; r18: v11 run headers carry
``mode`` — the workload class (``check`` / ``liveness`` /
``simulate``) — and the streaming simulation engine (sim/) emits
``sim`` records whose counters (steps, states, walks, violations,
stutter steps, enabled lanes, duplicate-estimator attempts/hits) are
CUMULATIVE per run: the validator cross-checks monotonicity exactly
like ``spill``, so a torn or re-based walk-stream writer fails
loudly — all
FIELD_SINCE-gated so
older streams stay clean).  ``--trace``
validates an exported Perfetto trace file's event structure instead
(obs/trace.py); ``--ledger`` validates cross-run regression ledger
files (obs/ledger.py — record structure + digest integrity);
``--tokens`` validates daemon tokens.json files (service/auth.py —
tokens_v, non-empty tenants, unique tokens/tenants, reserved-name
and token-length rules); ``--warm`` validates warm-artifact
directories (warm/store.py — manifest shape, warm_v, per-file
SHA-256 digests + byte counts; r19: v12 run headers carry ``warm``
— the warm-start mode, null on cold/standalone runs — and the
daemon emits ``warm`` reuse-decision events).  Bench
rules: ``bench_schema`` >= 2 requires the
headline keys, >= 3 additionally the telemetry/survivability key set
(``fpset_*``, ``ckpt_*``, ``stop_reason``...), >= 4 additionally
``ckpt_retries``, >= 5 additionally ``compact_impl``, >= 6
additionally ``fuse`` + ``dispatches_per_level``, >= 7 additionally
the ``work_*`` unit totals (r14 attribution), >= 8 additionally
the tiered-store keys (``hbm_budget``, ``spill_bytes_per_state``,
``spill_overlap_ratio`` — null on untiered runs, keys required),
>= 9 additionally the swarm-simulation throughput keys
(``walks_per_sec``, ``steps_per_state`` — null on check-mode runs,
keys required), >= 10 additionally the fleet-tier keys
(``fleet_backends``, ``fleet_jobs_per_sec``, ``fleet_route_ms``,
``fleet_replicated_wire_bytes`` — null on non-fleet runs, keys
required), >= 11 additionally the fleet survivability latencies
(``fleet_failover_ms`` — drain detected to queued jobs landed
elsewhere, ``fleet_reconcile_ms`` — rejoin detected to lost jobs
answered for; null on non-fleet runs, keys required).  r20: v13
streams additionally validate the dispatcher's
``route``/``replicate``/``failover`` events (FIELD_SINCE-gated) and
the ``ptt_fleet_*`` families render identically from the live
dispatcher and a stream scrape.  r21: v14 streams additionally
validate the survivability events — ``reconcile`` (backend, job_id,
the real state that replaced ``lost``), ``partition`` (a drained
backend rejoined still holding its jobs), ``recover`` (a ``dispatch
--recover`` pass with its confirmed/adopted/lost counts) — all
FIELD_SINCE-gated so committed v13-and-older streams stay clean.
r22: v15 streams carry the distributed-tracing envelope — every
``job_*`` event, ``run_header``, and dispatcher hop
(``route``/``replicate``/``failover``/``reconcile``) carries the
job's ``trace_id`` (null where no fleet minted one), ``route``
carries the split ``route_ms``/``ack_ms`` decision-vs-ack latencies,
and the new ``complete``/``relay``/``hold``/``shed``/``persist_fail``
events close the job, time the watch-relay legs, and make the
dispatcher's hold/shed/persist counters stream-derivable
(``persist_fail`` carries the CUMULATIVE count) — all
FIELD_SINCE-gated.  r23: v16 run headers carry ``probe_impl`` /
``expand_impl`` / ``sieve_impl`` (``legacy`` on the device engines,
null on the host engines: ``obs/telemetry.py IMPL_FIELDS``), and
bench_schema >= 12 artifacts additionally require those three keys
plus ``probe_lanes_per_sec`` (the flush-stage throughput) — all
FIELD_SINCE-gated so committed v15-and-older streams stay clean.  ``--metrics`` validates
Prometheus exposition
text files (``cli.py metrics`` output) instead: TYPE-histogram
families must carry cumulative monotone buckets ending at ``+Inf``,
a ``_count`` equal to the ``+Inf`` bucket, and a ``_sum`` inside the
bounds the buckets admit (obs/metrics.py ``validate_exposition``).

Exit status: 0 clean, 1 violations (listed on stderr), 2 usage.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import List

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from pulsar_tlaplus_tpu.obs.telemetry import (  # noqa: E402
    BASE_FIELDS,
    EVENTS,
    FIELD_SINCE,
    SCHEMA_VERSION,
)

# bench-artifact key requirements by bench_schema version (additive)
BENCH_KEYS_V2 = (
    "metric", "value", "unit", "vs_baseline", "vs_baseline_definition",
    "distinct_states", "levels", "compile_warmup_s",
)
BENCH_KEYS_V3 = BENCH_KEYS_V2 + (
    "stop_reason", "truncated", "hbm_recovered",
    "ckpt_frames", "ckpt_bytes", "ckpt_write_s",
    "fpset_flushes", "fpset_probe_rounds", "fpset_avg_probe_rounds",
    "fpset_failures", "fpset_occupancy",
    "fpset_valid_lanes", "fpset_max_probe_rounds",
    "visited_impl", "max_states", "stats_fetches",
)
# v4 (r9): the frame writer's transient-failure retry breadcrumb
BENCH_KEYS_V4 = BENCH_KEYS_V3 + ("ckpt_retries",)
# v5 (r10): the stream-compaction impl (logshift|sort differential)
BENCH_KEYS_V5 = BENCH_KEYS_V4 + ("compact_impl",)
# v6 (r13): the level-fusion mode and the run's dispatch economy (the
# fused-vs-stage differential headline)
BENCH_KEYS_V6 = BENCH_KEYS_V5 + ("fuse", "dispatches_per_level")
# v7 (r14): the in-kernel work-unit totals the cost-attribution model
# prices (docs/observability.md "Attribution")
BENCH_KEYS_V7 = BENCH_KEYS_V6 + (
    "work_expand_rows", "work_probe_lanes", "work_compact_elems",
    "work_append_rows", "work_groups",
)
# v8 (r16): the tiered-store budget + spill economy signals (null on
# untiered runs; the keys themselves are required)
BENCH_KEYS_V8 = BENCH_KEYS_V7 + (
    "hbm_budget", "spill_bytes_per_state", "spill_overlap_ratio",
)
# v9 (r18): the swarm-simulation throughput signals (null on
# check-mode runs; the keys themselves are required)
BENCH_KEYS_V9 = BENCH_KEYS_V8 + ("walks_per_sec", "steps_per_state")
# v10 (r20): the fleet-tier signals from `bench.py --fleet N` — how
# many backends served, end-to-end queue throughput through the
# dispatcher, mean route (placement) latency, and the replication
# sieve's total delta-compressed wire bytes (null on non-fleet runs;
# the keys themselves are required)
BENCH_KEYS_V10 = BENCH_KEYS_V9 + (
    "fleet_backends", "fleet_jobs_per_sec", "fleet_route_ms",
    "fleet_replicated_wire_bytes",
)
# v11 (r21): the fleet survivability latencies — mean time from a
# drain detected to its queued jobs landing elsewhere, and from a
# rejoin detected to its lost jobs answered for (null on non-fleet
# runs AND on fleet runs whose drill saw no drain/rejoin; the keys
# themselves are required)
BENCH_KEYS_V11 = BENCH_KEYS_V10 + (
    "fleet_failover_ms", "fleet_reconcile_ms",
)
# v12 (r23): the kernel fields (obs/telemetry.py IMPL_FIELDS; null on
# the host engines) and the flush-stage probe throughput (null when no
# probe lanes were counted; the keys themselves are required)
BENCH_KEYS_V12 = BENCH_KEYS_V11 + (
    "probe_impl", "expand_impl", "sieve_impl", "probe_lanes_per_sec",
)


def _check_fused_levels(path: str, runs: dict) -> List[str]:
    """v6 fused-run cross-check: for every run whose header declares
    ``fuse: "level"``, the non-``partial`` (boundary) ``level`` records
    must carry strictly increasing levels whose ``new_states`` match
    the result's ``level_sizes`` entry for that level — and on a clean
    (non-truncated, non-violation) run the per-level sizes must sum to
    the result's distinct-state count.  This is what pins the fused
    megakernel's host-side per-level accounting replay: a batch that
    dropped, duplicated, or misordered a level record fails here."""
    errors: List[str] = []
    for rid, r in runs.items():
        hd, res, levels = r["header"], r["result"], r["levels"]
        if not hd or hd.get("fuse") != "level" or res is None:
            continue
        sizes = res.get("level_sizes")
        prev = 0
        for e in levels:
            lv = e.get("level")
            if not isinstance(lv, int):
                continue
            if lv <= prev:
                errors.append(
                    f"{path}: run {rid}: fused boundary level records "
                    f"not strictly increasing ({lv} after {prev})"
                )
            prev = lv
            if (
                isinstance(sizes, list)
                and 1 <= lv <= len(sizes)
                and e.get("new_states") != sizes[lv - 1]
            ):
                errors.append(
                    f"{path}: run {rid}: level {lv} record says "
                    f"+{e.get('new_states')} but result.level_sizes"
                    f"[{lv - 1}] is {sizes[lv - 1]}"
                )
        if (
            isinstance(sizes, list)
            and not res.get("truncated")
            and not res.get("violation")
            and sum(sizes) != res.get("distinct_states")
        ):
            errors.append(
                f"{path}: run {rid}: fused level_sizes sum "
                f"{sum(sizes)} != distinct_states "
                f"{res.get('distinct_states')}"
            )
    return errors


# the spill record's cumulative counters (v9): each must be
# monotone non-decreasing per run_id (``fetches`` / ``fetch_planes``,
# the round trips and the columns they brought, where a record has
# them: optional, PR 47)
SPILL_CUMULATIVE = (
    "keys_evicted", "rows_evicted", "bytes_raw", "bytes_comp",
    "transfer_s", "misses_resolved", "fetches", "fetch_planes",
)

# the sim record's cumulative counters (v11): each must be monotone
# non-decreasing per run_id (the walk stream only moves forward;
# ``drawn_steps`` where a record has it: optional, PR 53)
SIM_CUMULATIVE = (
    "steps", "states", "walks", "violations", "stutter_steps",
    "enabled_lanes", "dup_attempts", "dup_hits", "drawn_steps",
)


def validate_stream(path: str) -> List[str]:
    """All schema violations in one stream (empty list = clean)."""
    errors: List[str] = []
    last_t: dict = {}
    last_seq: dict = {}
    fused_runs: dict = {}
    last_spill: dict = {}
    last_sim: dict = {}
    n = 0
    try:
        f = open(path)
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    with f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            n += 1
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"{path}:{i}: unparseable JSON ({e})")
                continue
            if not isinstance(rec, dict):
                errors.append(f"{path}:{i}: not a JSON object")
                continue
            missing = [k for k in BASE_FIELDS if k not in rec]
            if missing:
                errors.append(
                    f"{path}:{i}: missing base fields {missing}"
                )
                continue
            if not isinstance(rec["v"], int) or rec["v"] < 1:
                errors.append(f"{path}:{i}: bad schema version {rec['v']!r}")
            elif rec["v"] > SCHEMA_VERSION:
                errors.append(
                    f"{path}:{i}: schema v{rec['v']} newer than "
                    f"supported v{SCHEMA_VERSION}"
                )
            if not isinstance(rec["t"], (int, float)):
                errors.append(f"{path}:{i}: non-numeric t {rec['t']!r}")
            else:
                rid = rec["run_id"]
                if rec["t"] < last_t.get(rid, float("-inf")):
                    errors.append(
                        f"{path}:{i}: t went backwards for run "
                        f"{rid} ({rec['t']} < {last_t[rid]})"
                    )
                last_t[rid] = rec["t"]
            if isinstance(rec.get("seq"), int):
                # per-run_id STRICT monotonicity: interleaved run_ids
                # (a daemon stream, per-slice job streams) are legal,
                # but one run's writer repeating or reordering seq is
                # a torn/duplicated stream
                rid = rec["run_id"]
                prev = last_seq.get(rid)
                if prev is not None and rec["seq"] <= prev:
                    errors.append(
                        f"{path}:{i}: seq not increasing for run "
                        f"{rid} ({rec['seq']} <= {prev})"
                    )
                last_seq[rid] = rec["seq"]
            else:
                errors.append(
                    f"{path}:{i}: non-integer seq {rec.get('seq')!r}"
                )
            req = EVENTS.get(rec["event"])
            if req:
                # a record is held only to the fields its OWN schema
                # version requires — pre-r9 (v1) streams stay valid
                # even though v2 added fields (FIELD_SINCE)
                v = rec["v"] if isinstance(rec["v"], int) else 1
                miss = [
                    k for k in req
                    if k not in rec
                    and FIELD_SINCE.get((rec["event"], k), 1) <= v
                ]
                if miss:
                    errors.append(
                        f"{path}:{i}: {rec['event']} missing {miss}"
                    )
            if rec["event"] == "sim" and isinstance(
                rec.get("v"), int
            ) and rec["v"] >= 11:
                # v11 cross-check: sim counters are CUMULATIVE per run
                # — a record whose steps/states go backwards is a torn
                # writer or a silently re-based walk stream
                prev = last_sim.setdefault(rec["run_id"], {})
                for k in SIM_CUMULATIVE:
                    cur = rec.get(k)
                    if not isinstance(cur, (int, float)):
                        continue
                    if cur < prev.get(k, float("-inf")):
                        errors.append(
                            f"{path}:{i}: sim.{k} went backwards "
                            f"for run {rec['run_id']} ({cur} < "
                            f"{prev[k]} — cumulative contract)"
                        )
                    prev[k] = cur
            if rec["event"] == "spill" and isinstance(
                rec.get("v"), int
            ) and rec["v"] >= 9:
                # v9 cross-check: spill counters are CUMULATIVE per
                # run — a record whose bytes/keys go backwards is a
                # torn writer or a silently re-based store
                prev = last_spill.setdefault(rec["run_id"], {})
                for k in SPILL_CUMULATIVE:
                    cur = rec.get(k)
                    if not isinstance(cur, (int, float)):
                        continue
                    if cur < prev.get(k, float("-inf")):
                        errors.append(
                            f"{path}:{i}: spill.{k} went backwards "
                            f"for run {rec['run_id']} ({cur} < "
                            f"{prev[k]} — cumulative contract)"
                        )
                    prev[k] = cur
            # collect per-run material for the v6 fused-run
            # cross-check (boundary level records vs result sizes)
            run = fused_runs.setdefault(
                rec["run_id"],
                {"header": None, "result": None, "levels": []},
            )
            if rec["event"] == "run_header":
                run["header"] = rec
            elif rec["event"] == "result":
                run["result"] = rec
            elif rec["event"] == "level" and not rec.get("partial"):
                run["levels"].append(rec)
    if n == 0:
        errors.append(f"{path}: empty stream")
    errors += _check_fused_levels(path, fused_runs)
    return errors


def validate_bench_artifact(path_or_dict, path: str = "") -> List[str]:
    """Violations in one bench artifact (file path or parsed dict).
    Driver wrappers (``{"parsed": {...}}``) unwrap automatically."""
    if isinstance(path_or_dict, dict):
        d = path_or_dict
        label = path or "<dict>"
    else:
        label = path_or_dict
        try:
            with open(path_or_dict) as f:
                d = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return [f"{path_or_dict}: unreadable ({e})"]
    if "parsed" in d and isinstance(d["parsed"], dict):
        d = d["parsed"]
    errors: List[str] = []
    schema = d.get("bench_schema")
    if schema is None:
        # pre-schema artifacts (r1-r3): only the headline keys existed
        for k in ("metric", "value", "unit"):
            if k not in d:
                errors.append(f"{label}: missing {k}")
        return errors
    if not isinstance(schema, int) or schema < 2:
        errors.append(f"{label}: bad bench_schema {schema!r}")
        return errors
    if schema >= 12:
        required = BENCH_KEYS_V12
    elif schema >= 11:
        required = BENCH_KEYS_V11
    elif schema >= 10:
        required = BENCH_KEYS_V10
    elif schema >= 9:
        required = BENCH_KEYS_V9
    elif schema >= 8:
        required = BENCH_KEYS_V8
    elif schema >= 7:
        required = BENCH_KEYS_V7
    elif schema >= 6:
        required = BENCH_KEYS_V6
    elif schema >= 5:
        required = BENCH_KEYS_V5
    elif schema >= 4:
        required = BENCH_KEYS_V4
    elif schema >= 3:
        required = BENCH_KEYS_V3
    else:
        required = BENCH_KEYS_V2
    for k in required:
        if k not in d:
            errors.append(
                f"{label}: bench_schema {schema} missing key {k!r}"
            )
    if not isinstance(d.get("value"), (int, float)):
        errors.append(f"{label}: non-numeric value {d.get('value')!r}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate telemetry streams (.jsonl) and bench "
        "artifacts (.json) against the versioned schemas"
    )
    ap.add_argument("files", nargs="*", help=".jsonl streams / .json artifacts")
    ap.add_argument(
        "--all-bench", action="store_true",
        help="also validate every BENCH_*.json in the repo root",
    )
    ap.add_argument(
        "--trace", action="store_true",
        help="treat the .json files as exported Perfetto traces "
        "(cli.py trace output) and validate their event structure",
    )
    ap.add_argument(
        "--ledger", action="store_true",
        help="treat the .jsonl files as cross-run regression ledgers "
        "(cli.py ledger output) and validate their record structure "
        "+ digest integrity instead of the telemetry stream schema",
    )
    ap.add_argument(
        "--tokens", action="store_true",
        help="treat the .json files as daemon tokens.json files "
        "(serve --tokens) and validate their shape (service/auth.py)",
    )
    ap.add_argument(
        "--metrics", action="store_true",
        help="treat the files as Prometheus exposition text (cli.py "
        "metrics output) and run the histogram-consistency "
        "cross-check (obs/metrics.py validate_exposition)",
    )
    ap.add_argument(
        "--warm", action="store_true",
        help="treat the files as warm-artifact dirs (or their "
        "manifest.json) and validate manifest shape + SHA-256 "
        "digest integrity (warm/store.py, docs/incremental.md)",
    )
    args = ap.parse_args(argv)
    files = list(args.files)
    if args.all_bench:
        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        files += sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    if not files:
        ap.error("nothing to validate (pass files or --all-bench)")
    errors: List[str] = []
    for p in files:
        if args.metrics:
            from pulsar_tlaplus_tpu.obs.metrics import (
                validate_exposition,
            )

            try:
                with open(p) as fh:
                    errors += validate_exposition(fh.read(), label=p)
            except OSError as e:
                errors += [f"{p}: unreadable ({e})"]
        elif args.warm:
            from pulsar_tlaplus_tpu.warm.store import validate_artifact

            errors += validate_artifact(p)
        elif p.endswith(".jsonl"):
            if args.ledger:
                from pulsar_tlaplus_tpu.obs.ledger import (
                    validate_ledger,
                )

                errors += validate_ledger(p)
            else:
                errors += validate_stream(p)
        elif args.trace:
            from pulsar_tlaplus_tpu.obs.trace import validate_trace

            errors += validate_trace(p)
        elif args.tokens:
            from pulsar_tlaplus_tpu.service.auth import (
                validate_tokens_file,
            )

            errors += validate_tokens_file(p)
        else:
            errors += validate_bench_artifact(p)
    for e in errors:
        print(e, file=sys.stderr)
    print(
        f"{len(files)} file(s), {len(errors)} violation(s)",
        file=sys.stderr,
    )
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
