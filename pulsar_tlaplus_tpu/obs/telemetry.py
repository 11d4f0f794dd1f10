"""Telemetry core — versioned JSONL run events, the TLC-style progress
heartbeat, and the dispatch-plus-fetch latency (RTT) probe.

Every engine emits into one append-only JSONL stream (``--telemetry
out.jsonl`` / ``-telemetry``): a run header, per-level progress
records, per-flush fpset aggregates, checkpoint-frame writes with their
write-stall seconds, HBM-recovery and fault-injection events, and the
final result.  The design rules:

- **Versioned schema.**  Every record carries ``v`` (the schema
  version), ``event``, ``t`` (monotonic seconds since the stream
  opened — wall-clock jumps can never reorder records), ``seq`` (a
  per-stream counter), and ``run_id``.  :data:`EVENTS` is the
  authoritative required-field table; ``scripts/
  check_telemetry_schema.py`` validates against it.
- **Zero hot-path syncs.**  Emission sites are host-side points the
  engines already pass through (the stats fetch, level boundaries,
  checkpoint writes).  Telemetry never adds a device round trip — the
  heartbeat below reports from the *last fetched* stats snapshot, and
  the zero-sync device counters ride the engines' existing single
  stats fetch (see ``device_bfs._fpflush_jit``).
- **Crash-durable lines.**  The stream is opened line-buffered and
  every record is one ``write()`` of a complete line, so a ``kill -9``
  (or the ``PTT_FAULT`` kill site) can lose at most the record being
  written — never corrupt earlier ones.  Fault events are emitted
  *before* the fault fires for exactly this reason.
- **Resume linking.**  Checkpoint frames embed the writer's
  ``run_id`` and ``frame_seq`` (utils/ckpt.py frame meta); a resumed
  run's header carries them back as ``resume_of`` /
  ``resume_frame_seq``, so a chain of interrupted runs is one
  navigable story across stream files.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Callable, Dict, Optional, Tuple, Union

# v1: the round-8 stream.  v2 (round 9): ``ckpt_frame`` records carry
# the frame writer's ``retries`` count, and the liveness engine emits
# ``sweep`` records.  v3 (round 10): the device engines emit
# ``compact`` records — per-stats-fetch deltas of the stream-compaction
# dispatch counters (the log-shift vs sort differential signal) — and
# their run headers carry ``compact_impl``.  v4 (round 11): the checker
# daemon (service/) emits ``job_*`` job-lifecycle events and ``serve``
# daemon-lifecycle events into its own stream (docs/service.md); per-
# job engine streams are unchanged, but a stream may now legitimately
# interleave several run_ids (one per scheduling slice / daemon
# restart) — the validator additionally requires per-run_id strictly
# increasing ``seq``.  v5 (round 12, the flight deck): the daemon's
# ``job_suspend`` records carry ``slice_wall_s`` (the suspended slice's
# engine wall — the mesh time-slice length actually delivered) and
# ``job_resume`` records carry ``restore_s`` (run-start to the first
# level boundary of the resumed slice: frame load + device rebuild =
# the context-switch restore cost the ROADMAP serve bench asks for);
# ``obs/trace.py`` renders suspend->resume gaps as explicit
# "context-switch" spans from exactly these fields.  v6 (round 13, the
# fused level megakernel): the device engine emits one ``fuse`` record
# per megakernel dispatch (levels closed, flushes run), its run header
# carries ``fuse``/``fuse_group``, intra-level ``level`` records are
# tagged ``partial`` so boundary records stay unambiguous, and the
# result stats carry ``stage_fused_n``/``dispatches_per_level``; the
# validator additionally cross-checks a fused run's boundary level
# records against the result's ``level_sizes`` (strictly increasing
# levels, per-level sizes summing to the distinct-state count).
# v7 (round 14, fused-era cost attribution): ``fuse`` records carry
# per-dispatch work-unit deltas (``work_expand_rows``,
# ``work_probe_lanes``, ``work_compact_elems``, ``work_append_rows``)
# accumulated INSIDE the megakernel's while loop and riding the one
# stats fetch; engines emit one ``attribution`` record (the per-stage
# work-unit totals, the machine-readable input to the calibrated cost
# model in ``obs/attribution.py``) before the result; the liveness
# sweep's ``sweep`` records carry cumulative sweep work units
# (``sort_lanes``, ``prop_lanes``, ``compact_elems``); result stats
# carry the ``work_*`` totals.
# v8 (round 15, the self-tuning checker, taken out by PR 48): run
# headers carry ``profile_sig`` — then the tuned profile that shaped
# the run's knobs, a constant null since (the field itself is REQUIRED
# at v8) — and streams of that time carry one ``tune`` record per knob
# adjustment of the in-run controller (knob, value, prev, reason).
# Both stay in the tables below so that committed streams validate;
# no engine emits ``tune`` any more.
# v9 (round 16, the tiered state store): run headers carry
# ``hbm_budget`` — the device-memory byte budget the run was tiered
# under (null on untiered runs; REQUIRED at v9 like profile_sig so
# spill trajectories always split cleanly) — and tiered engines emit
# one ``spill`` record per eviction/spill boundary: the tier written,
# keys/rows evicted, raw vs compressed bytes, transfer seconds, and
# misses resolved — ALL CUMULATIVE per run, so the validator can
# cross-check that per-level spill bytes are monotone-cumulative
# (a spill event whose counters go backwards is a torn writer or a
# re-based store; docs/memory.md).
# v10 (round 17, the hardened open-network daemon): run headers carry
# ``tenant`` — the bearer-token-derived tenant the run was executed
# for (null on standalone runs; REQUIRED at v10 like profile_sig /
# hbm_budget so per-tenant trajectories always split) — and the
# service layer emits three new events: ``admission`` (one per submit
# decision: admit / reject / shed / dedup, with tenant + reason),
# ``auth`` (TCP handshake accept/reject), and ``deadline`` (a job
# cancelled by the deadline sweep, ``stop_reason="deadline"``).  The
# ``spill`` record may carry ``degraded: true`` when the spill tier
# lost durability to ENOSPC (stop_reason="spill_enospc").
# v11 (round 18, the swarm simulation subsystem): run headers carry
# ``mode`` — the workload class (``check`` for exhaustive BFS,
# ``liveness`` for the two-phase liveness engine, ``simulate`` for the
# streaming walker swarm; REQUIRED at v11 like profile_sig /
# hbm_budget / tenant so workload trajectories always split) — and the
# simulation engine (sim/engine.py) emits one ``sim`` record per
# segment dispatch: CUMULATIVE steps / walkers / violations plus the
# states/walks totals, stutter and enabled-lane counters, and the
# sampled-duplicate estimator — cumulative so the validator can
# cross-check monotonicity exactly like ``spill`` (a sim record whose
# counters go backwards is a torn writer or a silently re-based walk
# stream; docs/simulation.md).
# v12 (round 19, incremental checking): run headers carry ``warm`` —
# the warm-start mode the run executed under (``continue`` when it
# resumed a prior run's artifact frame, ``reseed`` when it was seeded
# from a prior fingerprint set across a constant widening, null on
# cold/standalone runs; REQUIRED at v12 like profile_sig / hbm_budget /
# tenant / mode so warm trajectories always split — and so the ledger
# can refuse a warm-continue partial as a cold run's gate baseline) —
# and the daemon emits one ``warm`` event per reuse decision: the
# planned/installed mode with a machine-readable reason (``sig_match``,
# ``widened:AXIS``, or the cold fallback reason — module_edit,
# invariant_change, binding_change, narrowed, layout_change,
# digest_mismatch, torn_artifact, ... — docs/incremental.md).
# v13 (round 20, fleet/): the dispatcher's own stream — one ``route``
# record per submit placement (which backend, why), one ``replicate``
# record per artifact sieve pass (what shipped vs what the peer
# already held), one ``failover`` record per backend drain (how many
# queued jobs were resubmitted elsewhere).
# v14 (round 21, fleet survivability): three more dispatcher events —
# one ``reconcile`` record per lost job whose rejoined backend
# answered for it (which backend, which job, the real terminal state
# that replaced ``lost``), one ``partition`` record per drained
# backend that rejoined still holding its jobs (the signature of a
# partition window closing, as opposed to a restart), and one
# ``recover`` record per ``dispatch --recover`` pass (how many
# persisted jobs were confirmed / adopted / typed lost against the
# backends' authoritative job tables, and whether a torn
# fleet_jobs.json was quarantined first).
# v15 (round 22, the fleet observability plane): every accepted
# submit is minted a ``trace_id`` by the dispatcher and the id is
# stamped on every hop of the job's journey — the dispatcher's
# ``route`` / ``replicate`` / ``failover`` / ``reconcile`` records,
# the backend daemon's ``job_*`` lifecycle events (forwarded on the
# wire), and every engine ``run_header`` (null on standalone runs;
# REQUIRED at v15 like profile_sig / tenant / mode / warm so traced
# and untraced trajectories always split) — which is what lets
# ``obs/trace.py`` stitch one dispatcher stream plus N backend
# streams into ONE Perfetto timeline with cross-backend flow arrows.
# The dispatcher additionally emits latency observations so the
# fixed-bucket histogram families (obs/metrics.py ``ptt_*_seconds``)
# derive identically from a live scrape and a stream replay:
# ``route`` records carry ``route_ms`` (decision) and ``ack_ms``
# (submit acked end-to-end), ``failover`` records carry ``wall_ms``
# and the failed-over jobs' ``trace_ids``, ``partition`` records
# carry the reconcile pass ``wall_ms``, ``replicate`` records carry
# the transfer ``wall_ms`` and the triggering job's ``trace_id`` —
# and four NEW events: ``complete`` (the dispatcher observed a routed
# job reach a terminal state: end-to-end ``e2e_ms`` from accept to
# observed-terminal), ``relay`` (one watch-relay leg, ``leg_ms``),
# ``hold`` / ``shed`` (the all-backends-down queue-and-hold admitting
# or overflowing a submit), and ``persist_fail`` (a fleet_jobs.json
# persist that stayed failed after the retry — the counter was
# previously invisible to stream replay).
# v16 (round 23): every run header carries ``probe_impl`` /
# ``expand_impl`` / ``sieve_impl`` (null on the host engines) —
# REQUIRED at v16.  They named a choice of kernel while there was one;
# see IMPL_FIELDS below.
# Validators accept <= SCHEMA_VERSION and hold a record only to the
# fields its OWN version requires (FIELD_SINCE) — pre-r10 streams stay
# valid.
SCHEMA_VERSION = 16

# The five fields that recorded which implementation of a kernel stage
# a run of the device engines used.  There is one implementation of
# each now, so they are constants — kept because checkpoint frames
# (``visited_impl`` is an input of the configuration signature), warm
# artifacts, ledger keys and telemetry streams written before carry
# them.  Every writer takes them from here.
IMPL_FIELDS: Dict[str, str] = {
    "visited_impl": "fpset",
    "compact_impl": "logshift",
    "probe_impl": "legacy",
    "expand_impl": "legacy",
    "sieve_impl": "legacy",
}


def model_stats(model, keys) -> Dict[str, object]:
    """What a model's own constructor measured, for the device engines'
    ``result.stats``: a ``CompiledSpec`` carries ``codegen_stats`` (the
    generator's wall and the widths it came to) and, beside them, the
    key kind the engine chose for those widths (``key_exact`` false:
    the exact count rests on 64-bit hashes, as TLC's does).  A
    hand-written model carries nothing and adds nothing."""
    cg = getattr(model, "codegen_stats", None)
    return dict(cg, key_exact=bool(keys.exact)) if cg else {}


# Authoritative event table: event name -> required fields beyond the
# base envelope.  Unknown events are legal (forward compatibility) but
# must still carry the base envelope.
BASE_FIELDS: Tuple[str, ...] = ("v", "event", "t", "seq", "run_id")

# required fields introduced AFTER schema v1: (event, field) -> the
# version that added it.  The validator skips them for older records.
FIELD_SINCE: Dict[Tuple[str, str], int] = {
    ("ckpt_frame", "retries"): 2,
    ("compact", "dispatches"): 3,
    ("compact", "impl"): 3,
    # v4: the service daemon's job-lifecycle events (docs/service.md).
    # The events are NEW at v4, so gating their required fields keeps a
    # hypothetical pre-v4 stream using these names validator-clean.
    ("job_submit", "job_id"): 4,
    ("job_submit", "spec"): 4,
    ("job_start", "job_id"): 4,
    ("job_start", "spec"): 4,
    ("job_start", "slice"): 4,
    ("job_resume", "job_id"): 4,
    ("job_resume", "spec"): 4,
    ("job_resume", "slice"): 4,
    ("job_suspend", "job_id"): 4,
    ("job_suspend", "slice"): 4,
    # v5: the context-switch cost breakdown (docs/observability.md
    # "Flight deck") — required only at v5 so every existing v4 daemon
    # stream stays validator-clean
    ("job_suspend", "slice_wall_s"): 5,
    ("job_resume", "restore_s"): 5,
    ("job_result", "job_id"): 4,
    ("job_result", "status"): 4,
    ("job_cancel", "job_id"): 4,
    ("serve", "action"): 4,
    # v6: the fused level megakernel's per-dispatch record (round 13).
    # The event is NEW at v6; gating its fields keeps hypothetical
    # older streams using the name validator-clean.
    ("fuse", "levels"): 6,
    ("fuse", "dispatches"): 6,
    # v7 (round 14): in-kernel work-unit deltas on every fuse record,
    # cumulative sweep work units on sweep records, and the new
    # ``attribution`` per-stage work-total record — all gated so every
    # existing v6-and-older stream stays validator-clean.
    ("fuse", "work_expand_rows"): 7,
    ("fuse", "work_probe_lanes"): 7,
    ("fuse", "work_compact_elems"): 7,
    ("fuse", "work_append_rows"): 7,
    ("sweep", "sort_lanes"): 7,
    ("sweep", "prop_lanes"): 7,
    ("sweep", "compact_elems"): 7,
    ("attribution", "stages"): 7,
    # v8 (round 15): ``profile_sig`` on every run header (null since
    # PR 48) and the historic ``tune`` record — both gated so every
    # committed v7-and-older stream stays validator-clean.
    ("run_header", "profile_sig"): 8,
    ("tune", "knob"): 8,
    ("tune", "value"): 8,
    # v9 (round 16): the tiered-store budget on every run header
    # (null on untiered runs) and the cumulative ``spill`` record —
    # gated so every committed v8-and-older stream stays clean.
    ("run_header", "hbm_budget"): 9,
    # v10 (round 17): tenant identity on every run header (null
    # outside the daemon) and the open-network service events —
    # admission decisions, TCP auth handshakes, deadline cancels —
    # gated so every committed v9-and-older stream stays clean.
    ("run_header", "tenant"): 10,
    # v11 (round 18): the workload class on every run header and the
    # streaming simulation engine's cumulative ``sim`` record — gated
    # so every committed v10-and-older stream stays clean.
    ("run_header", "mode"): 11,
    ("sim", "steps"): 11,
    ("sim", "walkers"): 11,
    ("sim", "violations"): 11,
    # v12 (round 19): the warm-start mode on every run header (null on
    # cold/standalone runs) and the daemon's per-decision ``warm``
    # event — gated so every committed v11-and-older stream stays
    # clean.
    ("run_header", "warm"): 12,
    ("warm", "mode"): 12,
    ("warm", "reason"): 12,
    # v13 (round 20): the fleet dispatcher's events — NEW at v13, so
    # gating their required fields keeps every committed v12-and-older
    # stream using these names validator-clean.
    ("route", "backend"): 13,
    ("route", "tenant"): 13,
    ("replicate", "src"): 13,
    ("replicate", "dst"): 13,
    ("replicate", "blobs"): 13,
    ("replicate", "wire_bytes"): 13,
    ("failover", "backend"): 13,
    ("failover", "resubmitted"): 13,
    # v14 (round 21): the fleet survivability events — NEW at v14, so
    # gating their required fields keeps every committed v13-and-older
    # stream using these names validator-clean.
    ("reconcile", "backend"): 14,
    ("reconcile", "job_id"): 14,
    ("reconcile", "state"): 14,
    ("partition", "backend"): 14,
    ("recover", "jobs"): 14,
    # v15 (round 22): the distributed-tracing plane.  ``trace_id`` is
    # REQUIRED on every dispatcher hop record, every daemon job_*
    # lifecycle event, and every engine run_header (null outside a
    # traced fleet/daemon context on the header; the daemon mints its
    # own id for direct submits so job events always carry one) — and
    # the latency fields behind the ``ptt_*_seconds`` histogram
    # families ride the same records so stream replay re-bins
    # identically to the live scrape.  All gated at 15 so every
    # committed v14-and-older stream stays validator-clean.
    ("route", "trace_id"): 15,
    ("route", "route_ms"): 15,
    ("route", "ack_ms"): 15,
    ("replicate", "trace_id"): 15,
    ("replicate", "wall_ms"): 15,
    ("failover", "trace_ids"): 15,
    ("failover", "wall_ms"): 15,
    ("reconcile", "trace_id"): 15,
    ("partition", "wall_ms"): 15,
    ("job_submit", "trace_id"): 15,
    ("job_start", "trace_id"): 15,
    ("job_resume", "trace_id"): 15,
    ("job_suspend", "trace_id"): 15,
    ("job_result", "trace_id"): 15,
    ("job_cancel", "trace_id"): 15,
    ("run_header", "trace_id"): 15,
    # v16 (round 23): the kernel fields (IMPL_FIELDS) on every run
    # header (null on the host engines) — gated so every committed
    # v15-and-older stream stays validator-clean.
    ("run_header", "probe_impl"): 16,
    ("run_header", "expand_impl"): 16,
    ("run_header", "sieve_impl"): 16,
    ("admission", "action"): 10,
    ("admission", "tenant"): 10,
    ("auth", "action"): 10,
    ("deadline", "job_id"): 10,
    ("spill", "tier"): 9,
    ("spill", "keys_evicted"): 9,
    ("spill", "rows_evicted"): 9,
    ("spill", "bytes_raw"): 9,
    ("spill", "bytes_comp"): 9,
    ("spill", "transfer_s"): 9,
    ("spill", "misses_resolved"): 9,
}
EVENTS: Dict[str, Tuple[str, ...]] = {
    # run lifecycle (v8 adds profile_sig — historic, a constant
    # null since PR 48; v9 adds
    # hbm_budget — the tiered-store byte budget, null when untiered)
    "run_header": (
        "engine", "visited_impl", "config_sig", "profile_sig",
        "hbm_budget", "tenant", "mode", "warm", "trace_id",
        "probe_impl", "expand_impl", "sieve_impl",
    ),
    "result": ("distinct_states", "diameter", "wall_s", "truncated"),
    # progress
    "level": (
        "level", "new_states", "distinct_states", "frontier", "wall_s",
        "states_per_sec",
    ),
    "progress": ("distinct_states", "states_per_sec"),
    # dedup / fpset (deltas since the previous flush record)
    "flush": ("flushes", "probe_rounds", "failures", "valid_lanes"),
    "fpset_insert": ("inserts", "probe_rounds", "n"),
    # stream compaction (r10): per-stats-fetch deltas of the compact
    # dispatch counter, tagged with the active impl (logshift|sort);
    # PTT_STAGE_TIMING runs add ``drain_s`` for the per-stage table
    "compact": ("dispatches", "impl"),
    # fused level megakernel (r13): one record per dispatch — levels
    # closed inside the dispatch (>1 = a ramp batch) and the flush
    # groups it ran; the dispatch-count regression signal.  v7 (r14):
    # per-dispatch work-unit deltas from the in-kernel counters — the
    # cost-attribution inputs a fused run carries without a stage rerun
    "fuse": (
        "levels", "dispatches", "work_expand_rows", "work_probe_lanes",
        "work_compact_elems", "work_append_rows",
    ),
    # fused-era cost attribution (r14): the per-stage work-unit totals
    # a run accumulated — the machine-readable input to the calibrated
    # cost model (obs/attribution.py); one record right before result
    "attribution": ("stages",),
    # historic (r15 to PR 48): one record per knob adjustment of the
    # in-run controller; kept so that committed streams validate
    "tune": ("knob", "value"),
    # tiered state store (r16, store/): one record per eviction/spill
    # boundary with CUMULATIVE per-run counters — the tier the data
    # landed in (ram | ram+disk), keys/rows evicted, raw vs compressed
    # bytes, transfer seconds (D2H gather + encode + durable write),
    # and cold-tier misses resolved.  Cumulative so the validator's
    # monotone cross-check catches torn/re-based writers.
    "spill": (
        "tier", "keys_evicted", "rows_evicted", "bytes_raw",
        "bytes_comp", "transfer_s", "misses_resolved",
    ),
    # survivability (r9: ``retries`` is the frame writer's
    # transient-failure retry count — the ckpt_retries breadcrumb)
    "ckpt_frame": (
        "frame_seq", "bytes", "write_s", "retries", "distinct_states",
    ),
    "hbm_recovery": ("recovery_n",),
    "fault": ("kind", "site", "count"),
    # liveness edge-sweep progress (r9): one record per sweep chunk.
    # v7 (r14): cumulative sweep work units — merged-sort lanes,
    # gid-propagation pass-lanes, edge-compaction elements — the
    # sweep's cost-attribution inputs
    "sweep": (
        "chunk", "chunks", "swept", "edges", "sort_lanes", "prop_lanes",
        "compact_elems",
    ),
    # legacy differential stage timings (PTT_STAGE_TIMING runs)
    "stage_timing": ("stages",),
    # checking-as-a-service job lifecycle (r11, service/scheduler.py):
    # one submit -> N start/resume/suspend slices -> one result.  These
    # live in the DAEMON's stream (service.jsonl) under the daemon's
    # run_id; the per-job engine events stream separately under each
    # slice's engine run_id (docs/service.md)
    "job_submit": ("job_id", "spec", "trace_id"),
    "job_start": ("job_id", "spec", "slice", "trace_id"),
    "job_resume": ("job_id", "spec", "slice", "restore_s", "trace_id"),
    "job_suspend": ("job_id", "slice", "slice_wall_s", "trace_id"),
    "job_result": ("job_id", "status", "trace_id"),
    "job_cancel": ("job_id", "trace_id"),
    # daemon lifecycle: start (socket, pid, warmed specs) / stop
    "serve": ("action",),
    # swarm simulation (r18, sim/engine.py): one record per segment
    # dispatch with CUMULATIVE per-run counters — random steps taken
    # across the swarm, the (constant) walker count, walker-steps
    # with invariant failures, states visited, completed walks, and
    # the sampled-duplicate estimator.  Cumulative so the validator's
    # monotone cross-check catches torn/re-based writers (the same
    # contract as ``spill``).
    "sim": ("steps", "walkers", "violations"),
    # open-network hardening (r17, service/): one admission record
    # per submit decision — action in {admit, reject, shed, dedup},
    # reason in {queue_full, tenant_queued, tenant_running,
    # tenant_states} on rejections; auth records the TCP handshake
    # (accept carries the derived tenant); deadline records the
    # sweep cancelling an expired job (stop_reason="deadline")
    "admission": ("action", "tenant"),
    "auth": ("action",),
    "deadline": ("job_id",),
    # incremental checking (r19, warm/): one record per reuse decision
    # in the daemon's stream — ``phase`` distinguishes the submit-time
    # plan from the install-time outcome, ``mode`` is
    # continue/reseed/cold, ``reason`` the machine-readable cause
    # (sig_match / widened:AXIS / the typed cold-fallback reason)
    "warm": ("mode", "reason"),
    # fleet tier (r20, fleet/): the DISPATCHER's stream.  ``route`` is
    # one submit placement — the chosen backend and why (``reason`` in
    # {sticky, least_loaded, only_backend}); ``replicate`` is one
    # artifact sieve pass owner->peer — blobs shipped vs reused and
    # the delta-compressed wire bytes (0 blobs = the peer already held
    # everything, the sieve's whole point); ``failover`` is one
    # backend drain — the down backend and how many of its queued jobs
    # were resubmitted elsewhere through the submit_id dedup path
    "route": (
        "backend", "tenant", "trace_id", "route_ms", "ack_ms",
    ),
    "replicate": (
        "src", "dst", "blobs", "wire_bytes", "trace_id", "wall_ms",
    ),
    "failover": ("backend", "resubmitted", "trace_ids", "wall_ms"),
    # fleet survivability (r21, fleet/dispatcher.py): ``reconcile`` is
    # one lost job answered for by its rejoined backend — ``state`` is
    # the REAL state that replaced ``lost`` (done delivers the
    # backend's finished result; running resumes watch relay);
    # ``partition`` is one drained backend rejoining while still
    # holding its jobs (a partition window closed — a restarted
    # backend would have forgotten them); ``recover`` is one
    # ``dispatch --recover`` pass — persisted jobs reconciled against
    # every backend's authoritative job table (confirmed / adopted /
    # lost counts, plus whether a torn fleet_jobs.json was
    # quarantined first)
    "reconcile": ("backend", "job_id", "state", "trace_id"),
    "partition": ("backend", "wall_ms"),
    "recover": ("jobs",),
    # fleet observability plane (r22, fleet/dispatcher.py): NEW at
    # v15, so their required fields need no FIELD_SINCE gating (the
    # names cannot appear in older streams).  ``complete`` is the
    # dispatcher observing a routed job reach a terminal state —
    # ``e2e_ms`` is accept-to-observed-terminal, the end-to-end job
    # latency histogram's input; ``relay`` is one watch-relay leg
    # (owner re-resolution cadence, ``leg_ms``); ``hold`` / ``shed``
    # are the all-backends-down queue-and-hold admitting a submit
    # into the bounded buffer vs overflowing it with the typed
    # ``capacity`` rejection; ``persist_fail`` is a fleet_jobs.json
    # persist that stayed failed after the retry-once path (``n`` is
    # the cumulative counter, so replay derives the same
    # ptt_fleet_persist_failures_total a live scrape reports).
    "complete": ("job_id", "backend", "e2e_ms", "trace_id"),
    "relay": ("job_id", "leg_ms", "trace_id"),
    "hold": ("tenant", "held", "trace_id"),
    "shed": ("tenant", "held", "trace_id"),
    "persist_fail": ("n",),
}


def new_run_id() -> str:
    return uuid.uuid4().hex[:12]


class Telemetry:
    """One JSONL event stream (append-only, line-buffered, thread-safe).

    ``t`` is monotonic seconds since this object was created; the run
    header records the wall-clock anchor (``wall_unix``) once so humans
    can place the run in time without wall-clock jumps ever reordering
    records.
    """

    enabled = True

    def __init__(self, path: str, run_id: Optional[str] = None):
        self.path = path
        self.run_id = run_id or new_run_id()
        self._t0 = time.monotonic()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._lock = threading.Lock()
        self._seq = 0

    def emit(self, event: str, **fields) -> None:
        rec = {
            "v": SCHEMA_VERSION,
            "event": event,
            "t": 0.0,
            "run_id": self.run_id,
        }
        rec.update(fields)
        with self._lock:
            # timestamp UNDER the lock: the heartbeat thread and the
            # engine thread share this stream, and a t captured before
            # a lost lock race would violate the per-run monotonic-t
            # contract the schema validator enforces
            rec["t"] = round(time.monotonic() - self._t0, 6)
            rec["seq"] = self._seq
            self._seq += 1
            if self._f.closed:
                return
            # one write of one complete line: crash-durable up to the
            # record being written (see module docstring)
            self._f.write(json.dumps(rec) + "\n")

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class NullTelemetry:
    """No-op stand-in so engines never branch on "telemetry enabled"."""

    enabled = False
    path = None
    run_id = None

    def emit(self, event: str, **fields) -> None:
        pass

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = NullTelemetry()


def as_telemetry(
    t: Union[None, str, Telemetry, NullTelemetry],
    run_id: Optional[str] = None,
) -> Union[Telemetry, NullTelemetry]:
    """None -> the shared null sink; a path -> a fresh stream bound to
    ``run_id``; an existing Telemetry passes through unchanged (the
    caller keeps ownership — see :func:`owns_stream`)."""
    if t is None:
        return NULL
    if isinstance(t, (Telemetry, NullTelemetry)):
        return t
    return Telemetry(t, run_id=run_id)


def owns_stream(arg) -> bool:
    """True when :func:`as_telemetry` would CREATE the stream for this
    argument — i.e. the engine opened it and must close it.  A caller
    passing an existing Telemetry instance keeps ownership (it may be
    collecting several runs into one stream), so engines must not
    close it."""
    return not isinstance(arg, (Telemetry, NullTelemetry))


# ------------------------------------------------------------ heartbeat


class Heartbeat:
    """TLC-style periodic progress lines from the last fetched stats
    snapshot — ZERO device syncs added.

    The engine mutates ``snap`` (a plain dict: ``distinct_states``,
    ``level``, ``frontier``, optionally ``occupancy``) at points it
    already syncs (the stats fetch / level boundary); this thread wakes
    every ``every_s`` seconds, reads whatever snapshot is there, and
    reports — it never touches the device.  ``capacity`` (max_states)
    enables the ETA-to-capacity estimate from the recent rate.

    Shutdown contract (SIGTERM/preemption): the thread is a daemon and
    the engine stops it in a ``finally`` around the run loop, so a
    preempted run ends with a joined thread and a complete final line —
    never a heartbeat printing into a dead run (and ``os._exit`` style
    deaths can't be held up by it either).
    """

    def __init__(
        self,
        every_s: float,
        snap: dict,
        telemetry: Union[Telemetry, NullTelemetry] = NULL,
        capacity: Optional[int] = None,
        log: Optional[Callable[[str], None]] = None,
    ):
        if every_s <= 0:
            raise ValueError(f"heartbeat interval must be > 0: {every_s}")
        self.every_s = every_s
        self.snap = snap
        self.tel = telemetry
        self.capacity = capacity
        self._log = log
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.beats = 0
        # EWMA-smoothed rate (r14): fused dispatches close up to 8 ramp
        # levels between stats fetches, so the raw beat-over-beat rate
        # lurches at every fetch; the exponentially weighted average is
        # what the line and the ETA report.  None until the first beat.
        self.ewma_sps: Optional[float] = None
        # walks/s EWMA (r18): simulation engines put a cumulative
        # ``walks`` count in the snapshot — completed behaviors land
        # B-at-a-time per round, the chunkiest counter there is, so
        # the reported walks/s is always the smoothed estimate
        self.ewma_wps: Optional[float] = None
        self._prev_walks: Optional[Tuple[float, int]] = None

    # EWMA weight of the newest beat-over-beat rate sample: ~0.3 keeps
    # the line responsive (half-life ~2 beats) while absorbing the
    # fuse-batch sawtooth
    EWMA_ALPHA = 0.3

    def _emit_line(self, msg: str) -> None:
        if self._log is not None:
            self._log(msg)
        else:
            import sys

            print(msg, file=sys.stderr, flush=True)

    def _beat(self, t_start: float, prev: Tuple[float, int]):
        now = time.monotonic()
        nv = int(self.snap.get("distinct_states", 0))
        level = self.snap.get("level")
        frontier = self.snap.get("frontier")
        occ = self.snap.get("occupancy")
        gen = self.snap.get("generated")
        elapsed = max(now - t_start, 1e-9)
        avg_sps = nv / elapsed
        dt = max(now - prev[0], 1e-9)
        recent_sps = max(nv - prev[1], 0) / dt
        # EWMA across fuse batches (r14): a ramp dispatch lands up to
        # 8 levels of states in one fetch, so the raw sample sawtooths;
        # smooth it and drive the ETA from the smoothed estimate
        if self.ewma_sps is None:
            self.ewma_sps = recent_sps
        else:
            self.ewma_sps = (
                self.EWMA_ALPHA * recent_sps
                + (1.0 - self.EWMA_ALPHA) * self.ewma_sps
            )
        # simulation engines (r18): cumulative completed-walk count in
        # the snapshot -> a smoothed walks/s beside the state rate
        walks = self.snap.get("walks")
        if walks is not None:
            walks = int(walks)
            if self._prev_walks is None:
                self._prev_walks = (t_start, 0)
            dwt = max(now - self._prev_walks[0], 1e-9)
            recent_wps = max(walks - self._prev_walks[1], 0) / dwt
            self.ewma_wps = (
                recent_wps
                if self.ewma_wps is None
                else self.EWMA_ALPHA * recent_wps
                + (1.0 - self.EWMA_ALPHA) * self.ewma_wps
            )
            self._prev_walks = (now, walks)
        # the engine tags its snapshot ``partial`` when the last level
        # record was an intra-level anchor — mark the line so a reader
        # knows the level/frontier figures are mid-level
        partial = bool(self.snap.get("partial"))
        eta_s = None
        if self.capacity and self.ewma_sps > 0:
            eta_s = (self.capacity - nv) / self.ewma_sps
        msg = (
            f"Progress({level if level is not None else '?'}"
            + ("~" if partial else "")
            + f") at {elapsed:.0f}s: "
            + (f"{int(gen):,} states generated, " if gen is not None else "")
            # a simulation snapshot (walks present) counts VISITED
            # states — the swarm never dedups, so "distinct" would lie
            + (
                f"{nv:,} states visited"
                if walks is not None
                else f"{nv:,} distinct states"
            )
            + (f", frontier {int(frontier):,}" if frontier is not None else "")
            + f", {self.ewma_sps:,.0f} st/s (avg {avg_sps:,.0f})"
            + (
                f", {walks:,} walks ({self.ewma_wps:,.1f} walks/s)"
                if walks is not None and self.ewma_wps is not None
                else ""
            )
            + (f", fpset occupancy {occ:.1%}" if occ is not None else "")
            + (
                f", ~{eta_s:.0f}s to the state cap"
                if eta_s is not None and eta_s >= 0
                else ""
            )
        )
        self._emit_line(msg)
        self.tel.emit(
            "progress",
            distinct_states=nv,
            states_per_sec=round(recent_sps, 1),
            states_per_sec_ewma=round(self.ewma_sps, 1),
            avg_states_per_sec=round(avg_sps, 1),
            **({"partial": True} if partial else {}),
            **(
                {
                    "walks": walks,
                    "walks_per_sec_ewma": round(self.ewma_wps, 2),
                }
                if walks is not None and self.ewma_wps is not None
                else {}
            ),
            **({"generated": int(gen)} if gen is not None else {}),
            **({"level": level} if level is not None else {}),
            **(
                {"frontier": int(frontier)}
                if frontier is not None
                else {}
            ),
            **({"occupancy": occ} if occ is not None else {}),
            **({"eta_capacity_s": round(eta_s, 1)} if eta_s else {}),
        )
        self.beats += 1
        return (now, nv)

    def _loop(self):
        t_start = time.monotonic()
        prev = (t_start, int(self.snap.get("distinct_states", 0)))
        while not self._stop.wait(self.every_s):
            prev = self._beat(t_start, prev)

    def start(self) -> "Heartbeat":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="ptt-heartbeat", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.every_s + 1.0)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False


def parse_level_window(spec: str) -> Tuple[int, int]:
    """Parse an xprof level window ``"LO:HI"`` -> (lo, hi); raises
    ValueError with a usable message on malformed or inverted input
    (shared by the CLI and bench front-ends)."""
    try:
        lo_s, hi_s = spec.split(":", 1)
        lo, hi = int(lo_s), int(hi_s)
    except ValueError:
        raise ValueError(
            f"bad level window {spec!r} (want LO:HI, e.g. 7:7)"
        ) from None
    if lo > hi:
        raise ValueError(
            f"bad level window {spec!r} (LO must be <= HI)"
        )
    return lo, hi


# ------------------------------------------------------------ RTT probe


def measure_rtt(n: int = 3) -> float:
    """One-time host<->device round-trip probe (seconds).

    Fetches a freshly computed device scalar ``n`` times and returns
    the MINIMUM wall time — the first fetch may pay a (cached
    thereafter) compile, and min is the honest latency floor the
    ``_stage_mark`` barrier pays per drain: a dispatch plus a fetch,
    measured on whatever device is in use.  Called once at warmup;
    the report layer subtracts ``stage_<name>_n x rtt`` from legacy
    stage timings (docs/observability.md).
    """
    import jax.numpy as jnp
    import numpy as np

    best = float("inf")
    y = jnp.int32(0)
    for _ in range(max(n, 1)):
        y = y + jnp.int32(1)  # a fresh value: the fetch cannot be cached
        t0 = time.perf_counter()
        np.asarray(y)
        best = min(best, time.perf_counter() - t0)
    return best
