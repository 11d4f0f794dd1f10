"""Fully device-resident BFS checker — the single-chip throughput engine.

Motivation:

- every host<->device sync serializes the pipeline, so per-chunk host
  involvement is kept off the hot path entirely;
- random-access gathers are latency-bound on the device — the design
  keeps every hot-path operation but the table probe a contiguous copy
  or a contiguous-index scatter;
- dispatch is async: the host enqueues work far ahead and fetches one
  small stats vector per group of flushes.

Layout:

- **Candidate accumulator**: expand sub-batches append their candidate
  keys + packed rows into an HBM accumulator (``ACAP = flush_factor *
  G * A`` lanes); the visited-set probe ("flush", ``ops/fpset.py``)
  runs once per accumulator fill.
- **Row store instead of frontier double-buffering**: all discovered
  states live in one append-only packed-row store in gid order; a BFS
  level is just a contiguous gid range, so expand windows are
  contiguous slices (no gathers) and trace reconstruction reads rows
  directly.
- **Fingerprint keys sized to the state** (``ops.dedup.KeySpec``):
  exact 2-column keys for <64-bit states, exact 3-column for <96, and
  64-bit murmur3 fingerprints (TLC's fingerprint-width regime, with
  the collision probability reported like TLC does) for wide states.
- **Invariants evaluate at append time on deduped new states only**;
  invariant work drops by the duplication factor for free.

Counterexample traces: the per-state ``(parent gid, action lane)`` log
is appended by the same scatter as the rows; a trace is reconstructed by
walking the parent chain on device (one fetch) and replaying lanes
through the model on the host (SURVEY.md §2.2-E7).

Round-13 fusion (``fuse="level"``, the default): the per-level stage
chain (expand -> fpset lookup_or_insert -> stream compact -> append,
each its own jitted dispatch since round 10) collapses into ONE
megakernel dispatch per level — ``_fused_jit`` chains the identical
traced sub-functions (``ops.fpset.flush_acc``, ``ops.compact.
compact_rows``, the expand/append bodies of ``engine/bodies.py``) with
every buffer donated end-to-end, and a ``lax.while_loop`` walks flush
groups AND level boundaries inside the dispatch.  Small consecutive levels (the
dispatch-bound ramp: frontiers at or below one expand window) batch up
to ``fuse_group`` levels per dispatch, with early exit on frontier
growth past the window, violation/deadlock, or capacity; the kernel
returns per-level sizes so host-side level accounting, telemetry
``level`` records, checkpoint frames, and ``PTT_FAULT`` level/flush
sites replay exactly.  Steady-state levels therefore cost 1 dispatch +
1 stats fetch (the kernel returns the stats vector — no separate stats
dispatch), and the whole ramp costs 1.  ``fuse="stage"`` keeps the
per-stage chain (the tiered store's path under pressure, and the
reference the fused kernel is held to); discovery order is identical
state-for-state either way (same flush partition, same lane ids, same
min-lane-wins dedup).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pulsar_tlaplus_tpu.engine import bodies
from pulsar_tlaplus_tpu.engine.bfs import CheckerResult
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.store import budget as store_budget
from pulsar_tlaplus_tpu.store import sieve as store_sieve
from pulsar_tlaplus_tpu.store.tiers import TieredStore
from pulsar_tlaplus_tpu.utils import ckpt, faults, recovery
from pulsar_tlaplus_tpu.ops import compact as compact_ops
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL, KeySpec
from pulsar_tlaplus_tpu.ref import pyeval

# one object: JAX merges a program's array constants by identity, and
# the traced bodies compare against the same sentinel as the programs
# around them
BIG = bodies.BIG

# Zero-sync device counters (round 8): the fpset metrics vector rides
# the ONE hot-path stats fetch — [flushes, probe_rounds, failures,
# valid_lanes_lo, max_probe_rounds, valid_lanes_hi].  valid_lanes is
# the candidate count after validity masking (the duplicate-rate
# denominator the host cannot know without a sync), carried as hi/lo
# uint32 words since r12 so it survives past 2.1G candidate lanes
# (``fpset.fpm_update`` owns the carry, ``fpset.fpm_logical`` the
# host-side 64-bit view); max_probe_rounds is the worst flush's probe
# depth (a running max, not a sum).  Pre-widening checkpoint frames
# carry the 3- or 5-wide prefix and restore zero-padded.  Shared with
# the sharded engine via ops/fpset.py (r9); this engine's vector also
# carries the probe rounds run at each step of the ladder in the
# ``fpset.FPM_STEPS`` words behind those (PR 37, ``fpset_step_rounds``)
# and, in the word after them, the lanes its narrow rounds kept from
# the table's column scatters (PR 42, ``fpset_write_lanes``).
FPM_N = fpset.FPM_WIDE_N

# In-kernel work-unit vector (round 14, fused-era cost attribution):
# the level megakernel accumulates per-stage work units — live expand
# rows, presented probe lanes (hi/lo), compacted elements (hi/lo),
# appended rows, while-iterations — and returns them in the packed
# stats vector, so per-stage cost attribution survives fusion with
# zero extra syncs.  The stage chain counts the identical units
# host-side at its dispatch sites (``_work_add``), so fused and stage
# totals are equal state-for-state (pinned in tests).
WKM_N = fpset.WKM_N


def _scoped(scope: str, name: str, fn):
    """``fn`` traced under the stage scope ``scope``, as a program
    named ``name`` (``jax.jit`` names a program after its function)."""
    out = spans.staged(scope)(fn)
    out.__name__ = out.__qualname__ = name
    return out


# the least length the tiered store fetches from the device: a fetch is
# the next power of two at or over what is needed, from here up to the
# buffer's own length, so a run meets a handful of fetch programs
SPILL_FETCH_MIN = 1 << 12
# sieved keys a cold-lookup batch under a budget (one fetch and one
# ``np.searchsorted`` pass over the cold runs a batch)
MISS_BATCH = 1 << 15
# share of ``hbm_budget`` kept back against transients: the tier
# ceilings are walked inside ``budget * (1 - HBM_HEADROOM)``
HBM_HEADROOM = 0.1


@functools.partial(jax.jit, static_argnames=("size",))
def ptt_spill_fetch(buf, start, *, size):
    """``buf[start: start + size]``: the slice a spill fetch brings to
    the host, one program a ``(buffer, size)`` (``start`` is traced)."""
    with spans.stage("spill_fetch"):
        return lax.dynamic_slice(buf, (start,), (size,))


@functools.partial(jax.jit, static_argnames=("size",))
def ptt_spill_fetch_cols(cols, start, *, size):
    """``[c[start: start + size] for c in cols]`` as ONE flat array of
    ``len(cols) * size`` ``uint32`` words, column after column: what a
    fetch of several equal-length columns brings to the host in one
    transfer (``int32`` columns cross as their bits; the host views them
    back).  One-dimensional, the layout of every other fetch: a
    ``[len(cols), size]`` result pads to the chip's tiles.  One program
    a ``(columns, size)``; ``start`` is traced."""
    with spans.stage("spill_fetch"):
        return jnp.concatenate([
            lax.dynamic_slice(
                c if c.dtype == jnp.uint32
                else lax.bitcast_convert_type(c, jnp.uint32),
                (start,), (size,),
            )
            for c in cols
        ])


class _Growth:
    """What one ``run()`` grew, counted on the host at the growth sites
    (no dispatch, sync or fetch of their own; ``last_stats`` carries
    them as ``grow_*``, docs/observability.md)."""

    def __init__(self):
        self.events = 0  # outermost growth calls that moved a capacity
        self.rehashes = 0  # table doublings
        self.rehash_slots = 0  # slots of every OLD table rehashed
        self.rehash_keys = 0  # occupied ones among them: the keys moved
        # lanes presented to the NEW tables summed over probe rounds;
        # over rehash_keys: the rehash's lanes_presented_per_valid
        self.rehash_lane_rounds = 0
        self.copy_bytes = 0  # bytes of the old rows and logs copied


def _growth_call(fn):
    """Method decorator of the growers: the exclusive host phase
    ``grow`` and, for the outermost of nested growth calls
    (``_grow_fused`` > ``_grow_store`` > ``_grow_logs``), one
    ``grow_events`` if any of (TCAP, LCAP, PCAP) moved.  (Not
    ``spans.in_phase`` under a second wrapper: a grower's first call at
    a tier traces ``ptt_rehash2`` / ``ptt_grow``, and every frame above
    a traced equation is on its traceback.)"""

    @functools.wraps(fn)
    def wrapped(self, bufs, need):
        outermost = not self._clock.open("grow")
        tiers = (self.TCAP, self.LCAP, self.PCAP)
        try:
            with self._clock.phase("grow"):
                return fn(self, bufs, need)
        finally:
            if outermost and (self.TCAP, self.LCAP, self.PCAP) != tiers:
                self._growth.events += 1

    return wrapped


class DeviceChecker:
    """Level-synchronous BFS on one device with no hot-path host syncs.

    Shapes are static per capacity tier: ``G`` frontier states per
    expand window produce ``NCs = G * A`` candidate lanes appended to
    the accumulator; a flush probes ``ACAP`` keys into the table.  The
    host grows the table / the row store between flushes (geometric
    tiers, re-jitting per tier via the jit cache).

    Beside the ``CheckerResult`` a run leaves two things on the
    checker, both part of its result: ``last_stats`` (the run's
    counters; the telemetry ``result`` event carries all of them,
    the ``host_<phase>_s`` and ``jit_*`` keys of ``obs/spans.py``
    among them) and ``last_bufs`` — the device buffers as the run
    left them: ``rows`` (packed states in gid order, windowed by
    ``rows_window``), ``parent`` and ``lane`` (the trace logs, one
    entry per gid), ``vk`` (the visited set).  The liveness engine,
    the tests and the benchmark's sample replay read them; set it to
    None to free the device memory.
    """

    def __init__(
        self,
        model,
        invariants: Optional[Tuple[str, ...]] = None,
        check_deadlock: bool = True,
        sub_batch: int = 8192,
        expand_chunk: Optional[int] = None,
        visited_cap: int = 1 << 16,
        frontier_cap: Optional[int] = None,
        max_states: int = 1 << 26,
        time_budget_s: Optional[float] = None,
        progress: bool = False,
        metrics_path: Optional[str] = None,
        group: int = 4,
        flush_factor: int = 1,
        fp_bits: Optional[int] = None,
        append_chunk: Optional[int] = None,
        seed_cap: Optional[int] = None,
        rows_window: str = "all",
        row_cap_states: Optional[int] = None,
        fuse: str = "level",
        fuse_group: Optional[int] = None,
        fpset_dense_rounds: Optional[int] = None,
        fpset_stages=None,
        hbm_budget=None,
        spill_dir: Optional[str] = None,
        spill_compress: Optional[bool] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 5,
        telemetry=None,
        heartbeat_s: Optional[float] = None,
        xprof_dir: Optional[str] = None,
        xprof_levels: Optional[Tuple[int, int]] = None,
        suspend_hook=None,
    ):
        self.model = model
        # cooperative suspend (checking-as-a-service): polled at level
        # boundaries; returning "suspended" writes a resumable frame
        # and exits with that stop_reason (the daemon's mesh
        # time-slicing), "cancelled" exits without one.  Reassignable
        # between run() calls — the service scheduler re-targets one
        # pooled (warmed) checker at successive jobs.
        self.suspend_hook = suspend_hook
        self.layout = model.layout
        if invariants is None:
            invariants = getattr(
                model, "default_invariants", pyeval.DEFAULT_INVARIANTS
            )
        self.invariant_names = tuple(invariants)
        # compiled specs surface evaluation errors (TLC semantics) via
        # the auto-invariant __EvalError__; an explicit invariant list
        # must not silently drop it, or a reachable state whose
        # invariant evaluation errors would pass unreported
        model_invs = getattr(model, "invariants", None)
        if (
            model_invs is not None
            and "__EvalError__" in model_invs
            and "__EvalError__" not in self.invariant_names
        ):
            self.invariant_names += ("__EvalError__",)
        self.check_deadlock = check_deadlock
        self.hbm_budget = store_budget.resolve_budget(hbm_budget)
        self.tiered = self.hbm_budget is not None
        self.A = model.A
        self.W = self.layout.W
        self.G = sub_batch
        self.Fi = expand_chunk or min(sub_batch, 8192)
        if self.G % self.Fi:
            raise ValueError("sub_batch must be a multiple of expand_chunk")
        self.NCs = self.G * self.A
        self.FLUSH = flush_factor
        self.ACAP = self.NCs * flush_factor
        if self.ACAP * self.W >= 1 << 31:
            # flat accumulator offsets (acc_off * W, idx * W) are int32
            raise ValueError(
                "accumulator exceeds int32 flat addressing: "
                "sub_batch * A * flush_factor * W must stay below 2^31"
            )
        # append scan chunking: C blind DUS windows of SLc rows cover
        # [n_visited, n_visited + APAD); capacity bounds use APAD
        if append_chunk is not None:
            self.SL = append_chunk
        self.SLc = min(self.SL, self.ACAP)
        self.C = -(-self.ACAP // self.SLc)
        self.APAD = self.C * self.SLc
        self.keys = KeySpec(self.layout.total_bits, self.W, fp_bits)
        self.K = self.keys.ncols
        if fp_bits is None:
            self.keys.warn_if_hashed(max_states)
        self.SCAP = max_states
        # the visited set can never hold more than max_states + one
        # accumulator of candidates, so cap the power-of-two tier there
        # (a 40M-state run would otherwise pay a 67M-wide flush sort)
        self.VCAP = min(
            self._round_cap(visited_cap),
            max(max_states + self.ACAP, self.ACAP * 2),
        )
        # Level fusion (round 13 tentpole): "level" (default) runs each
        # BFS level as ONE fused megakernel dispatch (ramp levels batch
        # several levels per dispatch — see the module docstring);
        # "stage" runs the per-stage dispatch chain (the tiered store
        # falls back to it under pressure).
        if fuse not in ("level", "stage"):
            raise ValueError(f"fuse must be level|stage: {fuse}")
        self.fuse = fuse
        # ramp batch depth: max levels one fused dispatch may close
        # (static — it shapes the kernel's per-level size vector).  The
        # cost model batches only while the frontier fits one expand
        # window (auto, the r10 --sweep-group pattern); an explicit
        # fuse_group caps or disables (1) the batching.
        if fuse_group is not None and fuse_group < 1:
            raise ValueError(f"fuse_group must be >= 1: {fuse_group}")
        self.RMAX = min(fuse_group or 8, 64)
        # fpset probe schedule: the caller's own, else ops/fpset.py's
        # defaults
        self.fps_dense, self.fps_stages = fpset.resolve_schedule(
            fpset_dense_rounds, fpset_stages
        )
        # The visited set is the HBM-resident hash-table FPSet
        # (ops/fpset.py).  ``VCAP`` is the max states admissible before
        # growth; the table carries ``TCAP = 2 * VCAP`` slots so the
        # run-loop bound ``nv_bound <= VCAP`` IS the load-factor <= 1/2
        # contract.
        t = 1 << 11
        while t < 2 * self.VCAP:
            t <<= 1
        self.TCAP = t
        self.VCAP = t // 2
        # Row-store policy (round 5, VERDICT r4 #2 — break the HBM wall):
        #
        # - ``rows_window="all"`` (default): every discovered state's
        #   packed row is kept for the whole run (liveness needs this;
        #   small runs don't care).  Rows + logs grow together toward
        #   SCAP as before.
        # - ``rows_window="frontier"``: packed rows are a SLIDING WINDOW
        #   — the current frontier plus as much of the level being built
        #   as fits ``row_cap_states``.  Rows older than the frontier
        #   are dropped at each level boundary (a chunked device-side
        #   copy shifts the frontier to offset 0); if the level being
        #   built outgrows the window, its row writes divert to a
        #   scratch region and the run CONTINUES deduping / counting /
        #   checking invariants — it only stops (stop_reason
        #   "row_window") if that level completes and would have to be
        #   expanded.  Counterexample traces never needed rows (the
        #   parent/lane logs + host replay reconstruct them), so
        #   safety-mode checking loses nothing until a level completes
        #   with lost rows.  This is the TPU answer to TLC's disk-spill
        #   tier: at bench shapes the run is bounded by wall clock, not
        #   by holding 80 B/state forever (a 60 M-state run kept 5.4 GB
        #   of rows it would never read).
        if rows_window not in ("all", "frontier"):
            raise ValueError(f"rows_window must be all|frontier: {rows_window}")
        self.rows_window = rows_window
        if rows_window == "frontier":
            rc = row_cap_states or (self.NCs + self.APAD)
            # the window must admit one frontier's expand-window slack
            # (G rows past the frontier end) plus one blind APAD append
            # window diverted to the tail scratch region
            self.LCAP = max(rc, self.NCs) + self.APAD
        else:
            # rows + trace logs grow geometrically toward SCAP
            # (allocating max_states-sized stores up front would waste
            # GBs on small runs); ``frontier_cap`` is a sizing hint
            self.LCAP = max(
                min(
                    self._round_cap(
                        max(visited_cap, frontier_cap or 0, self.NCs)
                    ),
                    max(max_states, self.NCs) + self.APAD,
                ),
                # the very first append writes a blind APAD window at 0,
                # so no tier below APAD is ever usable (and warmup
                # compiles at the initial tier)
                self.APAD,
            )
        # trace logs (parent gid + action lane per state) are kept for
        # EVERY state in both modes — they are what traces replay from.
        # In frontier mode they are presized to SCAP + one append window
        # outright: at 8 B/state the full-size buffers are cheap, and
        # tiered growth would recompile the (expensive) append program
        # per tier for no runtime win.
        self.PCAP = (
            self.LCAP
            if rows_window == "all"
            else max_states + self.APAD
        )
        # shift-copy chunk: <= one append window so the tail padding
        # bound below holds; rows buffers carry SHIFT_CW pad words in
        # frontier mode (see _shift_jit)
        self.SHIFT_CW = min(1 << 24, self.APAD * self.W)
        # the seed loader's blind DUS window must fit small frontier
        # windows too (bench-scale APAD dwarfs it, so no change there)
        self.SEED_CHUNK = min(DeviceChecker.SEED_CHUNK, self.APAD)
        # ---- tiered state store (round 16, store/): a byte budget for
        # everything device-resident.  Growth sites consult the budget
        # instead of truncating: the fpset table stops doubling at the
        # budget-derived tier and evicts cold generations to the host
        # store; the row/log stores become sliding windows whose aged
        # ranges spill at level boundaries.  docs/memory.md.
        self.spill_compress = (
            True if spill_compress is None else bool(spill_compress)
        )
        self._spill_dir_arg = spill_dir
        self.tstore: Optional[TieredStore] = None
        # log-shift chunk (tiered log windows slide like the rows)
        self.LOG_CW = min(1 << 22, self.APAD)
        if self.tiered:
            if self.rows_window != "all":
                raise ValueError(
                    "hbm_budget and rows_window='frontier' are "
                    "mutually exclusive — the tiered store IS the "
                    "row-window story (aged rows spill instead of "
                    "dropping)"
                )
            # budget-derived tier ceilings: round-robin doubling from
            # the initial tiers while the worst-case resident bytes
            # stay inside the effective budget — deterministic, so
            # prewarm walks exactly the reachable (capped) staircase
            eff = int(self.hbm_budget * (1.0 - HBM_HEADROOM))
            capv_abs = max(self.SCAP + self.ACAP, self.ACAP * 2)
            capl_abs = max(
                self.SCAP + self.APAD, self.NCs + self.APAD
            )
            tc, lc, pc = self.TCAP, self.LCAP, self.PCAP
            if self._device_bytes_est(tc, lc, pc) > eff:
                raise ValueError(
                    "hbm_budget too small: the initial tiers need "
                    f"{store_budget.fmt_bytes(self._device_bytes_est(tc, lc, pc))}"
                    f" (+{HBM_HEADROOM:.0%} headroom) but the "
                    f"budget is {store_budget.fmt_bytes(self.hbm_budget)}"
                    " — raise the budget or shrink sub_batch/"
                    "visited_cap"
                )
            while True:
                grew = False
                if (
                    tc // 2 < capv_abs
                    and self._device_bytes_est(tc * 2, lc, pc) <= eff
                ):
                    tc *= 2
                    grew = True
                nl = self._next_cap(lc, lc + 1, capl_abs)
                if nl > lc and self._device_bytes_est(tc, nl, pc) <= eff:
                    lc = nl
                    grew = True
                npc = self._next_cap(pc, pc + 1, capl_abs)
                if npc > pc and self._device_bytes_est(tc, lc, npc) <= eff:
                    pc = npc
                    grew = True
                if not grew:
                    break
            # structural floor: the run loop's in-flight contract
            # needs the hot table to absorb at least two accumulators
            # past any hot count eviction can reach — a budget below
            # that tier is honored as closely as possible, never
            # exactly (the viability check above catches gross cases)
            while tc // 2 < 2 * self.ACAP:
                tc *= 2
            self._tcap_max, self._lcap_max, self._pcap_max = tc, lc, pc
            # clamp the dispatch group-ahead so a full group of
            # in-flight flushes fits the BUDGETED table: otherwise
            # every growth site would be forced past the budget and
            # the hot tier could never stay small (the whole point)
            group = max(
                1, min(group, tc // 2 // self.ACAP - 1)
            )
            # the ceilings the budget gave, as the result reports them:
            # an override moves ``_tcap_max`` / ``_lcap_max`` / ``_pcap_max``
            self._tier_ceilings = [tc, lc, pc]
        self._reset_spill_state()
        max_rows = (
            self.LCAP if rows_window == "frontier"
            else self._lcap_max if self.tiered
            else max(max_states, self.NCs) + self.APAD
        )
        if max_rows * self.W >= 1 << 31:
            raise ValueError(
                "row store exceeds int32 flat addressing: reduce "
                "max_states (or use rows_window='frontier'; rows x W "
                "words must stay below 2^31 elements)"
            )
        if max_states + self.APAD >= 1 << 31:
            raise ValueError("trace logs exceed int32 addressing")
        self.time_budget_s = time_budget_s
        self.progress = progress
        self.metrics_path = metrics_path
        # armed/recovered/degraded bookkeeping shared with the sharded
        # engine (utils/recovery.py); ``group`` (the dispatch
        # group-ahead) lives there because recovery halves it
        self.rec = recovery.RecoveryState(checkpoint_path, group)
        # ``seed_cap`` sized the sorted columns of a seed-merge path
        # that is gone: the seed inserts straight into the main table.
        # The benchmark's configuration still passes it
        # (benchmark/configs/compaction-scaled.json), so it is accepted
        # and unused.
        del seed_cap
        # run-survivability state (round 7): level-boundary checkpoint
        # frames shared with the sharded engine via utils/ckpt.py,
        # HBM-exhaustion recovery (utils/recovery.py), and
        # preemption-safe shutdown
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        # incremental checking (warm/): write one frame at CLEAN
        # completion too (empty frontier) so a completed run leaves a
        # reseed-able warm artifact — budget truncations already frame
        self.final_frame = False
        # a warm-RESEEDED run's seed merges the artifact's trailing
        # levels into one frontier level, so its level count no longer
        # bounds the parent-chain depth — the installer raises this by
        # the artifact's original level count so trace walks reach the
        # roots (warm/plan.build_reseed_seed)
        self.extra_trace_depth = 0
        self._reset_ckpt_state()
        self._watcher = None
        self._flush_seq = 0
        self._jits: Dict[tuple, object] = {}
        self.last_stats: Dict[str, float] = {}
        self.last_bufs = None  # part of the result: the class docstring
        # the host-phase clock of the current run (a fresh one per
        # run(); this one serves growth sites reached outside a run)
        self._clock = spans.PhaseClock()
        self._growth = _Growth()
        # telemetry (round 8): a path or obs.telemetry.Telemetry; the
        # stream is opened per run() with a fresh run_id, and the
        # heartbeat reports from ``_snap`` — the last fetched stats
        # snapshot — so neither adds a device sync
        self._telemetry_arg = telemetry
        self.tel = obs.NULL
        self.heartbeat_s = heartbeat_s
        self.xprof_dir = xprof_dir
        self.xprof_levels = (
            tuple(int(x) for x in xprof_levels) if xprof_levels else None
        )
        self._xprof_on = False
        self._xprof_done = False
        self._run_id: Optional[str] = None
        self._snap: Dict[str, object] = {}
        self._fetch_n = 0
        self._fpm_prev = np.zeros((fpset.FPM_LOGICAL_N,), np.int64)
        self._compact_prev = 0
        self._compact_prev_s = 0.0
        self._resume_meta: Dict[str, object] = {}
        # PTT_STAGE_TIMING=1: drain after every dispatch and charge the
        # wait to per-stage counters — the LEGACY differential mode
        # (serializes the pipeline; each drain pays one host<->device
        # round trip, which the report layer subtracts via ``rtt_s``).
        # Dispatch counts (``stage_<name>_n``) are free host-side
        # counters and ride regardless.
        self._stage_timing = os.environ.get(
            "PTT_STAGE_TIMING", "0"
        ) not in ("", "0")

    # -------------------------------------------------------------- util

    # recovery bookkeeping delegates (utils/recovery.py is the one
    # source of truth; these keep the engine's established names)
    @property
    def group(self) -> int:
        return self.rec.group

    @property
    def _hbm_recovered(self) -> int:
        return self.rec.hbm_recovered

    @property
    def _headroom_frozen(self) -> bool:
        return self.rec.headroom_frozen

    def _round_cap(self, c: int) -> int:
        n = 1 << 10
        while n < c:
            n <<= 1
        return n

    # ----------------------------------------------- tiered-store sizing

    def _reset_spill_state(self) -> None:
        """The tiered store's per-run state: epochs, the hot tier's
        count and its peak, the fetch counters."""
        self._spill_active = False
        self._epoch = 1
        self._hot_max = 0
        self._hot_n = 0
        self._spill_sync_n = 0
        self._spill_emit_mark = 0
        self._spill_degraded_emitted = False
        self._budget_overridden = False
        self._spill_fetch_s = 0.0
        self._spill_fetches = 0
        self._spill_fetch_planes = 0
        self._spill_d2h_bytes = 0
        self._spill_d2h_padded_bytes = 0
        self._spill_evict_slots = 0

    @property
    def _hot_n(self) -> int:
        """Keys in the hot table (every assignment keeps the run's
        peak, ``spill_hot_keys_max``)."""
        return self._hot_keys

    @_hot_n.setter
    def _hot_n(self, n: int) -> None:
        self._hot_keys = n
        self._hot_max = max(self._hot_max, n)

    def _device_bytes_est(self, tcap: int, lcap: int, pcap: int) -> int:
        """Worst-case resident bytes at a (TCAP, LCAP, PCAP) tier
        triple: the fpset key columns + generation column, the padded
        row/log windows, and the fixed accumulator buffers.  This is
        what the budget caps — the arithmetic behind every
        grow-or-spill decision (docs/memory.md)."""
        fixed = (self.K + self.W) * self.ACAP * 4
        table = (tcap + 1) * (self.K + 1) * 4
        rows = (lcap * self.W + self.SHIFT_CW) * 4
        logs = 2 * (pcap + self.LOG_CW) * 4
        return fixed + table + rows + logs

    def _capv(self) -> int:
        """Max states the visited tier may ever admit: the run-
        reachable formula, budget-clamped in tiered mode (the capacity
        guard consults the tier budget instead of truncating)."""
        cap = max(self.SCAP + self.ACAP, self.ACAP * 2)
        if self.tiered:
            cap = min(cap, self._tcap_max // 2)
        return cap

    def _capl(self) -> int:
        """Max row-store states (budget-clamped window in tiered mode)."""
        cap = max(self.SCAP + self.APAD, self.NCs + self.APAD)
        if self.tiered:
            cap = min(cap, self._lcap_max)
        return cap

    def _capp(self) -> int:
        """Max trace-log states (budget-clamped window in tiered mode)."""
        cap = max(self.SCAP + self.APAD, self.NCs + self.APAD)
        if self.tiered:
            cap = min(cap, self._pcap_max)
        return cap

    def _log(self, msg: str):
        if self.progress:
            import sys

            print(f"  {msg}", file=sys.stderr, flush=True)

    def _dispatch_total(self) -> int:
        """Sum of every ``stage_<name>_n`` dispatch counter — one
        definition for the run-start baseline AND the result's
        ``dispatches_per_level`` numerator."""
        return sum(
            int(v)
            for k, v in self.last_stats.items()
            if k.startswith("stage_") and k.endswith("_n")
        )

    def _stage_mark(self, name: str, out):
        """One more dispatch of the stage ``name``: ``stage_<name>_n``,
        the free host-side counters ``dispatches_per_level`` sums (the
        clock's ``calls`` of the stage's programs are the same numbers,
        held to each other in the tests).  A stage is TIMED elsewhere:
        on the device by the ``ptt.`` scopes of a trace, on the host by
        the site's ``clock.call`` / ``clock.upload`` (obs/spans.py).
        ``PTT_STAGE_TIMING=1`` is kept for the attribution model's
        calibration alone (obs/attribution.py): it also blocks on
        ``out`` here and charges the wait to ``stage_<name>_s``, which
        serializes the pipeline it measures; each drain pays one
        host<->device round trip, and ``rtt_s`` (measured once at
        warmup) is in ``last_stats`` so the report layer subtracts
        ``stage_<name>_n x rtt_s``."""
        self.last_stats[f"stage_{name}_n"] = (
            self.last_stats.get(f"stage_{name}_n", 0) + 1
        )
        if not self._stage_timing:
            return out
        t0 = time.perf_counter()
        jax.block_until_ready(out)
        self.last_stats[f"stage_{name}_s"] = (
            self.last_stats.get(f"stage_{name}_s", 0.0)
            + time.perf_counter() - t0
        )
        return out

    def _work_add(self, **units):
        """Accumulate per-run work units (r14, fused-era cost
        attribution) into ``last_stats`` as ``work_<name>`` keys.  The
        stage chain calls this host-side at its dispatch sites with
        the SAME unit definitions the fused megakernel accumulates
        in-kernel, so fused and stage totals agree exactly — free
        host-side adds, zero device syncs."""
        for k, v in units.items():
            v = int(v)
            if v:
                key = f"work_{k}"
                self.last_stats[key] = self.last_stats.get(key, 0) + v

    # -------------------------------------------------------- jitted ops

    def _program(self, key, unit, **statics):
        """A program unit (``engine/bodies.py``) bound to this
        checker's static arguments, remembered under ``key``: JAX keys
        the unit on those arguments, so a later checker of the same
        binding, sizes and tier is handed the executable this one
        built.  A ``partial``, not a closure: a Python frame between a
        dispatch site and the program is on the traceback of every
        equation the program traces, and a first check pays for it
        (PERF.md §6, PR 33).  Key columns are passed as tuples."""
        fn = functools.partial(unit, **statics)
        self._jits[key] = fn
        return fn

    def _slice_jit(self):
        """Trivial LCAP-dependent slicer: flat rows[LCAP*W], off ->
        flat [G*W] window (a BFS level is a contiguous gid range of the
        row store).  Keeping this separate means row-store growth never
        recompiles the big expand graph.

        Every multi-GB row buffer in this engine is FLAT 1-D at jit
        boundaries: a [N, W] array with small W is stored tiled on TPU
        (minor dim padded toward 128), and ops like gather/DUS can
        force a full T(8,128) relayout copy of the whole store — 6.4x
        memory, an instant OOM at bench sizes (measured,
        scripts/profile.py lsm).  Flat u32 vectors have no pad; kernels
        reshape small windows internally."""
        key = ("slice", self.LCAP)
        if key in self._jits:
            return self._jits[key]
        G, W = self.G, self.W

        @spans.staged("expand")
        def ptt_slice(rows, off):
            return lax.dynamic_slice(rows, (off * W,), (G * W,))

        fn = jax.jit(ptt_slice)
        self._jits[key] = fn
        return fn

    def _expand_jit(self):
        """(ak cols, arows[W, ACAP] (word-major SoA), flat window[G*W],
        f_off, n_live, dead_gid, gid_base, acc_off) -> (ak', arows',
        dead_gid') — the stage-chain dispatch ``bodies.ptt_expand`` (a
        unit: built once a process per binding, window and chunk size,
        not per checker); capacity-independent apart from the fixed
        ACAP."""
        key = ("expand",)
        if key in self._jits:
            return self._jits[key]
        return self._program(
            key, bodies.ptt_expand, model=self.model, keys=self.keys,
            Fi=self.Fi, G=self.G, check_deadlock=self.check_deadlock,
        )

    def _init_jit(self):
        """(ak cols, arows, f_off, acc_off) -> (ak', arows').  Generates
        ``NCs`` initial-state candidates (indices f_off..f_off+NCs) into
        the accumulator — the mixed-radix counting kernel shape from
        SURVEY.md §3.2."""
        key = ("init",)
        if key in self._jits:
            return self._jits[key]
        return self._program(
            key, bodies.ptt_init, model=self.model, keys=self.keys,
            NCs=self.NCs, Fi=self.Fi,
        )

    def _fpflush_jit(self):
        """The flush: probe-or-insert the accumulator keys into the HBM
        hash table — (table cols, ak cols, n_acc, fpm) ->
        (table' cols, n_new, flag_acc[ACAP], fpm').

        Cost is O(ACAP * E[probes]) regardless of how many states have
        been visited.  ``flag_acc`` comes back directly in accumulator
        order (min-lane-wins: the lowest slot holding a key is the one
        flagged new), feeding the append.  ``fpm`` accumulates the
        per-flush metrics [flushes, probe_rounds, failures,
        valid_lanes_lo, max_probe_rounds, valid_lanes_hi] on device
        (:data:`FPM_N`) so
        they ride the one hot-path stats fetch — zero extra syncs;
        failures (stage overflow / probe limit) surface at the next
        stats fetch as a hard error — states were dropped, the run
        cannot continue honestly."""
        key = (
            "fpflush", self.TCAP, self.fps_dense, self.fps_stages,
            compact_ops.materialization(),
        )
        if key in self._jits:
            return self._jits[key]
        return self._program(
            key, bodies.ptt_fpflush2, dense_rounds=self.fps_dense,
            stages=self.fps_stages, materialize=key[-1],
        )

    def _rehash_jit(self):
        """fpset growth: old table cols -> double-capacity cols + the
        rehash vector (failures, keys moved, lanes presented), fully on
        device (``fpset.rehash_cols``).  The transient is old+new table
        and a few columns of one chunk."""
        key = ("rehash", self.TCAP, compact_ops.materialization())
        if key in self._jits:
            return self._jits[key]
        return self._program(
            key, bodies.ptt_rehash2, materialize=key[-1]
        )

    # invariant-evaluation chunk for the append: bounds the unpacked-
    # state / invariant intermediates (all proportional to SL lanes; a
    # full-ACAP unpack is multi-GB at bench shapes)
    SL = 1 << 17

    def _compact_jit(self):
        """The compaction stage, split out of the append as its OWN
        dispatch (round 10): the acc-order new-flag compacts the W
        accumulator word columns to the front in discovery order —
        ``(arows[W, ACAP] donated, flag_acc) -> (crows[W, ACAP],
        idx[ACAP])``.

        Gathers are latency-bound per element on TPU (~17-50 ns — a
        gather-based append measured 10.9 s per 8.9M lanes,
        scripts/profile.py stages), so compaction is dense passes: the
        log-shift of ``ops/compact.py`` (exclusive prefix sum + log2(A)
        masked doubling shifts, contiguous copies only).  Standing
        alone it gets per-dispatch
        ``stage_compact_n``/``_s`` accounting (the BASELINE per-stage
        table's before/after), and the accumulator is DONATED: the
        compacted matrix aliases its memory and is recycled as the
        next fill's accumulator buffer, so the split adds only the idx
        plane per in-flight flush — not a second W x ACAP store."""
        key = ("compact", compact_ops.materialization())
        if key in self._jits:
            return self._jits[key]
        # the row-matrix compaction body lives in ops/compact.py
        # since r13 (shared with the fused level megakernel)
        return self._program(
            key, bodies.ptt_compact, materialize=key[-1]
        )

    def _append_jit(self):
        """Land the flush's new states (already compacted to the front
        of ``crows`` in discovery order by ``_compact_jit``) in the row
        store + trace logs, evaluating invariants on exactly the new
        states.

        ``is_init`` rides as a traced flag (one compile, not two):
        roots log ``-1 - init_idx`` parents, expand lanes log
        ``(parent gid, action lane)`` — both derived from ``idx``, the
        compaction's original-slot index.

        Invariants evaluate on the deduped new states (round 2 paid
        this on every candidate lane) in SL-sized chunks of the
        compacted columns.  Round 5: the chunk loop's trip count is
        DYNAMIC — ``ceil(n_new / SL)`` — so a flush that yields 4M new
        states out of a 26M-lane accumulator no longer unpacks and
        DUS-writes the full APAD window (the round-4 scan always ran
        all C chunks; at deep-level duplicate rates that was ~2-3x
        wasted append time).

        Row writes land at ``n_visited - row_base`` (``row_base`` = gid
        of rows[0]; 0 in rows_window="all").  ``rows_ok=False`` diverts
        them to the scratch window at ``LCAP - APAD`` (the sliding
        window is full; those rows are never read)."""
        key = ("append", self.LCAP, self.PCAP)
        if key in self._jits:
            return self._jits[key]
        return self._program(
            key, bodies.ptt_append, model=self.model,
            invariant_names=self.invariant_names, SL=self.SLc,
            C=self.C, LCAP=self.LCAP,
        )

    # ------------------------------------------- fused level megakernel

    # fused stats-vector tail: [level_base, nf, w_off, n_lv, rows_ok,
    # groups_left] between the standard [nv, dead, viol..., fpm] prefix
    # and the RMAX per-level sizes
    FUSED_TAIL = 6

    def _fused_jit(self):
        """The round-13 level megakernel: ONE dispatch walks flush
        groups — and, on the ramp, whole level boundaries — of the BFS
        inside a ``lax.while_loop``, chaining the identical traced
        sub-functions the stage chain dispatches separately
        (expand -> ``ops.fpset.flush_acc`` ->
        ``ops.compact.compact_rows`` -> append) with every buffer
        donated end-to-end.  The program itself is ``bodies.ptt_level2``.

        Operands: ``(vk, ak, arows, rows, parent, lane, n_visited,
        dead_gid, viol, fpm, wkm, level_base, nf, w_off, levels_left,
        groups_left, row_base, rows_ok)``; returns the updated buffers
        + state scalars + one packed int32 stats vector ``[nv, dead,
        viol..., fpm..., wkm..., level_base, nf, w_off, n_lv, rows_ok,
        groups_left, lsizes[RMAX]]`` so the host's ONE fetch reads
        everything (no separate stats dispatch).  ``wkm`` is the
        :data:`fpset.WKM_N` work-unit vector (round 14): every while
        iteration accumulates the group's live expand rows, presented
        probe lanes, compacted elements, appended rows, and the
        iteration itself — per-stage work units the cost-attribution
        layer converts into estimated seconds, riding the same fetch
        with zero extra syncs.

        The loop runs while (a) the host-granted group/level budgets
        hold, (b) the next flush group's worst case fits the capacity
        tiers (``nv + min(ACAP, live*A) <= VCAP`` etc. — on exhaustion
        the host fetches, grows, and re-enters mid-level via
        ``w_off``), and (c) at a level boundary: the frontier is
        nonzero, no violation/deadlock was found, and — past the first
        level of the dispatch — the new frontier still fits one expand
        window (the ramp's early exit on frontier growth).  Per-level
        sizes come back in ``lsizes`` so host-side accounting,
        telemetry ``level`` records, checkpoint frames, and
        ``PTT_FAULT`` sites replay exactly.  Discovery order is
        identical to the stage chain state-for-state: same window
        layout, same flush partition, same min-lane-wins dedup.

        Backend note (BASELINE.md Round-13): XLA:CPU copies while-loop
        carried buffers once per iteration (measured ~110 ms per
        800 MB), so on the virtual CPU mesh a big-store shape pays a
        per-group store copy the stage chain avoids — negligible at
        test sizes, and the 253k differential still favors fused
        there.  On the TPU backend loop carries alias in place (the
        resident-BFS premise this kernel is built on)."""
        key = (
            "fused", self.TCAP, self.LCAP, self.PCAP,
            self.fps_dense, self.fps_stages, self.RMAX,
            # read when the program is built (it was read when the
            # program traced): part of the program's key
            compact_ops.materialization(),
        )
        if key in self._jits:
            return self._jits[key]
        return self._program(
            key, bodies.ptt_level2, model=self.model, keys=self.keys,
            invariant_names=self.invariant_names, Fi=self.Fi, G=self.G,
            FLUSH=self.FLUSH, check_deadlock=self.check_deadlock,
            dense_rounds=self.fps_dense, stages=self.fps_stages,
            materialize=key[-1], SL=self.SLc, C=self.C, VCAP=self.VCAP,
            LCAP=self.LCAP, PCAP=self.PCAP, SCAP=self.SCAP,
            RMAX=self.RMAX,
            frontier_mode=self.rows_window == "frontier",
        )

    def _shift_jit(self):
        """Frontier-window mode: slide the new frontier's rows to
        offset 0 (drop everything older) with a chunked copy —
        ``(rows, src_off_rows, n_rows)``.  Chunks are processed in
        increasing order, so the in-place copy-down can never overwrite
        source it has yet to read (each iteration's slice materializes
        before its DUS); a contiguous HBM copy moves a 44M-row window
        in ~10 ms vs the GBs it frees.  The rows buffer carries
        ``SHIFT_CW`` words of tail padding so the ceil-rounded last
        chunk's read can never clamp (a clamped dynamic_slice would
        shift the whole chunk and corrupt real frontier rows)."""
        key = ("shift", self.LCAP)
        if key in self._jits:
            return self._jits[key]
        W = self.W
        CW = self.SHIFT_CW

        def shift(rows, src_off, n_rows):
            nw = n_rows * W

            def body(i, rows):
                chunk = lax.dynamic_slice(
                    rows, (src_off * W + i * CW,), (CW,)
                )
                return lax.dynamic_update_slice(rows, chunk, (i * CW,))

            return lax.fori_loop(
                0, (nw + CW - 1) // CW, body, rows
            )

        # a tiered run's window slides after a spill: the same copy
        # under the spill's scope, and (a program's name is part of its
        # cache key, its scopes are not) under a name of its own
        ptt_shift = (
            _scoped("spill_shift", "ptt_spill_shift", shift)
            if self.tiered
            else _scoped("levelctl", "ptt_shift", shift)
        )
        fn = jax.jit(ptt_shift, donate_argnums=(0,))
        self._jits[key] = fn
        return fn

    # ------------------------------------ tiered-store device ops (r16)

    def _logshift_jit(self):
        """Tiered mode: slide the live tail of the parent/lane trace
        logs down after an aged range spilled — ``(parent, lane,
        src_off, n)``, the :meth:`_shift_jit` contract for the two
        int32 log planes (``LOG_CW`` tail padding gives the same
        clamp-safety)."""
        key = ("logshift", self.PCAP)
        if key in self._jits:
            return self._jits[key]
        CW = self.LOG_CW

        @spans.staged("spill_shift")
        def ptt_spill_logshift(parent, lane, src_off, n):
            def body(i, st):
                p, ln = st
                cp = lax.dynamic_slice(p, (src_off + i * CW,), (CW,))
                cl = lax.dynamic_slice(ln, (src_off + i * CW,), (CW,))
                return (
                    lax.dynamic_update_slice(p, cp, (i * CW,)),
                    lax.dynamic_update_slice(ln, cl, (i * CW,)),
                )

            return lax.fori_loop(
                0, (n + CW - 1) // CW, body, (parent, lane)
            )

        fn = jax.jit(ptt_spill_logshift, donate_argnums=(0, 1))
        self._jits[key] = fn
        return fn

    def _tag_jit(self):
        """``(vk cols, gen, epoch) -> gen'`` — stamp occupied-but-
        untagged fpset slots with the current eviction epoch (one
        masked pass per level boundary; store/sieve.py)."""
        key = ("spill_tag", self.TCAP)
        if key in self._jits:
            return self._jits[key]
        K = self.K

        @spans.staged("spill_tag")
        def ptt_spill_tag(*args):
            return store_sieve.tag_generation(
                args[:K], args[K], args[K + 1]
            )

        fn = jax.jit(ptt_spill_tag, donate_argnums=(self.K,))
        self._jits[key] = fn
        return fn

    def _evict_jit(self):
        """``(vk cols, gen, cutoff) -> (vk holed, gen', ev sorted
        cols, n_evicted)`` — extract generations at or below the
        cutoff, sorted for the host's delta codec.  The holed table
        must be rehashed (:meth:`_rehash_same_jit`) before it serves
        lookups again."""
        key = ("spill_evict", self.TCAP)
        if key in self._jits:
            return self._jits[key]
        K = self.K

        @spans.staged("spill_evict")
        def ptt_spill_evict(*args):
            holed, gen, ev, n = store_sieve.extract_cold(
                args[:K], args[K], args[K + 1]
            )
            return (*holed, gen, *ev, n)

        fn = jax.jit(
            ptt_spill_evict, donate_argnums=tuple(range(self.K + 1))
        )
        self._jits[key] = fn
        return fn

    def _rehash_same_jit(self):
        """Rebuild a holed (post-eviction) table at the SAME capacity
        — open-addressing probe chains break across holes, so the
        survivors re-insert into a fresh table.  No donation: XLA may
        not alias the input (rehash reads old slots while writing new
        ones)."""
        key = ("spill_rehash", self.TCAP)
        if key in self._jits:
            return self._jits[key]
        K, TCAP = self.K, self.TCAP

        # a rehash like a doubling's: ``stage_device_s.rehash.*`` reads
        # it, and its probe rounds keep the probe's parts
        @spans.staged("rehash")
        def ptt_spill_rehash(*old):
            new, rhm = fpset.rehash_cols(
                old, fpset.empty_cols(TCAP, K)
            )
            return (*new, rhm[0])

        fn = jax.jit(ptt_spill_rehash)
        self._jits[key] = fn
        return fn

    def _sieve_jit(self):
        """``(ak cols, flag_acc) -> (kcols dense, lane_ids, n_new)``
        — pack exactly the hot-filter survivors for cold-tier miss
        resolution; only these keys ever cross the link (the sieve)."""
        key = ("spill_sieve",)
        if key in self._jits:
            return self._jits[key]
        K = self.K

        @spans.staged("spill_sieve")
        def ptt_spill_sieve(*args):
            return store_sieve.sieve_new(args[:K], args[K])

        fn = jax.jit(ptt_spill_sieve)
        self._jits[key] = fn
        return fn

    # width of one unflag scatter (false-new lanes per dispatch); a
    # flush with more cold duplicates chunks the merge host-side
    UNFLAG_P = 1 << 10

    def _unflag_jit(self):
        """``(flag_acc, lanes[UNFLAG_P], n) -> flag_acc'`` — merge the
        cold-tier verdicts back: lanes resolved already-visited stop
        being new BEFORE the compaction that assigns gids (the tiered
        discovery-order exactness hinge; store/sieve.py)."""
        key = ("spill_unflag",)
        if key in self._jits:
            return self._jits[key]

        @spans.staged("spill_unflag")
        def ptt_spill_unflag(flag_acc, lanes, n):
            return store_sieve.unflag_lanes(flag_acc, lanes, n)

        fn = jax.jit(ptt_spill_unflag, donate_argnums=(0,))
        self._jits[key] = fn
        return fn

    def _stats_jit(self):
        key = ("stats",)
        if key in self._jits:
            return self._jits[key]

        # stats layout: [nv, dead, viol..., flushes, rounds, failed]
        @spans.staged("levelctl")
        def ptt_stats(n_visited, dead_gid, viol, fpm):
            return jnp.concatenate(
                [jnp.stack([n_visited, dead_gid]), viol, fpm]
            )

        fn = jax.jit(ptt_stats)
        self._jits[key] = fn
        return fn

    def _chain_jit(self, max_depth: int):
        key = ("chain", max_depth)
        if key in self._jits:
            return self._jits[key]

        def step(parent_log, lane_log, gid):
            def body(i, st):
                g, gids, lanes = st
                gids = gids.at[i].set(jnp.where(g >= 0, g, BIG))
                lanes = lanes.at[i].set(
                    jnp.where(g >= 0, lane_log[jnp.maximum(g, 0)], -1)
                )
                nxt = jnp.where(g >= 0, parent_log[jnp.maximum(g, 0)], g)
                return nxt, gids, lanes

            gids = jnp.full((max_depth,), BIG, jnp.int32)
            lanes = jnp.full((max_depth,), -1, jnp.int32)
            g_end, gids, lanes = lax.fori_loop(
                0, max_depth, body, (gid, gids, lanes)
            )
            # g_end = the root's (negative) parent entry: -1 - init_idx
            return gids, lanes, g_end

        fn = jax.jit(step)
        self._jits[key] = fn
        return fn

    # ----------------------------------------------- host-seeded starts

    SEED_CHUNK = 1 << 15

    def _fpseed_merge_jit(self):
        """Seed merge: insert one SEED_CHUNK of host-seeded states
        straight into the MAIN table (probes are O(chunk) whatever the
        table size) and fuse the discovery-time invariant check of the
        main append path."""
        key = (
            "fpseedmerge", self.TCAP, self.fps_dense, self.fps_stages,
        )
        if key in self._jits:
            return self._jits[key]
        NCs, K = self.SEED_CHUNK, self.K
        layout = self.layout
        m = self.model
        inv_fns = [m.invariants[n] for n in self.invariant_names]
        n_inv = len(self.invariant_names)
        keyspec = self.keys

        @spans.staged("seed")
        def ptt_fpseed_merge2(*args):
            tc = args[:K]
            rows, n_valid, n_visited, viol, gid_base, fpm = args[K:]
            kcols = keyspec.make(rows)
            lane = jnp.arange(NCs, dtype=jnp.int32)
            valid = lane < n_valid
            (
                is_new, tc2, n_failed, rounds, lane_rounds, step_rounds,
                write_saved,
            ) = fpset.lookup_or_insert(
                tc, kcols, valid,
                dense_rounds=self.fps_dense,
                stages=self.fps_stages,
            )
            if n_inv:
                states = jax.vmap(layout.unpack)(rows)
                vnew = []
                for fn in inv_fns:
                    ok = jax.vmap(fn)(states)
                    bad = valid & ~ok
                    vnew.append(
                        jnp.min(jnp.where(bad, gid_base + lane, BIG))
                    )
                viol = jnp.minimum(viol, jnp.stack(vnew))
            fpm = fpset.fpm_update(
                fpm, rounds, n_failed,
                jnp.sum(valid.astype(jnp.int32)), lane_rounds,
                step_rounds, write_saved,
            )
            return (
                *tc2,
                n_visited + jnp.sum(is_new.astype(jnp.int32)),
                viol, fpm,
            )

        fn = jax.jit(
            ptt_fpseed_merge2, donate_argnums=tuple(range(self.K))
        )
        self._jits[key] = fn
        return fn

    def _seed_write_jit(self):
        """Seed rows/logs land via exact-size DUS windows (the host
        knows every seed count, so no clamping is possible and no
        scatter is needed)."""
        key = ("seedwrite", self.LCAP, self.PCAP)
        if key in self._jits:
            return self._jits[key]

        W = self.W

        @spans.staged("seed")
        def ptt_seed_write(rows_store, parent_log, lane_log, rows, par,
                           lane, off):
            rows_store = lax.dynamic_update_slice(
                rows_store, rows.reshape(rows.shape[0] * W), (off * W,)
            )
            parent_log = lax.dynamic_update_slice(parent_log, par, (off,))
            lane_log = lax.dynamic_update_slice(lane_log, lane, (off,))
            return rows_store, parent_log, lane_log

        fn = jax.jit(ptt_seed_write, donate_argnums=(0, 1, 2))
        self._jits[key] = fn
        return fn

    def prestage_seed(self, seed) -> None:
        """Push the seed arrays to the device ahead of :meth:`run`
        (e.g. from the seed-builder thread while warmup compiles): the
        bulk H2D overlaps the compiles instead of sitting at the head
        of the measured run."""
        rows, parents, lanes, lsizes = seed
        rows = np.ascontiguousarray(rows, np.uint32)
        parents = np.ascontiguousarray(parents, np.int32)
        lanes = np.ascontiguousarray(lanes, np.int32)
        n = len(rows)
        NCs = self.SEED_CHUNK
        npad = -(-n // NCs) * NCs + NCs
        W = self.W
        self._seed_staged = (
            self._seed_token(rows, parents, seed[3]),
            jnp.asarray(
                np.concatenate(
                    [rows, np.zeros((npad - n, W), np.uint32)]
                )
            ),
            jnp.asarray(
                np.concatenate([parents, np.zeros(npad - n, np.int32)])
            ),
            jnp.asarray(
                np.concatenate([lanes, np.zeros(npad - n, np.int32)])
            ),
        )

    @staticmethod
    def _seed_token(rows, parents, lsizes):
        """Cheap identity token so a prestaged seed can never be
        silently substituted for a *different* seed of the same length
        passed to run() (content-sampled, not just the count)."""
        n = len(rows)
        step = max(1, n // 64)
        return (
            n,
            tuple(int(x) for x in lsizes),
            int(np.asarray(rows[::step], np.uint64).sum()),
            int(np.asarray(parents[::step], np.int64).sum()),
        )

    @spans.in_phase("seed_load")
    def _load_seed(self, bufs, st, seed):
        """Bulk-load a host-enumerated BFS prefix: packed states in BFS
        (= gid) order with parent gids (roots: ``-1 - init_idx``) and
        action lanes, plus per-level sizes.  The caller guarantees the
        states are distinct, level-complete, and deadlock-free (they
        were fully expanded by the host).  Returns level_sizes."""
        rows, parents, lanes, lsizes = seed
        rows = np.ascontiguousarray(rows, np.uint32)
        parents = np.ascontiguousarray(parents, np.int32)
        lanes = np.ascontiguousarray(lanes, np.int32)
        n = len(rows)
        if sum(lsizes) != n:
            raise ValueError("seed level sizes do not sum to the state count")
        if n > self.SCAP:
            raise ValueError(f"seed too large ({n} states)")
        if (
            self.rows_window == "frontier"
            and n + self.SEED_CHUNK > self.LCAP
        ):
            raise ValueError(
                f"seed ({n} states) exceeds the frontier rows window "
                f"({self.LCAP}); raise row_cap_states"
            )
        if self.tiered and (
            n + self.SEED_CHUNK > min(self._capl(), self._capp())
            or n + self.ACAP > self._capv()
        ):
            # seeds load before any spill boundary exists: honor them
            # past the budget (warned once), like the init valve —
            # table ceiling included (the seed merge inserts every
            # seed key hot before any eviction can run)
            self._lcap_max = max(self._lcap_max, n + self.SEED_CHUNK)
            self._pcap_max = max(self._pcap_max, n + self.SEED_CHUNK)
            while self._tcap_max // 2 < n + self.ACAP:
                self._tcap_max *= 2
            if not self._budget_overridden:
                self._budget_overridden = True
                self._log(
                    "WARNING: hbm_budget too small for the seed — "
                    "growing past the budget"
                )
        if (
            self.rows_window == "frontier"
            and lsizes
            and lsizes[-1] + self.APAD > self.LCAP
        ):
            # mirror of the init-path guard: the seeded frontier must
            # leave room for one blind APAD append window, or the first
            # flush diverts rows to the scratch window at LCAP - APAD —
            # which OVERLAPS the live frontier rows and silently
            # corrupts the search (ADVICE r5 medium)
            raise ValueError(
                f"seed frontier ({lsizes[-1]} states) exceeds the "
                f"frontier rows window ({self.LCAP} rows, "
                f"{self.APAD} reserved for the append); raise "
                "row_cap_states"
            )
        self._grow_visited(bufs, n + self.ACAP)
        # seed writes are SEED_CHUNK-padded DUS windows starting at
        # offsets up to n, so the store must admit one full chunk past
        # the worst-case write start or the DUS would clamp and corrupt
        self._grow_store(bufs, n + self.SEED_CHUNK)
        if self.fuse == "level":
            # land on the unified fused staircase (SEED_CHUNK <= APAD,
            # so this covers the guard above and keeps the first fused
            # dispatch on a prewarmed tier triple)
            self._grow_fused(bufs, n)
        merge = self._fpseed_merge_jit()
        write = self._seed_write_jit()
        NCs = self.SEED_CHUNK
        W = self.W
        # ONE bulk H2D per array (per-chunk transfers would pay a host
        # round trip each); chunks below are device-side slices of
        # these
        # chunk starts are level-relative (off + c0 < n), so the last
        # slice can extend past n by up to NCs; pad a full extra chunk
        # or dynamic_slice would clamp the start and merge SHIFTED rows
        staged = getattr(self, "_seed_staged", None)
        if staged is None or staged[0] != self._seed_token(
            rows, parents, lsizes
        ):
            # not (or differently) prestaged: pay the H2D here
            self.prestage_seed(seed)
            staged = self._seed_staged
        # prestaged (ideally during warmup): the bulk H2D already
        # happened off the measured path
        _, rows_d, par_d, lan_d = staged
        self._seed_staged = None
        vks = bufs["vk"]  # insert straight into the main table
        clock = self._clock
        n_vis = jnp.int32(0)
        off = 0
        for count in lsizes:
            for c0 in range(0, count, NCs):
                cn = min(NCs, count - c0)
                s0 = off + c0
                jrows = lax.dynamic_slice(
                    rows_d, (s0, 0), (NCs, W)
                )
                # one offset serves the merge and the write
                with clock.upload("ptt_fpseed_merge2", 2):
                    cn_d, s0_d = jnp.int32(cn), jnp.int32(s0)
                with clock.call("ptt_fpseed_merge2"):
                    out = merge(
                        *vks, jrows, cn_d, n_vis, st["viol"], s0_d,
                        st["fpm"],
                    )
                vks = out[: self.K]
                n_vis, st["viol"], st["fpm"] = out[self.K:]
                jpar = lax.dynamic_slice(par_d, (s0,), (NCs,))
                jlan = lax.dynamic_slice(lan_d, (s0,), (NCs,))
                with clock.call("ptt_seed_write"):
                    (
                        bufs["rows"], bufs["parent"], bufs["lane"],
                    ) = write(
                        bufs["rows"], bufs["parent"], bufs["lane"],
                        jrows, jpar, jlan, s0_d,
                    )
            off += count
        bufs["vk"] = vks
        fpm = np.asarray(st["fpm"])
        # the merges' rounds ran at SEED_CHUNK's ladder, not a flush's
        self._fold_lane_arb(fpm, NCs)
        if int(fpm[2]):
            raise RuntimeError(
                "fpset probe overflow while loading the seed — "
                "raise visited_cap"
            )
        if int(np.asarray(n_vis)) != n:
            raise ValueError(
                "seed states are not all distinct "
                f"({int(np.asarray(n_vis))} of {n} unique)"
            )
        st["n_visited"] = jnp.int32(n)
        # seed states land via seed_write, not the append body: they
        # are not append work (the post-seed fetch must not count them)
        self._work_nv_prev = int(n)
        return [int(x) for x in lsizes]

    # ------------------------------------------------------------ growth

    @_growth_call
    def _grow_visited(self, bufs, need: int):
        cap = self._capv()
        # clamp at the most any run can use: nv never exceeds SCAP, so
        # a table/column set admitting SCAP + one accumulator suffices
        # — and the clamp makes the tier schedule DETERMINISTIC, which
        # is what lets warmup(tiers=True) pre-compile every reachable
        # tier (VERDICT r5 #8: a 317 s lazy compile landed mid-window)
        need = min(need, cap)
        # double + on-device rehash, capped at the most any run can
        # use (nv never exceeds SCAP, so a table admitting
        # SCAP + ACAP states at load 1/2 never needs to grow again
        # even when the caller's headroom ask overshoots it).  In
        # tiered mode the cap is additionally budget-clamped — a
        # need past it is served by EVICTION, not growth
        # (_ensure_hot_capacity).
        grew = False
        clock = self._clock
        while self.VCAP < need and self.VCAP < cap:
            with clock.call("ptt_rehash2"):
                out = self._rehash_jit()(bufs["vk"])
            bufs["vk"] = out[: self.K]
            # the one host sync of a doubling: the fail-stop count and
            # the rehash's two counters in the same vector
            failed, keys, lane_rounds = fpset.rhm_logical(out[self.K])
            if failed:
                raise RuntimeError(
                    "fpset rehash overflow — table corrupted its "
                    "load-factor contract (bug)"
                )
            self._growth.rehashes += 1
            self._growth.rehash_slots += self.TCAP
            self._growth.rehash_keys += keys
            self._growth.rehash_lane_rounds += lane_rounds
            self.TCAP *= 2
            self.VCAP = self.TCAP // 2
            grew = True
        if grew and self.tiered and "gen" in bufs:
            # the rehash scattered every key to a fresh slot, so
            # per-slot ages are void: restart the epoch clock with
            # all survivors at the base generation (a documented
            # coarsening — eviction order resets, membership and
            # discovery order are untouched)
            gen0 = jnp.zeros((self.TCAP + 1,), jnp.int32)
            with clock.upload("ptt_spill_tag", 1):
                epoch_d = jnp.int32(1)
            with clock.call("ptt_spill_tag"):
                bufs["gen"] = self._tag_jit()(*bufs["vk"], gen0, epoch_d)
            self._epoch = 2

    def _rows_len(self) -> int:
        """Rows buffer length in words (frontier AND tiered modes pad
        by SHIFT_CW so the sliding-window shift's ceil-rounded last
        chunk read can never clamp)."""
        pad = (
            self.SHIFT_CW
            if self.rows_window == "frontier" or self.tiered
            else 0
        )
        return self.LCAP * self.W + pad

    def _logs_len(self) -> int:
        """Trace-log buffer length (tiered mode pads by LOG_CW — the
        log window slides down after an aged range spills, with the
        same clamp-safety contract as the rows shift)."""
        return self.PCAP + (self.LOG_CW if self.tiered else 0)

    @staticmethod
    def _next_cap(cur: int, need: int, cap: int) -> int:
        """The log/row tiers' doubling-with-clamp schedule as pure
        arithmetic — one source of truth for the growers below AND the
        fused prewarm's tier-triple enumeration (the walk must land on
        exactly the tiers a run will reach)."""
        need = min(need, cap)
        while cur < need:
            cur += min(cur, max(cap - cur, need - cur))
        return cur

    @staticmethod
    def _next_table(tcap: int, need: int, cap: int) -> int:
        """fpset doubling schedule (pure arithmetic twin of
        ``_grow_visited``'s rehash loop): the table capacity after
        growing until ``need`` states fit at load <= 1/2."""
        while tcap // 2 < need and tcap // 2 < cap:
            tcap *= 2
        return tcap

    def _grow_buf(self, buf, pad: int):
        """``buf`` with ``pad`` zeros after it, as one program under
        the ``ptt.grow`` scope (``bodies.ptt_grow``); the old buffer's
        bytes are the copy ``grow_copy_bytes`` counts."""
        self._growth.copy_bytes += buf.nbytes
        with self._clock.call("ptt_grow"):
            return bodies.ptt_grow(buf, pad=pad)

    @_growth_call
    def _grow_logs(self, bufs, need: int):
        cap = self._capp()
        target = self._next_cap(self.PCAP, need, cap)
        while self.PCAP < target:
            pad = min(self.PCAP, target - self.PCAP)
            bufs["parent"] = self._grow_buf(bufs["parent"], pad)
            bufs["lane"] = self._grow_buf(bufs["lane"], pad)
            self.PCAP += pad

    @_growth_call
    def _grow_store(self, bufs, need: int):
        """Admit ``need`` states in the trace logs and (all-mode only)
        the row store.  Frontier mode's rows window is fixed — row
        capacity there is handled by the run loop's rows_ok logic."""
        self._grow_logs(bufs, need)
        if self.rows_window == "frontier":
            return
        # doubling, capped at the most any run can use (SCAP states
        # plus one blind append window; budget-clamped in tiered mode)
        # so a preset near-SCAP store is never forced to a wasteful
        # next power of two
        cap = self._capl()
        target = self._next_cap(self.LCAP, need, cap)
        while self.LCAP < target:
            pad = min(self.LCAP, target - self.LCAP)
            bufs["rows"] = self._grow_buf(bufs["rows"], pad * self.W)
            self.LCAP += pad

    @_growth_call
    def _grow_fused(self, bufs, need_states: int):
        """Unified growth for the fused path: every fused-mode growth
        site sizes visited + store/logs from ONE need, so the
        (TCAP, LCAP, PCAP) tier triple is a single deterministic
        staircase of ``need_states`` — which is what lets
        ``warmup(tiers=True)`` pre-compile every megakernel tier a run
        can reach (``_fused_tier_triples`` walks the same arithmetic).
        """
        self._grow_visited(bufs, need_states + self.ACAP)
        self._grow_store(bufs, need_states + self.APAD)

    def _fused_tier_triples(self):
        """Every (TCAP, VCAP, LCAP, PCAP) the unified fused growth
        schedule can reach from the CURRENT tiers, in order — pure
        arithmetic over the same ``_next_cap``/``_next_table``
        formulas the growers execute."""
        tcap, vcap = self.TCAP, self.VCAP
        lcap, pcap = self.LCAP, self.PCAP
        capv = self._capv()
        capl = self._capl()
        frontier = self.rows_window == "frontier"
        out = [(tcap, vcap, lcap, pcap)]
        while True:
            # the smallest need that grows ANY dimension
            cands = []
            if vcap < capv:
                cands.append(vcap - self.ACAP + 1)
            if pcap < capl:
                cands.append(pcap - self.APAD + 1)
            if not frontier and lcap < capl:
                cands.append(lcap - self.APAD + 1)
            if not cands:
                return out
            need = max(min(cands), 1)
            tcap = self._next_table(tcap, need + self.ACAP, capv)
            vcap = tcap // 2
            pcap = self._next_cap(pcap, need + self.APAD, capl)
            if not frontier:
                lcap = self._next_cap(lcap, need + self.APAD, capl)
            out.append((tcap, vcap, lcap, pcap))

    # --------------------------------------------------------------- run

    def _prewarm_tiers(self):
        """Pre-compile every capacity tier reachable under
        ``max_states`` (VERDICT r5 #8): the growth schedules are
        deterministic (doubling clamped at the capacity formulas — see
        ``_grow_visited``), so warmup can walk them on dummy data and
        leave every tier's program in ``_jits``.  After this, no
        harness pays a mid-window lazy compile at a tier crossing (a
        317 s compile once landed inside the measured sustained
        window).  Dummies are allocated and freed one tier at a time —
        the transient peaks at the largest tier, which the run itself
        would reach anyway."""
        z = jnp.zeros
        drain = jax.block_until_ready
        K = self.K
        save = (self.TCAP, self.VCAP, self.LCAP, self.PCAP)
        cap = self._capv()
        fused = self.fuse == "level"
        while self.VCAP < cap:
            # the growth path's exact sequence: rehash AT the
            # current tier (old -> doubled), then flush at the new.
            # Fused mode never dispatches the standalone flush
            # mid-run (the megakernel owns it — the triple walk
            # below covers its tiers), so only rehash compiles here
            out = self._rehash_jit()(fpset.empty_cols(self.TCAP, K))
            drain(out)
            del out
            self.TCAP *= 2
            self.VCAP = self.TCAP // 2
            if fused:
                continue
            ak = tuple(
                jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                for _ in range(K)
            )
            out = self._fpflush_jit()(
                fpset.empty_cols(self.TCAP, K), ak,
                jnp.int32(0), z((FPM_N,), jnp.int32),
            )
            drain(out)
            del ak, out
        # row/log tiers grow only in rows_window="all" (frontier mode
        # fixes the window and presizes the logs to SCAP up front).
        # Fused mode skips the stage slice/append tier compiles for
        # the same reason as the flush above — the megakernel triple
        # walk below owns every store tier its run can touch.
        if self.rows_window == "all" and not fused:
            capL = self._capl()
            n_inv = len(self.invariant_names)
            viol0 = jnp.full((n_inv,), int(BIG), jnp.int32)
            while self.LCAP < capL or self.PCAP < capL:
                if self.PCAP < capL:
                    self.PCAP += min(self.PCAP, capL - self.PCAP)
                if self.LCAP < capL:
                    self.LCAP += min(self.LCAP, capL - self.LCAP)
                rows_buf = z((self._rows_len(),), jnp.uint32)
                drain(self._slice_jit()(rows_buf, jnp.int32(0)))
                del rows_buf
                app = self._append_jit()(
                    z((self._rows_len(),), jnp.uint32),
                    z((self._logs_len(),), jnp.int32),
                    z((self._logs_len(),), jnp.int32),
                    z((self.W, self.ACAP), jnp.uint32),
                    z((self.ACAP,), jnp.int32),
                    jnp.int32(0), jnp.int32(0), viol0, jnp.int32(0),
                    jnp.bool_(False), jnp.int32(0), jnp.bool_(True),
                    jnp.int32(0),
                )
                drain(app)
                del app
        (self.TCAP, self.VCAP, self.LCAP, self.PCAP) = save
        if fused:
            # walk the UNIFIED fused growth staircase (one need drives
            # every dimension — see _grow_fused) and compile the level
            # megakernel at each reachable (TCAP, LCAP, PCAP) triple;
            # run-time tier crossings then re-enter a prewarmed program
            n_inv = len(self.invariant_names)
            viol0 = jnp.full((n_inv,), int(BIG), jnp.int32)
            for tcap, vcap, lcap, pcap in self._fused_tier_triples():
                self.TCAP, self.VCAP = tcap, vcap
                self.LCAP, self.PCAP = lcap, pcap
                key = (
                    "fused", tcap, lcap, pcap,
                    self.fps_dense, self.fps_stages, self.RMAX,
                )
                if key in self._jits:
                    continue  # the entry triple compiled in warmup()
                out = self._warm_fused(viol0)
                drain(out)
                del out
            (self.TCAP, self.VCAP, self.LCAP, self.PCAP) = save
            # the INIT path still dispatches the stage chain, at the
            # tier its growth reaches (n_initial + one accumulator /
            # append window — model-known here): compile the two
            # tier-keyed stage programs at exactly that tier so a warm
            # submit stays zero-compile (the r11 service contract)
            n_init = int(getattr(self.model, "n_initial", 0) or 0)
            capl = self._capl()
            self.TCAP = self._next_table(
                self.TCAP, n_init + self.ACAP, cap
            )
            self.VCAP = self.TCAP // 2
            self.PCAP = self._next_cap(
                self.PCAP, n_init + self.APAD, capl
            )
            if self.rows_window == "all":
                self.LCAP = self._next_cap(
                    self.LCAP, n_init + self.APAD, capl
                )
            if (
                "fpflush", self.TCAP, self.fps_dense, self.fps_stages,
            ) not in self._jits:
                ak = tuple(
                    jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                    for _ in range(K)
                )
                out = self._fpflush_jit()(
                    fpset.empty_cols(self.TCAP, K), ak,
                    jnp.int32(0), z((FPM_N,), jnp.int32),
                )
                drain(out)
                del ak, out
            if ("append", self.LCAP, self.PCAP) not in self._jits:
                app = self._append_jit()(
                    z((self._rows_len(),), jnp.uint32),
                    z((self._logs_len(),), jnp.int32),
                    z((self._logs_len(),), jnp.int32),
                    z((self.W, self.ACAP), jnp.uint32),
                    z((self.ACAP,), jnp.int32),
                    jnp.int32(0), jnp.int32(0), viol0, jnp.int32(0),
                    jnp.bool_(False), jnp.int32(0), jnp.bool_(True),
                    jnp.int32(0),
                )
                drain(app)
                del app
            (tc, self.VCAP, self.LCAP, self.PCAP) = save
            if tc is not None:
                self.TCAP = tc

    def _warm_fused(self, viol0):
        """Compile the level megakernel at the CURRENT tier triple on
        dummy buffers — ``nf=0`` with zero budgets, so the while_loop
        exits immediately and the dummies cost one allocation, not a
        walk."""
        z = jnp.zeros
        K = self.K
        return self._fused_jit()(
            fpset.empty_cols(self.TCAP, K),
            tuple(
                jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                for _ in range(K)
            ),
            z((self.W, self.ACAP), jnp.uint32),
            z((self._rows_len(),), jnp.uint32),
            z((self._logs_len(),), jnp.int32),
            z((self._logs_len(),), jnp.int32),
            jnp.int32(0), BIG, viol0, z((FPM_N,), jnp.int32),
            z((WKM_N,), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.bool_(True),
        )

    @spans.spanned("warmup")
    def warmup(self, seed: bool = False, tiers: bool = True) -> float:
        """Compile every hot-path jit at the current tiers on dummy data
        (outside any timed budget); returns the compile wall time.
        ``seed=True`` also compiles the small-shape seed pipeline;
        ``tiers=True`` (default) walks the capacity-growth schedule and
        pre-compiles EVERY tier reachable under ``max_states``, so no
        run pays a mid-window lazy compile at a tier crossing
        (VERDICT r5 #8).  Per-stage compile times land in
        ``self.last_stats`` as ``compile_<stage>_s`` (the warmup
        breakdown VERDICT r3 asks for)."""
        t0 = time.perf_counter()
        z = jnp.zeros
        n_inv = len(self.invariant_names)
        K = self.K
        tlast = [t0]

        def mark(stage: str):
            now = time.perf_counter()
            self.last_stats[f"compile_{stage}_s"] = round(
                now - tlast[0], 1
            )
            tlast[0] = now

        # block_until_ready is the completion barrier; callers delete
        # refs right after so the warmup dummies never coexist in HBM
        drain = jax.block_until_ready

        def acc():
            return (
                tuple(
                    jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                    for _ in range(K)
                ),
                z((self.W, self.ACAP), jnp.uint32),
            )

        ak, arows = acc()
        out = self._init_jit()(ak, arows, jnp.int32(0), jnp.int32(0))
        drain(out)
        mark("init")
        ak, arows = out[:K], out[K]
        if self.rows_window == "frontier" or self.fuse == "stage":
            rows_buf = z((self._rows_len(),), jnp.uint32)
            if self.fuse == "stage":
                window = self._slice_jit()(rows_buf, jnp.int32(0))
            if self.rows_window == "frontier":
                drain(
                    self._shift_jit()(
                        rows_buf, jnp.int32(0), jnp.int32(0)
                    )
                )
            del rows_buf
        if self.fuse == "stage":
            # the standalone expand program is a stage-chain dispatch;
            # fused mode compiles the expand body inside the megakernel
            out = self._expand_jit()(
                ak, arows, window, jnp.int32(0), jnp.int32(0), BIG,
                jnp.int32(0), jnp.int32(0),
            )
            drain(out)
            mark("expand")
            ak, arows = out[:K], out[K]
            del window
        tc = fpset.empty_cols(self.TCAP, K)
        fpm0 = jnp.zeros((FPM_N,), jnp.int32)
        out = self._fpflush_jit()(tc, ak, jnp.int32(0), fpm0)
        drain(out)
        mark("flush")
        del tc
        # the donated-input flush returns the table; reuse it as the
        # seed-merge compile dummy instead of allocating a second
        # TCAP-sized table (dropped right away when no seed compile
        # is coming — it must not squat HBM under the append dummy)
        seed_tbl = out[:K] if seed else None
        flag_w = out[K + 1]
        del out
        crows, idx_w = self._compact_jit()(arows, flag_w)
        drain(crows)
        mark("compact")
        del arows, flag_w
        viol0 = jnp.full((n_inv,), int(BIG), jnp.int32)
        app = self._append_jit()(
            z((self._rows_len(),), jnp.uint32),
            z((self._logs_len(),), jnp.int32), z((self._logs_len(),), jnp.int32),
            crows, idx_w, jnp.int32(0), jnp.int32(0), viol0,
            jnp.int32(0), jnp.bool_(False), jnp.int32(0),
            jnp.bool_(True), jnp.int32(0),
        )
        drain(app)
        mark("append")
        del app, ak, crows, idx_w
        drain(
            self._stats_jit()(
                jnp.int32(0), BIG, viol0, jnp.zeros((FPM_N,), jnp.int32)
            )
        )
        drain(
            self._chain_jit(4)(
                z((self._logs_len(),), jnp.int32),
                z((self._logs_len(),), jnp.int32), jnp.int32(-1),
            )
        )
        mark("misc")
        if self.fuse == "level":
            drain(self._warm_fused(viol0))
            mark("fused")
        if self.tiered:
            K = self.K
            tc = fpset.empty_cols(self.TCAP, K)
            gen0 = z((self.TCAP + 1,), jnp.int32)
            gen1 = self._tag_jit()(*tc, gen0, jnp.int32(1))
            out = self._evict_jit()(*tc, gen1, jnp.int32(1))
            drain(out)
            drain(self._rehash_same_jit()(*out[:K]))
            del tc, gen0, gen1, out
            ak0 = tuple(
                jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                for _ in range(K)
            )
            flag0 = z((self.ACAP,), jnp.uint32)
            drain(self._sieve_jit()(*ak0, flag0))
            drain(
                self._unflag_jit()(
                    flag0, z((self.UNFLAG_P,), jnp.int32),
                    jnp.int32(0),
                )
            )
            del ak0, flag0
            drain(
                self._logshift_jit()(
                    z((self._logs_len(),), jnp.int32),
                    z((self._logs_len(),), jnp.int32),
                    jnp.int32(0), jnp.int32(0),
                )
            )
            drain(
                self._shift_jit()(
                    z((self._rows_len(),), jnp.uint32),
                    jnp.int32(0), jnp.int32(0),
                )
            )
            mark("spill")
        if seed:
            write = self._seed_write_jit()
            drain(
                self._fpseed_merge_jit()(
                    *seed_tbl,
                    z((self.SEED_CHUNK, self.W), jnp.uint32),
                    jnp.int32(0), jnp.int32(0), viol0,
                    jnp.int32(0), jnp.zeros((FPM_N,), jnp.int32),
                )
            )
            drain(
                write(
                    z((self._rows_len(),), jnp.uint32),
                    z((self._logs_len(),), jnp.int32),
                    z((self._logs_len(),), jnp.int32),
                    z((self.SEED_CHUNK, self.W), jnp.uint32),
                    z((self.SEED_CHUNK,), jnp.int32),
                    z((self.SEED_CHUNK,), jnp.int32), jnp.int32(0),
                )
            )
            warm_pack = getattr(self.model, "warm_host_seed", None)
            if warm_pack is not None:
                warm_pack()
            mark("seed")
        if tiers:
            self._prewarm_tiers()
            mark("tiers")
        compile_s = time.perf_counter() - t0
        # one-time RTT probe, AFTER the compile clock stops (it
        # is a measurement, not a compile — ~3 round trips must not
        # inflate compile_warmup_s): the report layer subtracts
        # ``stage_<name>_n x rtt_s`` from the legacy PTT_STAGE_TIMING
        # barrier timings (docs/observability.md)
        self.last_stats["rtt_s"] = round(obs.measure_rtt(), 4)
        return compile_s

    def run(self, seed=None, resume: bool = False) -> CheckerResult:
        """``seed``: optional host-enumerated BFS prefix
        ``(packed_rows, parent_gids, action_lanes, level_sizes)`` —
        see :meth:`_load_seed`.  ``resume=True`` rebuilds the full
        device state from the ``checkpoint_path`` frame and continues
        the interrupted run (wall clock cumulative across resumes; the
        time budget gets a fresh clock)."""
        # this run's exclusive host phases, and the compile meter's
        # reading before it (obs/spans.py); every duration of the run
        # is on the monotonic clock the phases use
        clock = self._clock = spans.PhaseClock(obs.new_run_id())
        self._growth = _Growth()
        self._jit0 = spans.compile_meter().snapshot()
        with spans.span("run", run_id=clock.run_id):
            with clock.phase("init"):
                hb = self._begin_run(clock.t0, resume)
            try:
                with self._watcher:
                    if hb is not None:
                        hb.start()
                    return self._run(clock.t0, seed, resume)
            except BaseException as e:
                # the stream must tell WHY it ends when no result
                # record will follow (probe overflow, OOM without a
                # frame, ^C ^C)
                self.tel.emit("error", error=repr(e)[:300])
                raise
            finally:
                if hb is not None:
                    hb.stop()
                faults.set_observer(None)
                self._xprof_close()
                self._watcher = None
                if obs.owns_stream(self._telemetry_arg):
                    self.tel.close()
                self.tel = obs.NULL

    def _reset_ckpt_state(self) -> None:
        """A run's frame and restore counters at zero (a pooled
        checker's next run must not inherit the last run's)."""
        self._ckpt_frames = 0
        self._ckpt_bytes = 0
        self._ckpt_retries = 0
        # the frames' stall on the run loop's thread, whole, and its
        # three parts: the D2H gather, the host's pack of the table's
        # occupied slots, the compressed write
        self._ckpt_write_s = 0.0
        self._ckpt_gather_s = 0.0
        self._ckpt_pack_s = 0.0
        self._ckpt_npz_s = 0.0
        # where the write's deflate ran: the largest pool a frame used
        # (1: the run loop's own thread), the blocks compressed, the
        # threads' own seconds in zlib (the work; ckpt_npz_s is the
        # run loop's blocked seconds)
        self._ckpt_deflate_threads = 0
        self._ckpt_deflate_blocks = 0
        self._ckpt_deflate_cpu_s = 0.0
        # what the frames hold before compression, what crossed the
        # link for them (whole table columns, bucketed slices), the
        # states in them summed, and the last frame's level
        self._ckpt_raw_bytes = 0
        self._ckpt_d2h_bytes = 0
        self._ckpt_states = 0
        self._ckpt_last_level = 0
        # a restore's wall (THIS run's, on resume) and its three parts:
        # the frame's load and decompression, the host's rebuild of the
        # table's columns and the buffers' padding, the upload
        self._restore_s = 0.0
        self._restore_load_s = 0.0
        self._restore_unpack_s = 0.0
        self._restore_upload_s = 0.0
        self._restore_h2d_bytes = 0
        self._resume_level = self._resume_states = None

    def _begin_run(self, t0, resume: bool):
        """Per-run state, the telemetry stream and the crash
        breadcrumbs of one :meth:`run`; returns the heartbeat (or
        None) and leaves the preemption watcher in ``_watcher``."""
        self._budget_t0 = t0
        self._bufs_poisoned = False
        self._last_fpm = None
        self._flush_seq = 0
        # per-run recovery/telemetry state: a fresh run() must not
        # inherit a previous run's degraded capacity or frame counts
        self.rec.reset()
        self._reset_ckpt_state()
        self._fetch_n = 0
        self._fpm_prev = np.zeros((fpset.FPM_LOGICAL_N,), np.int64)
        # fpset_slot_rounds (PR 38): the table's slots summed over
        # probe rounds, folded at each fetch; the rounds already
        # folded, and the valid lanes the ratio starts from
        self._slot_rounds = 0
        self._slot_rounds_at = 0
        self._slot_valid_at = 0
        # fpset_lane_arb_rounds (PR 40): of those rounds, the ones
        # arbitrated among the lanes, the rounds they are a share of,
        # and the step rounds already folded
        self._arb_rounds = self._arb_of = 0
        self._arb_steps_at = np.zeros((fpset.FPM_STEPS,), np.int64)
        # fpset_write_lanes (PR 42): the lanes presented that the
        # table's column scatters were not handed (a device word that
        # wraps, folded at each fetch), the word as last folded, and
        # the lanes presented that the difference starts from
        self._write_saved = self._write_saved_at = 0
        self._write_lanes_at = 0
        # work-unit state (r14): the ``work_*`` counters are PER-RUN
        # (cost attribution prices THIS run; a pooled checker's next
        # job must not inherit the last job's work), so clear them and
        # rebaseline the device-vector / nv-delta trackers
        # (the phase and compile-meter keys likewise: a run that ends
        # in an error must not show the last run's)
        for k in [
            k for k in self.last_stats
            if k.startswith((
                "work_", "host_", "jit_", "level_wall_max_", "restore_",
                "resume_",
            ))
        ]:
            del self.last_stats[k]
        self._wkm_prev = np.zeros((fpset.WKM_LOGICAL_N,), np.int64)
        self._last_wkm_delta: Dict[str, int] = {}
        self._work_nv_prev = 0
        # compact-event deltas baseline at THIS run's starting counter
        # values: the stage counters in last_stats are lifetime
        # cumulative, and a second run() on the same checker must not
        # re-report the first run's dispatches
        self._compact_prev = int(
            self.last_stats.get("stage_compact_n", 0)
        )
        self._compact_prev_s = float(
            self.last_stats.get("stage_compact_s", 0.0)
        )
        self._resume_meta = {}
        # tiered-store per-run state (r16): fresh epochs/counters and a
        # fresh TieredStore — a fresh (non-resume) run WIPES its spill
        # dir (dead prior runs must not leak host/disk bytes); resume
        # restores the cold tiers from the frame's manifest instead
        self._reset_spill_state()
        if self.tiered and not resume:
            # fresh runs own their spill dir; resume builds the store
            # inside _restore_frame from the frame's manifest instead
            self._mk_tstore()
            self.tstore.wipe()
        # per-run dispatch accounting baseline (the stage counters in
        # last_stats are lifetime-cumulative): dispatches_per_level in
        # the result reports THIS run's dispatch/level ratio, and
        # fuse_levels counts THIS run's megakernel-closed levels
        self._disp_prev = self._dispatch_total()
        self.last_stats.pop("fuse_levels", None)
        self._xprof_on = False
        self._xprof_done = False
        # a crash mid-frame-write can leave a dead multi-GB tmp behind
        # (the atomic replace never published it); clear it up front
        ckpt.cleanup_stale_tmp(self.checkpoint_path)
        # telemetry stream: fresh run_id per run() (frames embed it, so
        # a resumed run can link back to the writer of its frame)
        rid = self._clock.run_id
        self.tel = obs.as_telemetry(self._telemetry_arg, run_id=rid)
        self._run_id = self._clock.run_id = self.tel.run_id or rid
        self._snap = {"distinct_states": 0}
        # crash breadcrumbs: fault events flush BEFORE the fault fires
        # (kill sites leave no other trace).  Installed FIRST — before
        # the heartbeat, the RTT probe, or any warmup-adjacent dispatch
        # — so even a level-1/flush-1 drill leaves its breadcrumb
        # (emitting to the null sink is a no-op, so this is
        # unconditional)
        faults.set_observer(
            lambda kind, site, count: self.tel.emit(
                "fault", kind=kind, site=site, count=count
            )
        )
        # the legacy stage-timing mode needs the RTT baseline even when
        # the caller skipped warmup() (report subtracts n x rtt)
        if self._stage_timing and "rtt_s" not in self.last_stats:
            self.last_stats["rtt_s"] = round(obs.measure_rtt(), 4)
        hb = None
        if self.heartbeat_s:
            hb = obs.Heartbeat(
                self.heartbeat_s, self._snap, telemetry=self.tel,
                capacity=self.SCAP,
            )
        # preemption-safe shutdown (TPU-VM contract): SIGTERM/SIGINT
        # request a checkpoint at the next level boundary; only armed
        # when there is a frame path to write to
        watcher = ckpt.PreemptionWatcher(
            enabled=bool(self.checkpoint_path), log=self._log
        )
        self._watcher = watcher
        return hb

    def _emit_header(self, resume: bool):
        """The run-header record: config signature, device, engine —
        plus, on resume, the writer identity of the frame being resumed
        (``resume_of`` / ``resume_frame_seq``) so stream files chain."""
        if not self.tel.enabled:
            return
        try:
            dev = str(jax.devices()[0])
        except Exception:  # noqa: BLE001 — headers must never kill a run
            dev = "unknown"
        f = dict(
            engine="device_bfs",
            device=dev,
            **obs.IMPL_FIELDS,
            fuse=self.fuse,
            fuse_group=self.RMAX,
            config_sig=self._config_sig(),
            wall_unix=round(time.time(), 3),
            max_states=self.SCAP,
            sub_batch=self.G,
            flush_factor=self.FLUSH,
            key_cols=self.K,
            key_exact=bool(self.keys.exact),
            rows_window=self.rows_window,
            invariants=list(self.invariant_names),
            resume=resume,
            # REQUIRED since schema v8, a constant null
            profile_sig=None,
            # tiered-store budget (r16, schema v9): None on untiered
            # runs — always present so spill trajectories split
            hbm_budget=self.hbm_budget,
            # tenant identity (r17, schema v10): set per slice by the
            # daemon scheduler, None on standalone runs — always
            # present so per-tenant attribution never needs a join
            tenant=getattr(self, "tenant", None),
            warm=getattr(self, "warm", None),
            # v15: distributed-trace identity (fleet dispatcher ->
            # scheduler -> engine; None on standalone runs)
            trace_id=getattr(self, "trace_id", None),
            # workload class (r18, schema v11): always "check" here —
            # the streaming walker swarm (sim/) is its own engine
            mode="check",
        )
        rm = self._resume_meta
        if resume and rm:
            if rm.get("run_id"):
                f["resume_of"] = rm["run_id"]
            if rm.get("frame_seq") is not None:
                f["resume_frame_seq"] = rm["frame_seq"]
            if rm.get("level") is not None:
                f["resume_level"] = rm["level"]
        self.tel.emit("run_header", **f)

    # ------------------------------------------------------ xprof hooks

    def _xprof_tick(self, level_next: int):
        """Start/stop the JAX profiler trace around the configured
        level window (``xprof_levels=(lo, hi)``; no window = the whole
        run).  Real-chip usage: docs/observability.md."""
        if not self.xprof_dir:
            return
        lo, hi = self.xprof_levels or (0, 1 << 30)
        if self._xprof_on and level_next > hi:
            self._xprof_close()
        if (
            not self._xprof_on
            and not self._xprof_done
            and lo <= level_next <= hi
        ):
            jax.profiler.start_trace(self.xprof_dir)
            self._xprof_on = True
            self.tel.emit(
                "xprof", action="start", level=level_next,
                dir=self.xprof_dir,
            )

    def _xprof_close(self):
        if not self._xprof_on:
            return
        try:
            jax.profiler.stop_trace()
        finally:
            self._xprof_on = False
            self._xprof_done = True  # one window per run
        self.tel.emit("xprof", action="stop", dir=self.xprof_dir)

    @property
    def _host_wait_s(self) -> float:
        """Seconds this run's host has been blocked on stats fetches:
        the ``fetch`` phase of its clock."""
        return self._clock.seconds_of("fetch")

    def _run(self, t0, seed, resume) -> CheckerResult:
        with self._clock.phase("init"):
            frame = self._start(t0, seed, resume)
        # everything of the level loop that is no phase of its own
        # (level replay, telemetry emits, the log line, fault polls)
        # is ``account``
        with self._clock.phase("account"):
            return self._run_recoverable(*frame)

    def _start(self, t0, seed, resume):
        """Fresh, seeded or restored device state up to the first
        level boundary: the arguments of :meth:`_run_recoverable`."""
        if resume:
            if seed is not None:
                raise ValueError("resume and seed are mutually exclusive")
            if not self.checkpoint_path:
                raise ValueError("resume requires checkpoint_path")
            t_restore = time.perf_counter()
            (
                bufs, st, rb, level_sizes, level_base, nf, saved_wall,
            ) = self._restore_frame()
            # the context-switch restore cost (frame load + device
            # rebuild) — the serve bench's counterpart to the frame
            # write stall; the scheduler reads it per resumed slice
            self._restore_s = time.perf_counter() - t_restore
            # what the run resumed from: the frame's level and states
            # (cli.recovered_line prints them), and the restore's wall
            # by part
            self._resume_level = len(level_sizes)
            self.last_stats.update(
                restore_s=round(self._restore_s, 3),
                restore_load_s=self._restore_load_s,
                restore_unpack_s=self._restore_unpack_s,
                restore_upload_s=self._restore_upload_s,
                restore_h2d_bytes=self._restore_h2d_bytes,
                resume_level=self._resume_level,
                resume_states=self._resume_states,
            )
            t0 = time.perf_counter() - saved_wall
            self.rec.arm()  # the on-disk frame is valid
            self._emit_header(resume=True)
            stats = self._fetch(st)
            return (
                t0, bufs, st, rb, level_sizes, level_base, nf, stats
            )
        m = self.model
        self._emit_header(resume=False)
        # level-1 fault site: the run loop's poll counts start at 2
        # (the first level expanded AFTER init), so without this a
        # kill@level:1 drill would never fire — and the observer above
        # is already installed, so the breadcrumb lands first
        kinds = faults.poll("level", 1)
        if "oom" in kinds:
            raise faults.oom_error("level", 1)
        n_inv = len(self.invariant_names)
        K = self.K
        bufs = {
            "vk": fpset.empty_cols(self.TCAP, K),
            "ak": tuple(
                jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                for _ in range(K)
            ),
            "arows": jnp.zeros((self.W, self.ACAP), jnp.uint32),
            "rows": jnp.zeros((self._rows_len(),), jnp.uint32),
            "parent": jnp.zeros((self._logs_len(),), jnp.int32),
            "lane": jnp.zeros((self._logs_len(),), jnp.int32),
        }
        if self.tiered:
            # per-slot eviction generations (0 = empty/untagged);
            # tagged once per level boundary (store/sieve.py)
            bufs["gen"] = jnp.zeros((self.TCAP + 1,), jnp.int32)
        st = {
            "n_visited": jnp.int32(0),
            "dead_gid": BIG,
            "viol": jnp.full((n_inv,), int(BIG), jnp.int32),
            # device-accumulated fpset metrics [flushes, probe rounds,
            # failures] — ride the regular stats fetch
            "fpm": jnp.zeros((FPM_N,), jnp.int32),
        }
        if self.fuse == "level":
            # device-accumulated work units (r14) — ride the fused
            # kernel's packed stats vector, zero extra syncs
            st["wkm"] = jnp.zeros((WKM_N,), jnp.int32)

        # frontier-window state: gid of rows[0], and whether row writes
        # are still landing in the window (False = diverted to scratch;
        # the level being built can no longer become a frontier)
        rb = {"row_base": 0, "rows_ok": True}

        if seed is not None:
            level_sizes = self._load_seed(bufs, st, seed)
            stats = self._fetch(st)
            # early anchor record: the sustained-60s window needs a
            # reference point before the deep levels begin
            self._emit_metrics(
                t0, len(level_sizes), 0, int(stats[0]),
                level_sizes[-1] if level_sizes else 0,
                partial=True,
            )
            fv = self._first_viol(stats)
            gid = fv[1] if fv is not None else None
            if gid is not None:
                # violation inside the seeded prefix: the diameter is the
                # violating state's level, not the full seed depth
                cum = 0
                for li, cnt in enumerate(level_sizes):
                    cum += cnt
                    if gid < cum:
                        level_sizes = level_sizes[: li + 1]
                        break
        else:
            # ---- level 1: initial states (compaction.tla:188-202) ----
            n_init = m.n_initial
            if n_init > self.SCAP:
                raise ValueError("initial-state set exceeds max_states")
            if (
                self.rows_window == "frontier"
                and n_init + self.APAD > self.LCAP
            ):
                raise ValueError(
                    f"initial level ({n_init} states) exceeds the "
                    f"frontier rows window; raise row_cap_states"
                )
            if self.tiered and (
                n_init + self.APAD > min(self._capl(), self._capp())
                or n_init + self.ACAP > self._capv()
            ):
                # level 1 lands before any spill boundary exists:
                # honor it past the budget (warned once) — the same
                # correctness-first valve as the frontier windows.
                # The TABLE ceiling rises too: the whole level must be
                # hot until the first boundary can evict
                self._lcap_max = max(
                    self._lcap_max, n_init + self.APAD
                )
                self._pcap_max = max(
                    self._pcap_max, n_init + self.APAD
                )
                while self._tcap_max // 2 < n_init + self.ACAP:
                    self._tcap_max *= 2
                if not self._budget_overridden:
                    self._budget_overridden = True
                    self._log(
                        "WARNING: hbm_budget too small for the "
                        "initial level — growing past the budget"
                    )
            self._grow_visited(bufs, n_init + self.ACAP)
            self._grow_store(bufs, n_init + self.APAD)
            w = 0
            group_base = 0
            for f_off in range(0, n_init, self.NCs):
                # init work (r14): live initial-state lanes generated
                # (host-dispatched in BOTH fuse modes, so parity holds)
                self._work_add(
                    init_lanes=min(self.NCs, n_init - f_off)
                )
                with self._clock.phase("dispatch", level=1):
                    with self._clock.upload("ptt_init", 2):
                        f_off_d = jnp.int32(f_off)
                        acc_off_d = jnp.int32(w * self.NCs)
                    with self._clock.call("ptt_init"):
                        out = self._init_jit()(
                            bufs["ak"], bufs["arows"], f_off_d, acc_off_d
                        )
                bufs["ak"], bufs["arows"] = out[:K], out[K]
                w += 1
                if w == self.FLUSH or f_off + self.NCs >= n_init:
                    self._flush_acc(
                        bufs, st, rb, w * self.NCs, group_base, True
                    )
                    group_base = f_off + self.NCs
                    w = 0
            stats = self._fetch(st)
            level_sizes = [int(stats[0])]

        nv = int(stats[0])
        level_base = nv - (level_sizes[-1] if level_sizes else 0)
        nf = nv - level_base
        return t0, bufs, st, rb, level_sizes, level_base, nf, stats

    def _fold_lane_arb(self, fpm, nq: int) -> None:
        """fpset_lane_arb_rounds: of the probe rounds since the last
        fold, all run by batches of ``nq`` lanes on the table as it is
        now, those at a step of the ladder that arbitrates among its
        lanes (``fpset.arbitrates_among_lanes``: a static rule, so the
        host can apply it to ``fpset_step_rounds``' deltas)."""
        steps = np.asarray(fpm, np.int64)[
            fpset.FPM_N: fpset.FPM_WRITE_SAVED
        ]
        delta = steps - self._arb_steps_at
        self._arb_rounds += fpset.lane_arb_rounds(
            delta, nq, self.TCAP, self.fps_dense, self.fps_stages
        )
        self._arb_of += int(delta.sum())
        self._arb_steps_at = steps

    def _fetch(self, st, vec=None):
        """One stats fetch (the only hot-path host sync): returns the
        numpy stats vector and fail-stops on fpset probe overflow.
        Every zero-sync device counter (:data:`FPM_N`) rides this
        fetch; the heartbeat snapshot and the per-flush telemetry
        deltas update here — nothing else ever syncs.  ``vec`` is an
        already-dispatched stats vector (the fused megakernel returns
        one, so a fused level pays NO separate stats dispatch); its
        prefix layout matches ``_stats_jit`` and any tail beyond the
        fpm block is returned untouched for the caller to parse."""
        # the host blocked on the device: host_fetch_s (= host_wait_s)
        with self._clock.phase("fetch"):
            dev = vec
            if dev is None:
                with self._clock.call("ptt_stats"):
                    dev = self._stats_jit()(
                        st["n_visited"], st["dead_gid"], st["viol"],
                        st["fpm"],
                    )
            out = np.asarray(dev)
        self._fetch_n += 1
        nv = int(out[0])
        self._snap["distinct_states"] = nv
        if self.tiered and (
            self.tstore is None or not self.tstore.has_cold_keys
        ):
            # before the first eviction the hot table holds exactly
            # the distinct set; afterwards _resolve_cold_misses tracks
            # inserts per flush
            self._hot_n = nv
        # work-unit accounting (r14): a fused stats vector carries the
        # in-kernel work counters — fold their deltas into the per-run
        # ``work_*`` totals; whatever part of the nv delta the kernel
        # did NOT append was appended by stage-chain dispatches (the
        # init path, stage mode), so appends are never double-counted
        # and never missed.  Free host arithmetic on an already-fetched
        # vector — zero extra syncs.
        k_append = 0
        if vec is not None and self.fuse == "level":
            n_inv = len(self.invariant_names)
            wkm = out[2 + n_inv + FPM_N: 2 + n_inv + FPM_N + WKM_N]
            wl = fpset.wkm_logical(wkm)
            dw = wl - self._wkm_prev
            self._wkm_prev = wl
            self._last_wkm_delta = {
                "expand_rows": int(dw[0]),
                "probe_lanes": int(dw[1]),
                "compact_elems": int(dw[2]),
                "append_rows": int(dw[3]),
                "groups": int(dw[4]),
            }
            self._work_add(**self._last_wkm_delta)
            k_append = int(dw[3])
        stage_append = nv - getattr(self, "_work_nv_prev", nv) - k_append
        if stage_append > 0:
            self._work_add(append_rows=stage_append)
        self._work_nv_prev = nv
        n_inv = len(self.invariant_names)
        self._last_fpm = out[2 + n_inv: 2 + n_inv + FPM_N]
        # fpset_slot_rounds: the rounds since the last fetch all ran
        # on the table as it is now — a table grows only after a fetch
        # and before the next dispatch (_grow_fused, _grow_visited).
        # A rehash's own rounds are in no fpm vector and stay out
        rounds = int(self._last_fpm[1])
        self._slot_rounds += (rounds - self._slot_rounds_at) * self.TCAP
        self._slot_rounds_at = rounds
        self._fold_lane_arb(self._last_fpm, self.ACAP)
        # fpset_write_lanes: the device's word wraps at 2^32
        saved = int(self._last_fpm[fpset.FPM_WRITE_SAVED])
        self._write_saved += (saved - self._write_saved_at) & 0xFFFFFFFF
        self._write_saved_at = saved
        self._snap["occupancy"] = nv / max(self.TCAP, 1)
        if len(self._last_fpm) >= 4:
            # TLC's "states generated": candidate lanes examined
            # (64-bit reassembly of the hi/lo words, r12)
            self._snap["generated"] = int(
                fpset.fpm_logical(self._last_fpm)[3]
            )
        self._emit_flush_event(nv)
        self._emit_compact_event()
        if self._last_fpm[2]:
            # probe overflow: lanes were dropped by flushes
            # already appended — the counts cannot be trusted,
            # so this is a hard abort, not a truncation
            raise RuntimeError(
                "fpset probe overflow "
                f"({int(self._last_fpm[2])} lanes) — "
                + fpset.OVERFLOW_HINT
            )
        return out

    def _emit_flush_event(self, nv: int):
        """One telemetry record per stats fetch covering the flushes
        since the previous fetch (deltas of the device-accumulated
        counters) — per-flush visibility without per-flush syncs."""
        if not self.tel.enabled or self._last_fpm is None:
            return
        # logical view: valid-lane hi/lo words reassembled to 64 bits,
        # so the stream deltas stay honest past the int32 wrap (r12)
        cur = fpset.fpm_logical(self._last_fpm)
        d = cur - self._fpm_prev
        if d[0] <= 0:
            return
        self._fpm_prev = cur
        self.tel.emit(
            "flush",
            flushes=int(d[0]),
            probe_rounds=int(d[1]),
            failures=int(d[2]),
            valid_lanes=int(d[3]),
            avg_probe_rounds=round(int(d[1]) / max(int(d[0]), 1), 2),
            max_probe_rounds=int(cur[4]),
            occupancy=round(nv / max(self.TCAP, 1), 4),
            distinct_states=nv,
        )

    def _emit_compact_event(self):
        """One ``compact`` record per stats fetch covering the compact
        dispatches since the previous fetch — free host-side counters
        (``stage_compact_n``; drain seconds under PTT_STAGE_TIMING),
        zero extra device syncs."""
        if not self.tel.enabled:
            return
        n = int(self.last_stats.get("stage_compact_n", 0))
        d = n - self._compact_prev
        if d <= 0:
            return
        self._compact_prev = n
        f = dict(dispatches=d, impl=obs.IMPL_FIELDS["compact_impl"])
        s = self.last_stats.get("stage_compact_s")
        if s is not None:
            f["drain_s"] = round(s - self._compact_prev_s, 4)
            self._compact_prev_s = s
        self.tel.emit("compact", **f)

    @spans.in_phase("dispatch")
    def _flush_acc(self, bufs, st, rb, n_acc, acc_base, is_init):
        """Dispatch the dedup + append for the current accumulator
        fill (``n_acc`` valid lanes covering source rows starting
        at ``acc_base``): table probe-or-insert, compaction, append."""
        K = self.K
        # host-side work units (r14), mirroring the fused kernel's
        # in-kernel definitions exactly: the full accumulator width is
        # what the flush probes and the compaction moves (dense cost is
        # width-bound, not valid-lane-bound), and each call is one
        # flush group
        self._work_add(
            probe_lanes=self.ACAP, compact_elems=self.ACAP, groups=1
        )
        self._flush_seq += 1
        kinds = faults.poll("flush", self._flush_seq)
        if "oom" in kinds:
            raise faults.oom_error("flush", self._flush_seq)
        if "fpset_fail" in kinds:
            # synthetic stage overflow: account one dropped lane in
            # the device metrics — the next stats fetch fail-stops
            # exactly like a real probe overflow would
            st["fpm"] = st["fpm"] + jnp.asarray(
                [0, 0, 1] + [0] * (FPM_N - 3), jnp.int32
            )
        clock = self._clock
        with clock.upload("ptt_fpflush2", 1):
            n_acc_d = jnp.int32(n_acc)
        with clock.call("ptt_fpflush2"):
            out = self._fpflush_jit()(
                bufs["vk"], bufs["ak"], n_acc_d, st["fpm"]
            )
        self._stage_mark("flush", out)
        bufs["vk"] = out[:K]
        n_new, flag_acc, st["fpm"] = out[K], out[K + 1], out[K + 2]
        if self.tiered:
            # cold-tier miss resolution (r16): lanes the hot filter
            # flagged new may be duplicates of EVICTED keys; resolve
            # the sieved batch against the cold runs and merge the
            # verdicts back BEFORE the compaction that assigns gids —
            # tiered gid assignment stays identical to untiered
            n_new, flag_acc = self._resolve_cold_misses(
                bufs, flag_acc, n_new
            )
        # compact in its own dispatch (round 10): per-dispatch stage
        # accounting, and the donated accumulator comes back as the
        # compacted matrix — recycled below as the next fill's buffer
        # (its stale content is overwritten by expand DUS windows and
        # masked by n_acc at the next flush, the same contract the
        # accumulator always had)
        with clock.call("ptt_compact"):
            out = self._compact_jit()(bufs["arows"], flag_acc)
        crows, idx = self._stage_mark("compact", out)
        bufs["arows"] = crows
        with clock.upload("ptt_append", 5):
            acc_base_d, is_init_d = jnp.int32(acc_base), jnp.bool_(is_init)
            row_base_d = jnp.int32(rb["row_base"])
            rows_ok_d = jnp.bool_(rb["rows_ok"])
            log_base_d = jnp.int32(rb["row_base"] if self.tiered else 0)
        with clock.call("ptt_append"):
            out = self._append_jit()(
                bufs["rows"], bufs["parent"], bufs["lane"],
                crows, idx, n_new, st["n_visited"],
                st["viol"], acc_base_d, is_init_d,
                row_base_d, rows_ok_d, log_base_d,
            )
        (
            bufs["rows"], bufs["parent"], bufs["lane"],
            st["n_visited"], st["viol"],
        ) = self._stage_mark("append", out)

    # ------------------------------------ tiered-store orchestration

    def _mk_tstore(self) -> None:
        """Fresh TieredStore for this run (durable when the run
        checkpoints — spill files live beside the frame under
        ``<checkpoint_path>.spill/`` so suspend/crash resume restores
        the whole tiered store through the frame's manifest)."""
        if self.tstore is not None:
            self.tstore.close()
        sdir = self._spill_dir_arg or (
            f"{self.checkpoint_path}.spill"
            if self.checkpoint_path
            else None
        )
        self.tstore = TieredStore(
            self.K,
            spill_dir=sdir,
            compress=self.spill_compress,
            durable=bool(self.checkpoint_path),
        )

    def _spill_tier_label(self) -> str:
        return "ram+disk" if self.tstore.durable else "ram"

    @staticmethod
    def _spill_fetch_size(n: int, length: int) -> int:
        """Elements a fetch of ``n`` from a buffer of ``length`` brings
        over: a power of two from ``SPILL_FETCH_MIN`` up, or the
        buffer's own length."""
        return min(max(SPILL_FETCH_MIN, 1 << max(n - 1, 0).bit_length()),
                   length)

    @classmethod
    def _spill_fetch_window(cls, n: int, off: int, length: int):
        """``(size, start)`` of the bucketed slice that holds
        ``[off, off + n)`` of a buffer of ``length``: the bucket of
        :meth:`_spill_fetch_size`, pushed back where it would pass the
        buffer's end."""
        size = cls._spill_fetch_size(n, length)
        return size, min(off, length - size)

    def _bucketed_fetch(self, buf, n: int, off: int, program):
        """``(what crossed the link, its view of buf[off: off + n])``:
        the buffer whole where the bucket
        (:meth:`_spill_fetch_size`) is its length, else the bucket,
        sliced on the device by ``program(buf, start, size=)``."""
        length = buf.shape[0]
        size, start = self._spill_fetch_window(n, off, length)
        if size != length:
            name = program.__name__
            with self._clock.upload(name, 1):
                start_d = jnp.int32(start)
            with self._clock.call(name):
                buf = program(buf, start_d, size=size)
        got = np.asarray(buf)
        return got, got[off - start: off - start + n]

    def _spill_fetch(self, buf, n: int, off: int = 0) -> np.ndarray:
        """``buf[off: off + n]`` on the host.  The device slices a
        bucketed length (:meth:`_spill_fetch_size`) and the host trims
        it, so a check compiles a handful of fetch programs and not one
        a flush; ``spill_d2h_bytes`` counts what was needed,
        ``spill_d2h_padded_bytes`` what crossed the link."""
        t0 = time.perf_counter()
        with spans.span("spill.fetch"):
            got, out = self._bucketed_fetch(buf, n, off, ptt_spill_fetch)
        dt = time.perf_counter() - t0
        if len(got) != n:
            out = out.copy()  # what is kept does not hold the padding
        self._note_spill_fetch(dt, 1, out.nbytes, got.nbytes)
        return out

    def _spill_fetch_cols(self, cols, n: int, off: int = 0):
        """``[c[off: off + n] for c in cols]`` on the host, for device
        columns of ONE length (``uint32`` or ``int32``), in ONE round
        trip: :func:`ptt_spill_fetch_cols` packs their bucketed slices
        (:meth:`_spill_fetch_size`, as :meth:`_spill_fetch` buckets
        one) into a flat word array whose rows the host trims and views
        back.  Accounted as the planes fetched one by one would be:
        ``spill_d2h_bytes`` what was needed, ``spill_d2h_padded_bytes``
        a bucket a column."""
        cols = tuple(cols)
        size, start = self._spill_fetch_window(n, off, cols[0].shape[0])
        t0 = time.perf_counter()
        with spans.span("spill.fetch"):
            with self._clock.upload("ptt_spill_fetch_cols", 1):
                start_d = jnp.int32(start)
            with self._clock.call("ptt_spill_fetch_cols"):
                dev = ptt_spill_fetch_cols(cols, start_d, size=size)
            got = np.asarray(dev).reshape(len(cols), size)
        dt = time.perf_counter() - t0
        outs = [
            row[off - start: off - start + n].view(c.dtype)
            for c, row in zip(cols, got)
        ]
        if size != n:
            # what is kept does not hold the padding
            outs = [out.copy() for out in outs]
        self._note_spill_fetch(
            dt, len(cols), sum(out.nbytes for out in outs), got.nbytes
        )
        return outs

    def _note_spill_fetch(self, dt: float, planes: int, kept: int,
                          crossed: int) -> None:
        """Account one round trip of ``dt`` seconds that brought
        ``planes`` columns: ``kept`` bytes needed, ``crossed`` over the
        link."""
        self._spill_fetch_s += dt
        self.tstore.note_transfer(dt)
        self._spill_fetches += 1
        self._spill_fetch_planes += planes
        self._spill_d2h_bytes += kept
        self._spill_d2h_padded_bytes += crossed

    @spans.in_phase("spill")
    def _resolve_cold_misses(self, bufs, flag_acc, n_new):
        """Sieve the flush's hot-filter survivors, resolve them
        against the cold runs in ``MISS_BATCH``-wide batches, and
        clear the false-new lanes.  A batch's ``K`` key columns and
        its lanes cross the link in ONE fetch
        (:meth:`_spill_fetch_cols`), after the one sync that reads the
        sieve's count.  Returns the corrected ``(n_new, flag_acc)``.
        No cold keys yet = free (the hot verdict is exact; ``_hot_n``
        tracks lazily off the fetches)."""
        if not self.tstore.has_cold_keys:
            return n_new, flag_acc
        K = self.K
        clock = self._clock
        with clock.call("ptt_spill_sieve"):
            out = self._sieve_jit()(*bufs["ak"], flag_acc)
        self._stage_mark("sieve", out)
        kc, lanes, n_dev = out[:K], out[K], out[K + 1]
        with spans.span("spill.sieve_wait"):
            n = int(np.asarray(n_dev))
        self._spill_sync_n += 1
        false_lanes = []
        for off in range(0, n, MISS_BATCH):
            m = min(MISS_BATCH, n - off)
            *kq, lq = self._spill_fetch_cols((*kc, lanes), m, off)
            with spans.span("spill.lookup"):
                dup = self.tstore.lookup_keys(kq)
            if dup.any():
                false_lanes.append(lq[dup])
        self._hot_n += n
        k = 0
        if false_lanes:
            fl = np.concatenate(false_lanes).astype(np.int32)
            k = len(fl)
        P = self.UNFLAG_P
        for off in range(0, k, P):
            chunk = fl[off: off + P]
            padded = np.zeros((P,), np.int32)
            padded[: len(chunk)] = chunk
            with clock.upload("ptt_spill_unflag", 2):
                lanes_d, n_d = jnp.asarray(padded), jnp.int32(len(chunk))
            with clock.call("ptt_spill_unflag"):
                flag_acc = self._unflag_jit()(flag_acc, lanes_d, n_d)
            self._stage_mark("unflag", flag_acc)
        # the flush's corrected count: an argument of its append
        with clock.upload("ptt_append", 1):
            n_new = jnp.int32(n - k)
        return n_new, flag_acc

    @spans.spanned("spill.evict")
    def _evict_cold_keys(self, bufs, cutoff: int) -> int:
        """Evict generations <= cutoff to the cold tier: extract +
        device-sort, D2H the dense prefix, rehash the survivors (probe
        chains break across holes), restart the epoch clock.  Returns
        the evicted count."""
        K = self.K
        self._spill_evict_slots += self.TCAP
        clock = self._clock
        with clock.upload("ptt_spill_evict", 1):
            cutoff_d = jnp.int32(cutoff)
        with clock.call("ptt_spill_evict"):
            out = self._evict_jit()(*bufs["vk"], bufs["gen"], cutoff_d)
        self._stage_mark("evict", out)
        holed, gen = out[:K], out[K]
        ev, n_dev = out[K + 1: 2 * K + 1], out[2 * K + 1]
        n = int(np.asarray(n_dev))
        if n == 0:
            # nothing at or below the cutoff: keep the (unchanged)
            # table — where(False, ...) returned the originals
            bufs["vk"], bufs["gen"] = holed, gen
            return 0
        ev_np = self._spill_fetch_cols(ev, n)
        with clock.call("ptt_spill_rehash"):
            out2 = self._rehash_same_jit()(*holed)
        self._stage_mark("evict", out2)
        vk, failed = out2[:K], out2[K]
        if int(np.asarray(failed)):
            raise RuntimeError(
                "fpset rehash overflow during eviction — load-factor "
                "contract broken (bug)"
            )
        bufs["vk"] = vk
        # survivors restart at the base generation (their finer ages
        # died with the old slot layout — documented coarsening)
        gen0 = jnp.zeros((self.TCAP + 1,), jnp.int32)
        with clock.upload("ptt_spill_tag", 1):
            epoch_d = jnp.int32(1)
        with clock.call("ptt_spill_tag"):
            bufs["gen"] = self._tag_jit()(*vk, gen0, epoch_d)
        self._epoch = 2
        self.tstore.evict_keys(ev_np)
        self._hot_n -= n
        self._spill_active = True
        self._log(
            f"spill: evicted {n} cold keys to the "
            f"{self._spill_tier_label()} tier (hot {self._hot_n})"
        )
        return n

    @spans.in_phase("spill")
    def _ensure_hot_capacity(self, bufs, head: int) -> None:
        """The tiered replacement for unbounded visited growth: admit
        ``head`` more states in the hot table by growing WITHIN the
        budget, else by evicting cold generations; only when neither
        suffices does the budget get overridden (correctness first,
        with a warning)."""
        if self._hot_n + head <= self.VCAP:
            return
        if self.TCAP < self._tcap_max:
            self._grow_visited(bufs, self._hot_n + head)
            if self._hot_n + head <= self.VCAP:
                return
        # evict everything except the newest tagged generation, then
        # (if still short) everything tagged
        for cutoff in (self._epoch - 2, self._epoch - 1):
            if cutoff >= 1 and self._hot_n + head > self.VCAP:
                self._evict_cold_keys(bufs, cutoff)
        if self._hot_n + head <= self.VCAP:
            return
        # nothing evictable (the live level alone overflows the
        # budgeted table): grow past the budget rather than abort
        if not self._budget_overridden:
            self._budget_overridden = True
            self._log(
                "WARNING: hbm_budget too small for the live frontier "
                "— growing the hot table past the budget"
            )
        self._tcap_max *= 2
        self._grow_visited(bufs, self._hot_n + head)

    @spans.spanned("spill.rows")
    def _spill_aged(self, bufs, rb, upto: int, nv: int) -> None:
        """Spill rows + trace logs of [row_base, upto) to the cold
        tier and slide both device windows down (rows and logs share
        one base in tiered mode)."""
        base = rb["row_base"]
        if upto <= base:
            return
        rows_np = self._spill_fetch(bufs["rows"], (upto - base) * self.W)
        par_np, lan_np = self._spill_fetch_cols(
            (bufs["parent"], bufs["lane"]), upto - base
        )
        self.tstore.spill_rows(base, upto, rows_np)
        self.tstore.spill_logs(base, upto, par_np, lan_np)
        clock = self._clock
        # the two shifts take the same two scalars
        with clock.upload("ptt_spill_shift", 2):
            src_d, keep_d = jnp.int32(upto - base), jnp.int32(nv - upto)
        with clock.call("ptt_spill_shift"):
            bufs["rows"] = self._shift_jit()(bufs["rows"], src_d, keep_d)
        with clock.call("ptt_spill_logshift"):
            bufs["parent"], bufs["lane"] = self._logshift_jit()(
                bufs["parent"], bufs["lane"], src_d, keep_d
            )
        rb["row_base"] = upto
        self._spill_active = True

    @spans.in_phase("spill")
    def _tiered_ensure_windows(self, bufs, rb, level_base: int,
                               need_abs: int, nv: int) -> None:
        """Admit ``need_abs`` absolute states in the row/log windows:
        spill the aged range first, then grow within the budget, and
        only past both override the budget (warning)."""
        need = need_abs - rb["row_base"]
        if need <= min(self.LCAP, self.PCAP):
            return
        if level_base > rb["row_base"]:
            self._spill_aged(bufs, rb, level_base, nv)
            need = need_abs - rb["row_base"]
        if need <= min(self.LCAP, self.PCAP):
            return
        if self.LCAP < self._lcap_max or self.PCAP < self._pcap_max:
            self._grow_store(bufs, need)
            need = need_abs - rb["row_base"]
        if need <= min(self.LCAP, self.PCAP):
            return
        if not self._budget_overridden:
            self._budget_overridden = True
            self._log(
                "WARNING: hbm_budget too small for the live frontier "
                "windows — growing past the budget"
            )
        self._lcap_max = max(self._lcap_max * 2, need)
        self._pcap_max = max(self._pcap_max * 2, need)
        self._grow_store(bufs, need)

    def _tiered_pressure(self, nv: int, nf: int,
                         row_base: int) -> bool:
        """Would the next level's worst case overflow the budget-
        capped tiers?  True latches ``_spill_active`` — the fused
        megakernel hands the level loop to the spill-aware stage
        path (the budget consult that replaces truncation)."""
        if self._spill_active:
            return True
        hot = self._hot_n + 2 * self.ACAP > self._capv()
        win = (
            nv - row_base + self.APAD + self.G
            > min(self._lcap_max, self._pcap_max)
        )
        if hot or win:
            self._spill_active = True
        return self._spill_active

    @spans.in_phase("spill")
    def _tiered_boundary(self, bufs, st, rb, level_base: int,
                         nf: int, nv: int, level: int) -> None:
        """Level-boundary spill housekeeping: tag the epoch, spill
        aged rows/logs once spilling is active, keep the hot table
        inside the budget, and emit the cumulative ``spill`` record."""
        with self._clock.upload("ptt_spill_tag", 1):
            epoch_d = jnp.int32(self._epoch)
        with self._clock.call("ptt_spill_tag"):
            bufs["gen"] = self._tag_jit()(
                *bufs["vk"], bufs["gen"], epoch_d
            )
        self._epoch += 1
        # window pressure for the NEXT level: frontier + expand slack
        # + one blind append window
        self._tiered_ensure_windows(
            bufs, rb, level_base, level_base + nf + self.G + self.APAD,
            nv,
        )
        if self._spill_active and level_base > rb["row_base"]:
            self._spill_aged(bufs, rb, level_base, nv)
        self._ensure_hot_capacity(bufs, 2 * self.ACAP)
        self._emit_spill(level)

    def _emit_spill(self, level: int, final: bool = False) -> None:
        """One cumulative ``spill`` record per boundary with new spill
        work (schema v9; the validator cross-checks monotonicity), and
        one at the result (``final``), whose byte counts are the run's.
        The background worker is joined where correctness needs it,
        telemetry on or off alike: by a durable store at every such
        boundary (a write that failed has to stop the run there, and a
        frame's manifest needs every file), by an in-RAM store never
        before the result (an evicted run is queryable at once; a
        boundary's record carries the bytes encoded so far).
        A degraded store (ENOSPC on the durable writer) flags its
        record ``degraded`` and is emitted once even without fresh
        spill work — the honest breadcrumb behind
        ``stop_reason="spill_enospc"``."""
        if self.tstore is None:
            return
        s = self.tstore.stats
        mark = (
            s.evictions + s.keys_evicted + s.rows_evicted
            + s.misses_resolved
        )
        fresh = mark != self._spill_emit_mark
        if fresh and self.tstore.durable:
            with spans.span("spill.join"):
                self.tstore.flush()  # waits are measured (blocked_s)
        degraded = bool(self.tstore.degraded)
        force = (final and mark > 0) or (
            degraded and not self._spill_degraded_emitted
        )
        if not (fresh or force):
            return
        self._spill_emit_mark = mark
        if not self.tel.enabled:
            return
        if degraded:
            self._spill_degraded_emitted = True
        self.tel.emit(
            "spill",
            tier=self._spill_tier_label(),
            level=level,
            keys_evicted=int(s.keys_evicted),
            rows_evicted=int(s.rows_evicted),
            bytes_raw=int(s.bytes_raw),
            bytes_comp=int(s.bytes_comp),
            transfer_s=round(s.transfer_s, 4),
            misses_resolved=int(s.misses_resolved),
            miss_hits=int(s.miss_hits),
            evictions=int(s.evictions),
            hot_keys=int(self._hot_n),
            fetches=self._spill_fetches,
            fetch_planes=self._spill_fetch_planes,
            **({"degraded": True} if degraded else {}),
        )

    def _run_recoverable(
        self, t0, bufs, st, rb, level_sizes, level_base, nf, stats
    ) -> CheckerResult:
        """The level loop under the HBM-exhaustion recovery contract:
        a RESOURCE_EXHAUSTED with a valid checkpoint frame on disk
        frees the (possibly poisoned) device buffers, rebuilds state
        from the frame, and continues at degraded capacity — halved
        dispatch group-ahead and frozen growth headroom.  Only when
        recovery itself exhausts memory (or no fresh frame was written
        since the last recovery) does the run truncate with
        ``stop_reason="hbm"``."""
        while True:
            try:
                return self._level_loop(
                    t0, bufs, st, rb, level_sizes, level_base, nf,
                    stats,
                )
            except recovery.HbmExhausted as hx:
                last = (hx.nv, hx.level_sizes, hx.msg)
                # the rebuild happens OUTSIDE this except block: the
                # exception's traceback pins _level_loop's frame
                # locals (accumulator tuples, expand windows) and the
                # chained original XLA error — restoring under it
                # would re-OOM exactly when memory is tightest
            # degraded capacity for the retry: halve the dispatch
            # group-ahead (fewer in-flight flushes = smaller
            # worst-case transients) and freeze growth headroom
            self.rec.degrade()
            self.tel.emit(
                "hbm_recovery",
                recovery_n=self._hbm_recovered,
                group=self.group,
                distinct_states=last[0],
                error=last[2][:200],
            )
            self._log(
                "HBM exhausted: recovering from the last "
                f"checkpoint frame (recovery #{self._hbm_recovered}"
                f", group={self.group}) — {last[2][:120]}"
            )
            # drop every device buffer reference BEFORE the restore
            # allocates: the poisoned/donated storage must be freed
            # first or the rebuild would OOM on top of it
            bufs.clear()
            st.clear()
            try:
                (
                    bufs, st, rb, level_sizes, level_base, nf, _w,
                ) = self._restore_frame()
                stats = self._fetch(st)
            except Exception as e:  # noqa: BLE001
                if not recovery.is_resource_exhausted(e):
                    raise
                # recovery itself exhausted memory: report what
                # the interrupted run had verified, honestly
                self._bufs_poisoned = True
                return self._result(
                    t0, last[0], last[1], {},
                    truncated=True, stop_reason="hbm",
                )

    def _level_loop(
        self, t0, bufs, st, rb, level_sizes, level_base, nf, stats
    ) -> CheckerResult:
        """BFS levels over an initialized-or-restored level frame.

        Loop invariant: every buffer can absorb the worst case of all
        in-flight (unfetched) flushes, i.e. nv_bound = nv + pending *
        ACAP stays within VCAP and LCAP.  The current frontier is the
        contiguous row-store range [level_base, level_base + nf)."""
        K = self.K
        self._last_rb = rb  # the tiered trace walk needs the log base
        nv = int(stats[0])
        while True:
            reason = self._stop_reason(stats, t0)
            if reason is not None and not (
                reason.get("truncated") and nf == 0
            ):
                if reason.get("truncated"):
                    # budget stops leave a resumable frame (-recover
                    # continues the search where TLC would)
                    self._save_frame(
                        bufs, st, rb, level_sizes, level_base, nf, nv,
                        t0,
                    )
                return self._result(t0, nv, level_sizes, bufs, **reason)
            if nf == 0:
                if self.final_frame:
                    # the search is COMPLETE (empty frontier): the
                    # frame exists purely as the warm-reseed artifact
                    # — full fingerprint planes + rows, zero frontier
                    self._save_frame(
                        bufs, st, rb, level_sizes, level_base, 0, nv,
                        t0,
                    )
                return self._result(t0, nv, level_sizes, bufs)
            if (
                self.tstore is not None
                and self.tstore.degraded
            ):
                # spill-tier ENOSPC (r17): the cold tiers lost
                # durability mid-run.  Everything counted so far is
                # exact (the in-RAM copies kept dedup correct), but
                # the run can neither keep evicting nor write a
                # resumable manifest — truncate honestly instead of
                # surfacing the worker's raw crash
                self._emit_spill(len(level_sizes))
                return self._result(
                    t0, nv, level_sizes, bufs, truncated=True,
                    stop_reason="spill_enospc",
                )
            if self._watcher is not None and self._watcher.requested:
                # preemption-safe shutdown: SIGTERM/SIGINT landed since
                # the last boundary — write a resumable frame and exit.
                # If the save is refused because the rows window was
                # lost, fall through so the honest row_window stop
                # below reports instead (an older frame may still
                # exist on disk; "preempted" must not mask that state)
                saved = self._save_frame(
                    bufs, st, rb, level_sizes, level_base, nf, nv, t0
                )
                if saved or rb["rows_ok"]:
                    return self._result(
                        t0, nv, level_sizes, bufs, truncated=True,
                        stop_reason="preempted",
                    )
            elif self.suspend_hook is not None:
                # cooperative time-slicing (the service scheduler):
                # same boundary as the preemption watcher, but polled —
                # "suspended" frames and exits resumably (the next job
                # gets the device), "cancelled" discards the run.  A
                # refused frame write (rows window lost) keeps running:
                # suspending without a frame would lose the work.
                why = self.suspend_hook()
                if why == "cancelled":
                    return self._result(
                        t0, nv, level_sizes, bufs, truncated=True,
                        stop_reason="cancelled",
                    )
                if why:
                    saved = self._save_frame(
                        bufs, st, rb, level_sizes, level_base, nf, nv,
                        t0,
                    )
                    if saved:
                        return self._result(
                            t0, nv, level_sizes, bufs, truncated=True,
                            stop_reason=str(why),
                        )
            self._xprof_tick(len(level_sizes) + 1)
            if self._stage_timing:
                self._log(
                    f"level start: nf={nf} windows={-(-nf // self.G)}"
                )
            # the level's expand windows slice [row_off + f_off, + G);
            # the last partial window may read up to G rows past the
            # frontier end, so the store must cover it or the
            # dynamic_slice would clamp and re-expand shifted rows
            # while silently never expanding the level's tail
            if self.tiered:
                # window assurance for THIS level (idempotent — the
                # boundary hook already sized it for steady state, but
                # the first level after init/seed/restore lands here
                # first)
                self._tiered_ensure_windows(
                    bufs, rb, level_base,
                    level_base + nf + self.G + self.APAD, nv,
                )
            elif self.rows_window == "frontier":
                self._grow_logs(bufs, level_base + nf + self.G)
                if not rb["rows_ok"]:
                    # the level about to be expanded lost rows to the
                    # scratch window — stop honestly (everything
                    # counted/checked so far stands; traces replay
                    # from the complete logs)
                    return self._result(
                        t0, nv, level_sizes, bufs, truncated=True,
                        stop_reason="row_window",
                    )
                if level_base > rb["row_base"]:
                    # slide the frontier's rows to offset 0, dropping
                    # everything older (never read again).  Done at
                    # level START so the seeded first level — whose
                    # rows sit at absolute offsets with row_base=0 —
                    # gets the same guarantee as every later level
                    # (the expand's +G read slack would otherwise
                    # clamp when a large seed nearly fills the window)
                    with self._clock.phase(
                        "dispatch", level=len(level_sizes) + 1
                    ):
                        with self._clock.upload("ptt_shift", 2):
                            src_d = jnp.int32(level_base - rb["row_base"])
                            nf_d = jnp.int32(nf)
                        with self._clock.call("ptt_shift"):
                            bufs["rows"] = self._shift_jit()(
                                bufs["rows"], src_d, nf_d
                            )
                    rb["row_base"] = level_base
                if nf + self.G > self.LCAP:
                    # the frontier itself exceeds the rows window
                    return self._result(
                        t0, nv, level_sizes, bufs, truncated=True,
                        stop_reason="row_window",
                    )
            elif self.fuse == "stage":
                # fused mode sizes all stores from one unified need at
                # dispatch time (_grow_fused) so the tier triple stays
                # on the prewarmed staircase
                self._grow_store(bufs, level_base + nf + self.G)
            if self.fuse == "level" and not (
                self.tiered
                and self._tiered_pressure(nv, nf, rb["row_base"])
            ):
                (
                    stats, nv, level_base, nf, stop, partial,
                ) = self._fused_level_pass(
                    t0, bufs, st, rb, level_sizes, level_base, nf, nv,
                    stats,
                )
                if stop:
                    reason = self._stop_reason(stats, t0) or {
                        "truncated": True, "stop_reason": "hbm"
                    }
                    if (
                        reason.get("truncated")
                        and not self._bufs_poisoned
                    ):
                        # mid-level stop: the frame rewinds to the
                        # level boundary, exactly like the stage path
                        self._save_frame(
                            bufs, st, rb,
                            level_sizes[:-1] if partial
                            else list(level_sizes),
                            level_base, nf, nv, t0,
                        )
                    return self._result(
                        t0, nv, level_sizes, bufs, **reason
                    )
                if self.tiered and nf:
                    self._tiered_boundary(
                        bufs, st, rb, level_base, nf, nv,
                        len(level_sizes),
                    )
                if (
                    self.checkpoint_path
                    and nf
                    and len(level_sizes) % self.checkpoint_every == 0
                ):
                    self._save_frame(
                        bufs, st, rb, level_sizes, level_base, nf, nv,
                        t0,
                    )
                continue
            stop = False
            pending = 0  # flushes dispatched since the last fetch
            w = 0  # accumulator windows filled since the last flush
            group_f0 = 0  # level offset of the first window in the acc
            try:
                # deterministic fault sites (utils/faults.py): kill/
                # sigterm fire inside poll; an injected oom raises the
                # same RESOURCE_EXHAUSTED path a real allocator failure
                # takes (which is the point of the drill)
                kinds = faults.poll("level", len(level_sizes) + 1)
                if "oom" in kinds:
                    raise faults.oom_error(
                        "level", len(level_sizes) + 1
                    )
                clock = self._clock
                for f_off in range(0, nf, self.G):
                    last = f_off + self.G >= nf
                    # live rows this window expands (the fused kernel
                    # counts the identical clip in-kernel)
                    self._work_add(expand_rows=min(self.G, nf - f_off))
                    with clock.phase(
                        "dispatch", level=len(level_sizes) + 1
                    ):
                        with clock.upload("ptt_slice", 1):
                            off_d = jnp.int32(
                                level_base - rb["row_base"] + f_off
                            )
                        with clock.call("ptt_slice"):
                            window = self._slice_jit()(bufs["rows"], off_d)
                        with clock.upload("ptt_expand", 4):
                            f_off_d, nf_d = jnp.int32(f_off), jnp.int32(nf)
                            level_base_d = jnp.int32(level_base)
                            acc_off_d = jnp.int32(w * self.NCs)
                        with clock.call("ptt_expand"):
                            out = self._expand_jit()(
                                bufs["ak"], bufs["arows"], window,
                                f_off_d, nf_d, st["dead_gid"],
                                level_base_d, acc_off_d,
                            )
                        self._stage_mark("expand", out)
                    bufs["ak"], bufs["arows"] = out[:K], out[K]
                    st["dead_gid"] = out[K + 1]
                    w += 1
                    if w < self.FLUSH and not last:
                        continue
                    # capacity check for THIS flush under the worst case
                    # of all in-flight (unfetched) flushes: each adds at
                    # most ACAP states, and the append writes a blind
                    # APAD-row window past the running n_visited
                    nv_bound = nv + (pending + 1) * self.ACAP
                    # tiered mode bounds the HOT table (cold-duplicate
                    # inserts count; evicted keys do not) and the
                    # window-relative store offsets
                    hot_bound = (
                        self._hot_n + (pending + 1) * self.ACAP
                        if self.tiered
                        else nv_bound
                    )
                    log_off = rb["row_base"] if self.tiered else 0
                    rows_full = (
                        self.rows_window == "frontier"
                        and rb["rows_ok"]
                        and nv_bound - self.ACAP - rb["row_base"]
                        + self.APAD > self.LCAP
                    )
                    need_sync = (
                        hot_bound > self.VCAP
                        or nv_bound - self.ACAP - log_off + self.APAD
                        > self.PCAP
                        or nv_bound - self.ACAP >= self.SCAP
                        or rows_full
                        or pending >= self.group
                        or (
                            self.tiered
                            and nv_bound - self.ACAP - log_off
                            + self.APAD > self.LCAP
                        )
                    )
                    if need_sync:
                        stats = self._fetch(st)
                        nv, pending = int(stats[0]), 0
                        # intra-level progress record: deep levels run
                        # for minutes, and the sustained-window metrics
                        # (VERDICT r3 #3 / r4 #1) need finer anchors
                        # than level boundaries
                        self._emit_metrics(
                            t0, len(level_sizes) + 1,
                            nv - (level_base + nf), nv, nf,
                            partial=True,
                        )
                        if self._stop_reason(stats, t0) is not None:
                            stop = True
                            break
                        # grow with enough headroom for a full group of
                        # in-flight flushes, or every flush would sync
                        # (growth doubles, so this stays rare).  After
                        # an HBM recovery the headroom is frozen at one
                        # accumulator — degraded capacity so the retry
                        # fits where the full-headroom run did not
                        head = (
                            self.ACAP
                            if self.rec.headroom_frozen
                            else (self.group + 1) * self.ACAP
                        )
                        if self.tiered:
                            # the budget consult that replaces
                            # truncation: grow within it, evict past it
                            self._ensure_hot_capacity(bufs, head)
                            self._tiered_ensure_windows(
                                bufs, rb, level_base,
                                nv + head + self.APAD, nv,
                            )
                        elif nv + self.ACAP > self.VCAP:
                            self._grow_visited(bufs, nv + head)
                        if not self.tiered and (
                            nv + self.APAD > self.PCAP
                        ):
                            self._grow_store(
                                bufs, nv + head + self.APAD
                            )
                        if (
                            self.rows_window == "frontier"
                            and rb["rows_ok"]
                            and nv - rb["row_base"] + self.APAD
                            > self.LCAP
                        ):
                            # the window is truly full: divert this
                            # level's remaining row writes to scratch —
                            # dedup/invariants/logs continue, but the
                            # level can no longer become a frontier
                            rb["rows_ok"] = False
                            self._log(
                                "rows window full: dropping rows for "
                                "the rest of this level"
                            )
                    self._flush_acc(
                        bufs, st, rb, w * self.NCs,
                        level_base + group_f0, False,
                    )
                    pending += 1
                    group_f0 = f_off + self.G
                    w = 0
            except Exception as e:  # noqa: BLE001
                if not recovery.is_resource_exhausted(e):
                    raise
                if self._can_recover():
                    raise recovery.HbmExhausted(
                        nv, list(level_sizes), repr(e)
                    )
                # HBM exhausted with no frame to rebuild from: report
                # what was checked so far (truncated).  Only the small
                # stats scalars are read from here on; the big buffers
                # may hold donated/poisoned storage.
                self._log(f"HBM exhausted mid-level: truncating ({e!r:.120})")
                self._bufs_poisoned = True
                stop = True
            try:
                stats = self._fetch(st)
            except Exception as e:  # noqa: BLE001
                if not recovery.is_resource_exhausted(e):
                    raise
                if self._can_recover():
                    raise recovery.HbmExhausted(
                        nv, list(level_sizes), repr(e)
                    )
                self._bufs_poisoned = True
                stop = True  # keep the last successfully fetched stats
            nv = int(stats[0])
            level_count = nv - (level_base + nf)
            if level_count or stop:
                level_sizes.append(max(level_count, 0))
                self._emit_metrics(t0, len(level_sizes), level_count, nv, nf)
                wall = time.perf_counter() - t0
                self._log(
                    f"level {len(level_sizes)}: +{level_count} "
                    f"(total {nv}, {nv/max(wall,1e-9):.0f} st/s)"
                )
            if stop:
                reason = self._stop_reason(stats, t0) or {
                    "truncated": True, "stop_reason": "hbm"
                }
                if reason.get("truncated") and not self._bufs_poisoned:
                    # mid-level stop: snapshot rewinds to the level
                    # boundary (the partial last entry re-derives on
                    # resume — every already-appended state dedups to
                    # a no-op, so the retried level is exact)
                    self._save_frame(
                        bufs, st, rb, level_sizes[:-1], level_base, nf,
                        nv, t0,
                    )
                return self._result(t0, nv, level_sizes, bufs, **reason)
            level_base += nf
            nf = level_count
            if self.tiered and nf:
                self._tiered_boundary(
                    bufs, st, rb, level_base, nf, nv, len(level_sizes)
                )
            # (frontier mode: the rows_ok check and the frontier shift
            # happen at the TOP of the next iteration, so the seeded
            # first level takes the same path as every later level)
            if (
                self.checkpoint_path
                and nf
                and len(level_sizes) % self.checkpoint_every == 0
            ):
                self._save_frame(
                    bufs, st, rb, level_sizes, level_base, nf, nv, t0
                )

    # ------------------------------------------------------- fused pass

    def _levels_cap(self, nf: int, levels_done: int) -> int:
        """Max level boundaries one fused dispatch may cross — the
        cost model's batching decision, auto from the frontier size
        (the r10 ``--sweep-group`` pattern): ramp levels (frontier at
        or below one expand window, rows_window="all" — the frontier
        window's boundary shift is host-side) batch up to ``RMAX``
        levels; steady-state levels run one per dispatch.  Capped so a
        batch always ENDS on a due checkpoint boundary — frames,
        suspend polls, and preemption checks keep their level-boundary
        semantics."""
        if self.rows_window != "all" or nf > self.G:
            lv = 1
        else:
            lv = self.RMAX
        if self.checkpoint_path:
            lv = min(
                lv,
                self.checkpoint_every
                - (levels_done % self.checkpoint_every),
            )
        return max(lv, 1)

    def _groups_cap(self) -> int:
        """Flush groups one fused dispatch may run.  Unbudgeted runs
        are bounded by capacity and the level budget alone (whole
        levels per dispatch); a time-budgeted run keeps a finite fetch
        cadence so the budget check cannot blunt to whole-deep-level
        granularity (still far coarser than the stage path's
        per-``group`` syncs)."""
        if self.time_budget_s is not None:
            return max(8 * self.group, 32)
        return 1 << 30

    def _replay_flush_faults(self, st, fl_before: int):
        """The megakernel ran its flushes in-device; fire the host
        ``flush`` fault sites for exactly the flushes the device
        counted (the fpm flush-counter delta), preserving the drills'
        sequence numbering across the fused and stage paths.  An
        injected ``fpset_fail`` lands in the device metrics and
        fail-stops through the SAME fetch path a real stage overflow
        takes."""
        total = int(fpset.fpm_logical(self._last_fpm)[0])
        fired_fail = False
        for _ in range(total - fl_before):
            self._flush_seq += 1
            kinds = faults.poll("flush", self._flush_seq)
            if "oom" in kinds:
                raise faults.oom_error("flush", self._flush_seq)
            if "fpset_fail" in kinds:
                fired_fail = True
        if fired_fail:
            st["fpm"] = st["fpm"] + jnp.asarray(
                [0, 0, 1] + [0] * (FPM_N - 3), jnp.int32
            )
            self._fetch(st)  # realizes the fail-stop immediately

    def _fused_level_pass(
        self, t0, bufs, st, rb, level_sizes, level_base, nf, nv, stats
    ):
        """Advance the BFS from the current level boundary through
        fused megakernel dispatches until the next boundary the host
        must act on (growth between segments happens here; per-level
        accounting, telemetry, and fault sites replay from the
        kernel's returned level sizes).  Returns ``(stats, nv,
        level_base, nf, stop, partial)`` — ``partial`` flags a
        mid-level stop whose last ``level_sizes`` entry is the
        in-progress level's partial count (frame rewind semantics
        identical to the stage path)."""
        K = self.K
        n_inv = len(self.invariant_names)
        stop = False
        partial = False
        w_off = 0
        try:
            kinds = faults.poll("level", len(level_sizes) + 1)
            if "oom" in kinds:
                raise faults.oom_error("level", len(level_sizes) + 1)
            while True:
                # pre-dispatch growth from ONE unified need (keeps the
                # tier triple on the prewarmed staircase); headroom
                # freezes to one accumulator after an HBM recovery
                head = (
                    self.ACAP
                    if self.rec.headroom_frozen
                    else (self.group + 1) * self.ACAP
                )
                self._grow_fused(bufs, nv + head)
                lv_cap = self._levels_cap(nf, len(level_sizes))
                nv_in = nv
                fl_before = (
                    int(fpset.fpm_logical(self._last_fpm)[0])
                    if self._last_fpm is not None
                    else 0
                )
                # the call into the level kernel: a tier's first call
                # traces, lowers and compiles (or loads) in here
                with self._clock.phase(
                    "dispatch", level=len(level_sizes) + 1
                ):
                    with self._clock.upload("ptt_level2", 7):
                        scalars = (
                            jnp.int32(level_base), jnp.int32(nf),
                            jnp.int32(w_off), jnp.int32(lv_cap),
                            jnp.int32(self._groups_cap()),
                            jnp.int32(rb["row_base"]),
                            jnp.bool_(rb["rows_ok"]),
                        )
                    with self._clock.call("ptt_level2"):
                        out = self._fused_jit()(
                            bufs["vk"], bufs["ak"], bufs["arows"],
                            bufs["rows"], bufs["parent"], bufs["lane"],
                            st["n_visited"], st["dead_gid"],
                            st["viol"], st["fpm"], st["wkm"],
                            *scalars,
                        )
                    self._stage_mark("fused", out)
                bufs["vk"] = out[:K]
                bufs["ak"] = out[K: 2 * K]
                (
                    bufs["arows"], bufs["rows"], bufs["parent"],
                    bufs["lane"], st["n_visited"], st["dead_gid"],
                    st["viol"], st["fpm"], st["wkm"],
                ) = out[2 * K: 2 * K + 9]
                # the kernel's packed stats vector IS the fetch — a
                # fused level pays 1 dispatch + 1 fetch, nothing else
                stats = self._fetch(st, vec=out[2 * K + 9])
                nv = int(stats[0])
                tail = stats[2 + n_inv + FPM_N + WKM_N:]
                lb2, nf2, w_off2, n_lv, rows_ok_i = (
                    int(x) for x in tail[:5]
                )
                sizes = [
                    int(x)
                    for x in tail[
                        self.FUSED_TAIL: self.FUSED_TAIL + n_lv
                    ]
                ]
                if self.rows_window == "frontier":
                    rb["rows_ok"] = bool(rows_ok_i)
                if (
                    self.tiered
                    and n_lv == 0
                    and w_off2 == w_off
                    and nv == nv_in
                ):
                    # the kernel's capacity guard refused to run and
                    # growth is budget-capped: latch spilling and hand
                    # the level to the stage path (idempotent dedup
                    # re-derives any partial progress exactly)
                    self._spill_active = True
                    level_base, nf = lb2, nf2
                    break
                self._replay_flush_faults(st, fl_before)
                wd = self._last_wkm_delta
                self.tel.emit(
                    "fuse",
                    levels=n_lv,
                    dispatches=1,
                    flushes=int(fpset.fpm_logical(self._last_fpm)[0])
                    - fl_before,
                    frontier=int(nf),
                    # per-dispatch work-unit deltas (v7): the in-kernel
                    # counters this dispatch accumulated — the stream-
                    # level attribution signal
                    work_expand_rows=int(wd.get("expand_rows", 0)),
                    work_probe_lanes=int(wd.get("probe_lanes", 0)),
                    work_compact_elems=int(wd.get("compact_elems", 0)),
                    work_append_rows=int(wd.get("append_rows", 0)),
                )
                # ---- per-level accounting replay (the kernel's
                # lsizes): level records, log lines, and PTT_FAULT
                # level sites fire for every batched level, in order
                prev_nf = nf
                cum = level_base + nf
                for k, sz in enumerate(sizes):
                    if sz == 0:
                        # a level that added nothing ends the search
                        # (nf=0 exits the kernel right after); the
                        # stage path never appends empty levels either
                        continue
                    if k > 0:
                        kinds = faults.poll(
                            "level", len(level_sizes) + 1
                        )
                        if "oom" in kinds:
                            level_base, nf = lb2, nf2
                            raise faults.oom_error(
                                "level", len(level_sizes) + 1
                            )
                    cum += sz
                    level_sizes.append(sz)
                    self._emit_metrics(
                        t0, len(level_sizes), sz, cum, prev_nf
                    )
                    wall = time.perf_counter() - t0
                    self._log(
                        f"level {len(level_sizes)}: +{sz} "
                        f"(total {cum}, {cum/max(wall,1e-9):.0f} st/s)"
                    )
                    prev_nf = sz
                if n_lv:
                    self.last_stats["fuse_levels"] = (
                        self.last_stats.get("fuse_levels", 0) + n_lv
                    )
                level_base, nf = lb2, nf2
                if w_off2 == 0:
                    break  # at a boundary/terminal — the outer loop acts
                if sizes:
                    # a level that STARTED inside this dispatch is now
                    # mid-flight: its level site fires here (the pass
                    # entry only covered the dispatch's first level)
                    kinds = faults.poll("level", len(level_sizes) + 1)
                    if "oom" in kinds:
                        raise faults.oom_error(
                            "level", len(level_sizes) + 1
                        )
                w_off = w_off2
                # mid-level segment boundary: progress anchor + stop
                # check, then grow at the loop top and re-enter
                self._emit_metrics(
                    t0, len(level_sizes) + 1,
                    nv - (level_base + nf), nv, nf, partial=True,
                )
                if self._stop_reason(stats, t0) is not None:
                    stop = True
                    partial = True
                    break
        except Exception as e:  # noqa: BLE001
            if not recovery.is_resource_exhausted(e):
                raise
            if self._can_recover():
                raise recovery.HbmExhausted(
                    nv, list(level_sizes), repr(e)
                )
            self._log(
                f"HBM exhausted mid-level: truncating ({e!r:.120})"
            )
            self._bufs_poisoned = True
            stop = True
        if stop:
            if partial and not self._bufs_poisoned:
                # mirror the stage tail: the in-progress level's
                # partial count rides as the last diameter entry (it
                # re-derives on resume by dedup idempotence)
                level_count = nv - (level_base + nf)
                level_sizes.append(max(level_count, 0))
                self._emit_metrics(
                    t0, len(level_sizes), level_count, nv, nf
                )
            elif self._bufs_poisoned:
                level_count = nv - (level_base + nf)
                if level_count > 0:
                    level_sizes.append(level_count)
                    partial = True
        return stats, nv, level_base, nf, stop, partial

    # ------------------------------------------------ checkpoint/resume

    def _config_sig(self) -> str:
        """Everything a frame must agree on to be resumable here: the
        model hash, invariant set, key geometry (fp_bits regime), the
        visited/rows implementations, and the engine frame revision.
        Capacity tiers and fpset geometry live in the frame ARRAYS
        (tcap, n_visited, rows_lo) — a resumed run may legally raise
        ``max_states`` or ``row_cap_states``."""
        return ckpt.config_sig(
            model=ckpt.model_sig(self.model),
            invariants=self.invariant_names,
            check_deadlock=self.check_deadlock,
            state_bits=self.layout.total_bits,
            key_cols=self.K,
            key_exact=self.keys.exact,
            visited_impl=obs.IMPL_FIELDS["visited_impl"],
            rows_window=self.rows_window,
            engine="device_bfs_r7",
            **({"tiered": True} if self.tiered else {}),
        )

    def _can_recover(self) -> bool:
        return self.rec.can_recover()

    @spans.in_phase("ckpt")
    def _save_frame(
        self, bufs, st, rb, level_sizes, level_base, nf, nv, t0
    ) -> bool:
        """Write one resumable frame (atomic tmp + os.replace via
        utils/ckpt.py); returns True if a frame was written.

        Frame meaning: "``nv`` states discovered, about to (re-)expand
        the contiguous frontier [level_base, level_base + nf)".  A
        mid-level frame (``nv > level_base + nf``) is exact because the
        partially appended next level re-derives by dedup idempotence.
        Saved rows span [rows_lo, nv): the full store in
        ``rows_window="all"`` (liveness keeps reading it after resume),
        the live window from the frontier start in frontier mode."""
        if not self.checkpoint_path:
            return False
        if self._bufs_poisoned or not rb["rows_ok"]:
            # device rows unusable — keep the previous (older but
            # valid) frame rather than overwrite it with garbage
            return False
        if self.tstore is not None and self.tstore.degraded:
            # ENOSPC degraded the spill dir: a frame embedding a
            # manifest over unwritten files would poison resume —
            # keep the previous valid frame instead
            return False
        t_stall = time.perf_counter()
        W = self.W
        # tiered frames save the device WINDOW only — everything older
        # is in the cold tiers the embedded spill manifest describes
        lo = (
            rb["row_base"] if self.tiered
            else 0 if self.rows_window == "all"
            else level_base
        )
        # logs are windowed ONLY in tiered mode (frontier mode windows
        # the rows but keeps full logs)
        n_log = nv - (lo if self.tiered else 0)
        with spans.span("ckpt.gather"):
            arrays = {
                "n_visited": np.int64(nv),
                "level_sizes": np.asarray(level_sizes, np.int64),
                "lb": np.int64(level_base),
                "nf": np.int64(nf),
                "rows_lo": np.int64(lo),
                "hbm_recovered": np.int64(self._hbm_recovered),
                "fpm": np.asarray(st["fpm"]),
                "parent": self._ckpt_fetch(bufs["parent"], n_log),
                "lane": self._ckpt_fetch(bufs["lane"], n_log),
                "rows": self._ckpt_fetch(
                    bufs["rows"], (nv - lo) * W,
                    (lo - rb["row_base"]) * W,
                ),
            }
            # both table columns whole: the occupied slots are picked
            # out on the host (below)
            cols = tuple(np.asarray(c) for c in bufs["vk"])
            self._ckpt_d2h_bytes += sum(c.nbytes for c in cols)
        t_gather = time.perf_counter()
        with spans.span("ckpt.pack"):
            # compacted occupancy (keys + slot index): frame size
            # scales with the state count, not the table tier
            arrays.update(ckpt.pack_fpset(cols))
            del cols
            if self.tiered:
                # the spill manifest: every cold run/segment with file
                # names + content digests, so resume restores the WHOLE
                # tiered store (manifest() joins the async writes first
                # — a frame never references a half-written spill file)
                import json as _json

                try:
                    man = self.tstore.manifest()
                except ValueError:
                    # the join just latched ENOSPC degradation: the
                    # spill dir is incomplete, keep the previous valid
                    # frame
                    return False
                arrays["spill_manifest"] = np.frombuffer(
                    _json.dumps(man).encode(),
                    dtype=np.uint8,
                )
                arrays["spill_hot_n"] = np.int64(self._hot_n)
                arrays["spill_epoch"] = np.int64(self._epoch)
        t_pack = time.perf_counter()
        deflate: Dict[str, object] = {}
        with spans.span("ckpt.write"):
            nbytes, write_s, retries = ckpt.save_frame(
                self.checkpoint_path, self._config_sig(), arrays,
                wall_s=time.perf_counter() - t0,
                meta={
                    "run_id": self._run_id,
                    "frame_seq": self._ckpt_frames + 1,
                    "level": len(level_sizes),
                    "engine": "device_bfs",
                },
                stats=deflate,
            )
        # the frame's STALL is everything the run loop was blocked on
        # here, in three parts that add up to it: the D2H gather, the
        # host's pack of the table's occupied slots, the compressed
        # write (ckpt_write_s keeps its name and is the whole stall)
        t_end = time.perf_counter()
        stall_s = t_end - t_stall
        self._ckpt_frames += 1
        self._ckpt_bytes += nbytes
        self._ckpt_write_s += stall_s
        self._ckpt_gather_s += t_gather - t_stall
        self._ckpt_pack_s += t_pack - t_gather
        self._ckpt_npz_s += t_end - t_pack
        self._ckpt_deflate_threads = max(
            self._ckpt_deflate_threads, deflate["deflate_threads"]
        )
        self._ckpt_deflate_blocks += deflate["deflate_blocks"]
        self._ckpt_deflate_cpu_s += deflate["deflate_cpu_s"]
        self._ckpt_raw_bytes += sum(
            np.asarray(a).nbytes for a in arrays.values()
        )
        self._ckpt_states += nv
        self._ckpt_last_level = len(level_sizes)
        self._ckpt_retries += retries
        self.rec.arm()
        self.last_stats.update(
            self._ckpt_stats(),
            # the LAST frame's costs stand alone: when a slice suspends,
            # this frame IS the suspend frame — the scheduler attaches
            # these to the job_suspend event (context-switch write cost)
            ckpt_last_write_s=round(write_s, 3),
            ckpt_last_stall_s=round(stall_s, 3),
        )
        self.tel.emit(
            "ckpt_frame",
            frame_seq=self._ckpt_frames,
            bytes=nbytes,
            write_s=round(write_s, 3),
            stall_s=round(stall_s, 3),
            gather_s=round(t_gather - t_stall, 3),
            pack_s=round(t_pack - t_gather, 3),
            deflate_threads=deflate["deflate_threads"],
            deflate_blocks=deflate["deflate_blocks"],
            deflate_cpu_s=round(deflate["deflate_cpu_s"], 3),
            retries=retries,
            level=len(level_sizes),
            distinct_states=nv,
        )
        self._log(
            f"checkpoint: level {len(level_sizes)}, {nv} states "
            f"({nbytes >> 10} KiB, {stall_s:.2f}s stall) -> "
            f"{self.checkpoint_path}"
        )
        return True

    def _ckpt_stats(self) -> Dict[str, object]:
        """This run's frame counters as ``last_stats`` carries them
        (docs/observability.md): ``ckpt_write_s`` the whole stall,
        ``ckpt_gather_s`` + ``ckpt_pack_s`` + ``ckpt_npz_s`` its three
        parts; ``ckpt_deflate_cpu_s`` the write's work in zlib wherever
        it ran, over ``ckpt_npz_s`` the ``ckpt_deflate_speedup``."""
        return dict(
            ckpt_frames=self._ckpt_frames,
            ckpt_bytes=self._ckpt_bytes,
            ckpt_write_s=round(self._ckpt_write_s, 3),
            ckpt_gather_s=self._ckpt_gather_s,
            ckpt_pack_s=self._ckpt_pack_s,
            ckpt_npz_s=self._ckpt_npz_s,
            ckpt_deflate_threads=self._ckpt_deflate_threads,
            ckpt_deflate_blocks=self._ckpt_deflate_blocks,
            ckpt_deflate_cpu_s=self._ckpt_deflate_cpu_s,
            ckpt_deflate_speedup=(
                self._ckpt_deflate_cpu_s / self._ckpt_npz_s
                if self._ckpt_npz_s else 0.0
            ),
            ckpt_raw_bytes=self._ckpt_raw_bytes,
            ckpt_d2h_bytes=self._ckpt_d2h_bytes,
            ckpt_states=self._ckpt_states,
            ckpt_last_level=self._ckpt_last_level,
            ckpt_retries=self._ckpt_retries,
        )

    def _ckpt_fetch(self, buf, n: int, off: int = 0) -> np.ndarray:
        """``buf[off: off + n]`` on the host, for a frame.  A buffer
        over half full comes over whole; under that the device slices a
        bucketed length (:meth:`_spill_fetch_size`, one program a
        ``(buffer, size)``) and the host trims it, where an eager slice
        at the frame's own length was an executable a frame."""
        got, out = self._bucketed_fetch(
            buf, n, off, bodies.ptt_ckpt_fetch
        )
        self._ckpt_d2h_bytes += got.nbytes
        return out

    def _restore_pad(self, data, length: int, dtype):
        """The frame's ``data`` with zeros after it up to a bucketed
        size (:meth:`_spill_fetch_size`: a power of two, or the
        buffer's own ``length``), ready for :meth:`_restore_upload`."""
        data = np.asarray(data, dtype)
        host = np.zeros(
            (self._spill_fetch_size(len(data), length),), dtype
        )
        host[: len(data)] = data
        return host

    def _restore_upload(self, host, length: int):
        """The device buffer of ``length`` that starts with ``host``:
        uploaded whole where the bucket is the buffer's length, else
        padded on the device by one program a ``(bucket, length)``,
        where an eager fill and concatenate at the frame's own length
        compiled anew for every frame."""
        self._restore_h2d_bytes += host.nbytes
        with self._clock.upload("ptt_restore_pad", 1):
            up = jnp.array(host)  # a copy: the flush donates these buffers
        if len(host) == length:
            return up
        with self._clock.call("ptt_restore_pad"):
            return bodies.ptt_restore_pad(up, length=length)

    def _restore_frame(self):
        """Rebuild device buffers + level frame from the checkpoint;
        returns (bufs, st, rb, level_sizes, level_base, nf, wall_s)."""
        t_load = time.perf_counter()
        with spans.span("restore.load"):
            d = ckpt.load_frame(self.checkpoint_path, self._config_sig())
            # an npz member is read and decompressed when it is asked
            # for: ask for every one here, so that the load is timed
            # apart from what is done with it
            d = {k: d[k] for k in d.files}
        t_unpack = time.perf_counter()
        self._restore_load_s += t_unpack - t_load
        # writer identity (run_id / frame_seq) for the resume header —
        # the telemetry stream of the resumed run links back to the
        # prior run's last ckpt_frame event
        self._resume_meta = ckpt.frame_meta(d)
        K = self.K
        nv = self._resume_states = int(d["n_visited"])
        level_sizes = [int(x) for x in d["level_sizes"]]
        level_base = int(d["lb"])
        nf = int(d["nf"])
        lo = int(d["rows_lo"])
        if nv > self.SCAP:
            raise ValueError(
                f"checkpoint holds {nv} states — beyond max_states "
                f"({self.SCAP}); raise max_states to resume it"
            )
        with spans.span("restore.unpack"):
            cols = ckpt.unpack_fpset(d, K)
        # the snapshot fixes the table tier (jit programs are
        # tier-keyed, so no cache invalidation is needed); growth,
        # if the resumed run needs it, goes through regular rehash
        self.TCAP = cols[0].shape[0] - 1
        self.VCAP = self.TCAP // 2
        # size the row/log tiers BEFORE allocating (same doubling-with-
        # cap formulas as _grow_store/_grow_logs, minus the buffers).
        # Tiered frames hold the device WINDOW only, so the need is
        # window-relative
        need = (nv - lo if self.tiered else nv) + self.APAD
        cap = self._capl()
        if self.rows_window == "all":
            while self.LCAP < need:
                self.LCAP += min(
                    self.LCAP, max(cap - self.LCAP, need - self.LCAP)
                )
        elif nv - lo + self.APAD > self.LCAP:
            raise ValueError(
                f"checkpoint frontier ({nv - lo} rows) exceeds the "
                f"frontier rows window ({self.LCAP}); raise "
                "row_cap_states"
            )
        while self.PCAP < need:
            self.PCAP += min(
                self.PCAP, max(cap - self.PCAP, need - self.PCAP)
            )
        with spans.span("restore.unpack"):
            # saved rows land at their absolute offset in "all" mode
            # (lo == 0) and at window offset 0 with row_base = lo in
            # frontier mode — both are "offset (lo - row_base) = 0"
            lengths = {
                "rows": self._rows_len(), "parent": self._logs_len(),
                "lane": self._logs_len(),
            }
            padded = {
                k: self._restore_pad(
                    d[k], n, np.uint32 if k == "rows" else np.int32
                )
                for k, n in lengths.items()
            }
        t_upload = time.perf_counter()
        self._restore_unpack_s += t_upload - t_unpack
        with spans.span("restore.upload"):
            bufs = {
                # jnp.array (copy=True), NOT jnp.asarray: on the CPU
                # backend asarray can alias the numpy buffer zero-copy,
                # and the flush DONATES these columns — donating memory
                # numpy still owns is a use-after-free (observed as
                # flaky probe overflows and GC segfaults in the resume
                # tests)
                "vk": tuple(jnp.array(c) for c in cols),
                "ak": tuple(
                    jnp.full((self.ACAP,), SENTINEL, jnp.uint32)
                    for _ in range(K)
                ),
                "arows": jnp.zeros((self.W, self.ACAP), jnp.uint32),
                **{
                    k: self._restore_upload(padded[k], n)
                    for k, n in lengths.items()
                },
            }
            self._restore_h2d_bytes += sum(c.nbytes for c in cols)
            del cols, padded
            jax.block_until_ready(bufs)
        self._restore_upload_s += time.perf_counter() - t_upload
        if self.tiered:
            # restore the cold tiers through the frame's manifest
            # (digest-verified; a torn spill file fails loudly) and
            # restart the epoch clock with all hot keys at the base
            # generation
            import json as _json

            if "spill_manifest" not in d:
                raise ValueError(
                    "tiered resume needs a spill manifest in the "
                    "frame — this frame was written untiered"
                )
            self._mk_tstore()
            self.tstore.restore(
                _json.loads(d["spill_manifest"].tobytes().decode())
            )
            self._hot_n = int(d["spill_hot_n"])
            self._epoch = 2
            self._spill_active = bool(
                self.tstore.has_cold_keys or self.tstore._rows
            )
            gen0 = jnp.zeros((self.TCAP + 1,), jnp.int32)
            with self._clock.upload("ptt_spill_tag", 1):
                epoch_d = jnp.int32(1)
            with self._clock.call("ptt_spill_tag"):
                bufs["gen"] = self._tag_jit()(*bufs["vk"], gen0, epoch_d)
        n_inv = len(self.invariant_names)
        st = {
            "n_visited": jnp.int32(nv),
            "dead_gid": BIG,
            "viol": jnp.full((n_inv,), int(BIG), jnp.int32),
        }
        # pre-widening frames carry the 3- or 5-wide fpm prefix;
        # zero-pad the new counters (the r8 valid_lanes /
        # max_probe_rounds and the r12 valid_lanes_hi word restart)
        old = np.asarray(d["fpm"], np.int32).reshape(-1)
        fpm = np.zeros((FPM_N,), np.int32)
        fpm[: min(len(old), FPM_N)] = old[:FPM_N]
        st["fpm"] = jnp.asarray(fpm)
        # flush telemetry deltas continue from the frame's counts,
        # not from zero (a resumed run must not re-report them)
        self._fpm_prev = fpset.fpm_logical(fpm)
        # the slot-rounds restart with the work counters below: the
        # frame does not say which table its rounds ran on
        self._slot_rounds = 0
        self._slot_rounds_at = int(self._fpm_prev[1])
        self._slot_valid_at = int(self._fpm_prev[3])
        self._arb_rounds = self._arb_of = 0
        self._arb_steps_at = fpm[
            fpset.FPM_N: fpset.FPM_WRITE_SAVED
        ].astype(np.int64)
        self._write_saved = 0
        self._write_saved_at = int(fpm[fpset.FPM_WRITE_SAVED])
        self._write_lanes_at = int(self._fpm_prev[5])
        if self.fuse == "level":
            # work counters restart after resume (frames don't carry
            # them — the same regime as the r8 counter widenings);
            # attribution of a resumed run covers the resumed portion
            st["wkm"] = jnp.zeros((WKM_N,), jnp.int32)
            self._wkm_prev = np.zeros((fpset.WKM_LOGICAL_N,), np.int64)
        self._work_nv_prev = nv  # restored states are not appends
        if "hbm_recovered" in d:
            self.rec.hbm_recovered = max(
                self.rec.hbm_recovered, int(d["hbm_recovered"])
            )
        rb = {"row_base": lo, "rows_ok": True}
        self._log(
            f"resumed at level {len(level_sizes)}: {nv} states, "
            f"frontier {nf}"
        )
        return bufs, st, rb, level_sizes, level_base, nf, float(
            d["wall_s"]
        )

    def _over_time(self, t0) -> bool:
        # the budget runs on its own clock: ``t0`` is rewound on resume
        # so wall_s stays cumulative, but a resumed run always gets
        # ``time_budget_s`` of fresh runway
        return (
            self.time_budget_s is not None
            and time.perf_counter() - getattr(self, "_budget_t0", t0)
            > self.time_budget_s
        )

    def _stop_reason(self, stats, t0) -> Optional[dict]:
        """``_result`` kwargs if the run must stop, else None.  Priority:
        invariant violation, deadlock, then state/time budget."""
        fv = self._first_viol(stats)
        if fv is not None:
            return {"viol": fv}
        if int(stats[1]) < int(BIG):
            return {"dead_gid": int(stats[1])}
        if int(stats[0]) >= self.SCAP:
            return {"truncated": True, "stop_reason": "max_states"}
        if self._over_time(t0):
            return {"truncated": True, "stop_reason": "time_budget"}
        return None

    def _first_viol(self, stats) -> Optional[Tuple[str, int]]:
        """(invariant name, gid) of the lowest-gid violation, or None."""
        best = None
        for i, name in enumerate(self.invariant_names):
            g = int(stats[2 + i])
            if g < BIG and (best is None or g < best[1]):
                best = (name, g)
        return best

    def _emit_metrics(self, t0, level, level_count, nv, nf,
                      partial: bool = False):
        """Every record is kept (duplicate state counts included) —
        rate consumers skip zero-delta tails themselves (bench.py
        sustained_rates).  ``partial=True`` marks intra-level anchors
        (mid-level segment fetches, the seed handoff) so v6 stream
        consumers can separate them from level-boundary records — the
        fused-run validator holds only boundary records to the
        strictly-increasing / sizes-match-result contract."""
        wall = time.perf_counter() - t0
        if not partial:
            self._clock.level_boundary(level)
        self._snap.update(
            level=level, frontier=int(nf), distinct_states=int(nv),
            # the heartbeat marks its line when the newest record was
            # an intra-level anchor (r14 satellite: ramp-batch fetches
            # make level/frontier figures mid-flight)
            partial=bool(partial),
        )
        self.tel.emit(
            "level",
            **({"partial": True} if partial else {}),
            level=level,
            new_states=int(level_count),
            distinct_states=int(nv),
            frontier=int(nf),
            wall_s=round(wall, 3),
            states_per_sec=round(nv / max(wall, 1e-9), 1),
            host_wait_s=round(self._host_wait_s, 3),
        )
        if not self.metrics_path:
            return
        import json
        with open(self.metrics_path, "a") as f:
            f.write(
                json.dumps(
                    {
                        "level": level,
                        "new_states": level_count,
                        "distinct_states": nv,
                        "frontier": nf,
                        "wall_s": round(wall, 3),
                        # cumulative time the host spent blocked on stats
                        # fetches (everything else is device kernel time
                        # plus free async dispatch)
                        "host_wait_s": round(self._host_wait_s, 3),
                        "states_per_sec": round(nv / max(wall, 1e-9), 1),
                        "visited_cap": self.VCAP,
                    }
                )
                + "\n"
            )

    # ------------------------------------------------------------- trace

    @spans.in_phase("trace_walk")
    def _trace(self, bufs, gid: int, max_depth: int):
        """Walk the parent chain on device (one fetch), replay lanes
        through the oracle on the host (SURVEY.md §2.2-E7).  Tiered
        runs whose aged logs spilled walk the merged cold+device logs
        host-side instead — the chain is depth-bounded, so the host
        walk is off every hot path."""
        if (
            self.tiered
            and getattr(self, "_last_rb", None) is not None
            and self._last_rb["row_base"] > 0
        ):
            return self._trace_tiered(bufs, gid, max_depth)
        with self._clock.upload("step", 1):
            gid_d = jnp.int32(gid)
        with self._clock.call("step"):
            gids, lanes, g_end = self._chain_jit(max_depth)(
                bufs["parent"], bufs["lane"], gid_d
            )
        gids = np.asarray(gids)
        lanes = np.asarray(lanes)
        g_end = int(np.asarray(g_end))
        chain = []
        for i in range(max_depth):
            if int(gids[i]) == int(BIG):
                break
            chain.append((int(gids[i]), int(lanes[i])))
        if g_end >= 0:
            # a corrupted chain must never fall through to a nonsense
            # init_idx replay (and asserts vanish under python -O)
            raise RuntimeError(
                "parent chain did not terminate at an initial state "
                f"(depth {max_depth}, last gid {g_end}) — trace log corrupt"
            )
        init_idx = -1 - g_end
        chain.reverse()
        lanes = [lane for _gid, lane in chain[1:]]
        return self._replay_chain(init_idx, lanes)

    def _replay_chain(self, init_idx: int, lanes):
        replay = getattr(self.model, "replay_trace", None)
        if replay is None:
            # hand models beside compaction (bookkeeper, subscription,
            # georeplication) replay generically through their
            # successors kernels — the service registry needs traces
            # from every spec, not just the flagship
            from pulsar_tlaplus_tpu.engine.core import replay_lane_trace

            return replay_lane_trace(self.model, init_idx, lanes)
        return replay(init_idx, lanes)

    def _trace_tiered(self, bufs, gid: int, max_depth: int):
        """Host-side chain walk over the merged logs: the cold tiers
        stream the aged [0, row_base) ranges back, the device window
        supplies the tail — gid indexing is absolute either way."""
        base = self._last_rb["row_base"]
        nv = int(self._trace_nv)
        cold_par, cold_lan = self.tstore.fetch_logs(0, base)
        par = np.concatenate(
            [cold_par, np.asarray(bufs["parent"][: nv - base])]
        )
        lan = np.concatenate(
            [cold_lan, np.asarray(bufs["lane"][: nv - base])]
        )
        chain = []
        g = int(gid)
        for _ in range(max_depth):
            if g < 0:
                break
            chain.append((g, int(lan[g])))
            g = int(par[g])
        else:
            raise RuntimeError(
                "parent chain did not terminate at an initial state "
                f"(depth {max_depth}, last gid {g}) — trace log "
                "corrupt"
            )
        init_idx = -1 - g
        chain.reverse()
        lanes = [lane for _gid, lane in chain[1:]]
        return self._replay_chain(init_idx, lanes)

    # ------------------------------------------------------------ result

    @spans.in_phase("result")
    def _result(
        self, t0, nv, level_sizes, bufs,
        viol: Optional[Tuple[str, int]] = None,
        dead_gid: Optional[int] = None,
        truncated: bool = False,
        stop_reason: Optional[str] = None,
    ) -> CheckerResult:
        self.last_bufs = bufs  # part of the result: the class docstring
        wall = time.perf_counter() - t0
        if self._last_fpm is not None:
            # per-run fpset metrics for bench.py artifacts: flush count,
            # cumulative probe rounds (avg = rounds/flushes), failures
            # (always 0 here — nonzero aborts at the fetch), and the
            # final table occupancy
            fl, rd, fd = (int(x) for x in self._last_fpm[:3])
            self.last_stats.update(
                fpset_flushes=fl,
                fpset_probe_rounds=rd,
                fpset_avg_probe_rounds=round(rd / max(fl, 1), 2),
                fpset_failures=fd,
                fpset_table_cap=self.TCAP,
                fpset_occupancy=round(nv / max(self.TCAP, 1), 4),
            )
            if len(self._last_fpm) >= 5:
                # zero-sync device counters (r8): candidate lanes after
                # validity masking (duplicate-rate denominator — 64-bit
                # hi/lo reassembly since r12, honest past 2.1G lanes)
                # and the worst single flush's probe depth; lanes
                # presented to the table over all probe rounds (PR 28)
                # against the valid ones says how closely the probe's
                # width followed its pending count
                fpml = fpset.fpm_logical(self._last_fpm)
                vl, lr = int(fpml[3]), int(fpml[5])
                self.last_stats.update(
                    fpset_valid_lanes=vl,
                    fpset_max_probe_rounds=int(self._last_fpm[4]),
                    fpset_duplicate_ratio=round(
                        max(1.0 - nv / vl, 0.0), 4
                    ) if vl else None,
                    fpset_lane_rounds=lr,
                    fpset_lanes_presented_per_valid=round(
                        lr / vl, 4
                    ) if vl else None,
                    # the rounds by the schedule's entry [dense,
                    # *stages]: they sum to fpset_probe_rounds, and a
                    # 0 names a step no flush of this run entered
                    fpset_step_rounds=fpset.fpm_step_rounds(
                        self._last_fpm, self.fps_stages
                    ),
                    # the table's slots summed over this run's probe
                    # rounds (folded in _fetch), and over the valid
                    # lanes of the same rounds: what the table-sized
                    # passes of a round cost by (PR 38)
                    fpset_slot_rounds=self._slot_rounds,
                    fpset_slots_per_valid=round(
                        self._slot_rounds / (vl - self._slot_valid_at), 4
                    ) if vl > self._slot_valid_at else None,
                    # of this run's probe rounds, those arbitrated
                    # among the lanes, with no ``claims`` array at the
                    # table's size (folded in _fetch; PR 40)
                    fpset_lane_arb_rounds=self._arb_rounds,
                    fpset_lane_arb_rounds_pct=round(
                        100.0 * self._arb_rounds / self._arb_of, 4
                    ) if self._arb_of else None,
                )
                # of this run's lanes presented, those handed to the
                # table's column scatters: all of them at a step that
                # writes every lane, the winners in chunks at a narrow
                # one (folded in _fetch; PR 42)
                wl = lr - self._write_lanes_at - self._write_saved
                self.last_stats.update(
                    fpset_write_lanes=wl,
                    fpset_write_lanes_per_valid=round(
                        wl / (vl - self._slot_valid_at), 4
                    ) if vl > self._slot_valid_at else None,
                )
        # fusion telemetry (r13): this run's total dispatches per BFS
        # level — the regression-gate signal (steady-state fused levels
        # read 1.0 + the init/ramp amortization; the stage chain reads
        # the full per-stage chain length)
        self.last_stats["dispatches_per_level"] = round(
            (self._dispatch_total() - getattr(self, "_disp_prev", 0))
            / max(len(level_sizes), 1),
            2,
        )
        # tiered-store telemetry (r16): cumulative spill counters +
        # the two headline economy signals — compressed spill bytes
        # per distinct state (the 1B-state byte-rate arithmetic's
        # input) and the overlap ratio (1.0 = boundaries never waited
        # on a transfer)
        if self.tiered and self.tstore is not None:
            with spans.span("spill.join"):
                self.tstore.flush()
            sp = self.tstore.stats
            self.last_stats.update(
                hbm_budget=self.hbm_budget,
                spill_evictions=int(sp.evictions),
                spill_keys_evicted=int(sp.keys_evicted),
                spill_rows_evicted=int(sp.rows_evicted),
                spill_bytes_raw=int(sp.bytes_raw),
                spill_bytes_comp=int(sp.bytes_comp),
                # the fetches' seconds and the encoder's, mixed;
                # spill_fetch_s is the fetches' alone
                spill_transfer_s=round(sp.transfer_s, 3),
                spill_fetch_s=self._spill_fetch_s,
                spill_lookup_s=sp.lookup_s,
                spill_blocked_s=sp.blocked_s,
                spill_joins=int(sp.joins),
                spill_misses_resolved=int(sp.misses_resolved),
                spill_miss_hits=int(sp.miss_hits),
                spill_syncs=int(self._spill_sync_n),
                # round trips the fetches made, and the columns they
                # brought: planes a fetch is how often columns share one
                spill_fetches=self._spill_fetches,
                spill_fetch_planes=self._spill_fetch_planes,
                spill_hot_keys=int(self._hot_n),
                # the hot tier's peak over the run, against the final
                # count: what the budget held the device to
                spill_hot_keys_max=int(self._hot_max),
                spill_hot_share_max_pct=round(
                    100.0 * self._hot_max / max(nv, 1), 4
                ),
                spill_cold_runs=self.tstore.cold_runs,
                # the one index a lookup reads (PR 51): the seconds and
                # the count of the merges that built it, the keys in it
                spill_merge_s=sp.merge_s,
                spill_merges=int(sp.merges),
                spill_index_keys=int(sp.index_keys),
                spill_d2h_bytes=self._spill_d2h_bytes,
                spill_d2h_padded_bytes=self._spill_d2h_padded_bytes,
                # table slots summed over the evictions (a roofline's
                # bytes: benchmark/lib/spill_bytes.py)
                spill_evict_slots=self._spill_evict_slots,
                spill_tier_ceilings=list(self._tier_ceilings),
                spill_budget_overridden=bool(self._budget_overridden),
                spill_overlap_ratio=sp.overlap_ratio,
                spill_bytes_per_state=round(
                    sp.bytes_comp / max(nv, 1), 2
                ),
                spill_degraded=bool(self.tstore.degraded),
            )
            self._emit_spill(len(level_sizes), final=True)
            # run over: release the spill worker thread (the in-RAM
            # tiers stay readable for the trace walk / liveness sweep)
            self.tstore.quiesce()
        # survivability telemetry for bench artifacts (r7/r8/r9)
        self.last_stats.update(
            fuse=self.fuse,
            **obs.IMPL_FIELDS,
            hbm_recovered=self._hbm_recovered,
            **self._ckpt_stats(),
            stats_fetches=self._fetch_n,
            **obs.model_stats(self.model, self.keys),
        )
        if self._resume_level is not None:
            # the levels this resumed run closed on top of its frame's
            self.last_stats["resume_levels_run"] = (
                len(level_sizes) - self._resume_level
            )
        res = CheckerResult(
            distinct_states=nv,
            diameter=len(level_sizes),
            deadlock=dead_gid is not None,
            wall_s=wall,
            states_per_sec=nv / max(wall, 1e-9),
            level_sizes=level_sizes,
            truncated=truncated,
            stop_reason=stop_reason if truncated else None,
            hbm_recovered=self._hbm_recovered,
            fp_collision_prob=self.keys.collision_prob(nv),
        )
        gid = None
        if viol is not None:
            res.violation = viol[0]
            gid = viol[1]
        elif dead_gid is not None:
            res.violation = "Deadlock"
            gid = dead_gid
        if gid is not None:
            res.violation_gid = gid
            self._trace_nv = nv
            if getattr(self, "_bufs_poisoned", False):
                # after RESOURCE_EXHAUSTED the parent/lane logs may hold
                # donated/poisoned storage — walking them could crash or
                # fabricate a trace; report the verdict without one
                res.trace = None
                res.trace_actions = None
                res.truncated = True
            else:
                res.trace, res.trace_actions = self._trace(
                    bufs, gid,
                    len(level_sizes) + 2
                    + int(getattr(self, "extra_trace_depth", 0)),
                )
        # fused-era cost attribution (r14): one machine-readable record
        # of the per-stage work-unit totals right before the result —
        # the input obs/attribution.py prices with the calibrated
        # per-backend unit costs
        work = {
            k[len("work_"):]: int(v)
            for k, v in self.last_stats.items()
            if k.startswith("work_")
        }
        if work:
            self.tel.emit("attribution", stages=work)
        # host phases and the compile meter (obs/spans.py), taken here,
        # at the emit, the last thing a run does: host_<phase>_s sum
        # with host_unaccounted_s to the wall of run(); host_wait_s
        # keeps its key and is the fetch phase; jit_* is an orthogonal
        # cut (what JAX traced, lowered, compiled and loaded)
        phases = self._clock.stats()
        g = self._growth
        self.last_stats.update(
            phases,
            host_wait_s=phases["host_fetch_s"],
            grow_events=g.events,
            grow_rehashes=g.rehashes,
            grow_rehash_slots=g.rehash_slots,
            grow_rehash_keys=g.rehash_keys,
            grow_rehash_lane_rounds=g.rehash_lane_rounds,
            grow_copy_bytes=g.copy_bytes,
            grow_tiers_final=[self.TCAP, self.LCAP, self.PCAP],
            **spans.compile_meter().since(self._jit0),
        )
        # the final stream record carries the whole last_stats dict
        # (stage counters/timings, rtt_s, fpset_*, ckpt_*) — the report
        # layer rebuilds the per-stage table and BENCH keys from it
        self.tel.emit(
            "result",
            distinct_states=nv,
            diameter=len(level_sizes),
            wall_s=round(wall, 3),
            states_per_sec=round(nv / max(wall, 1e-9), 1),
            truncated=truncated,
            stop_reason=res.stop_reason,
            violation=res.violation,
            violation_gid=res.violation_gid,
            deadlock=res.deadlock,
            hbm_recovered=self._hbm_recovered,
            level_sizes=[int(x) for x in level_sizes],
            fp_collision_prob=res.fp_collision_prob,
            stats={
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in self.last_stats.items()
            },
        )
        return res
