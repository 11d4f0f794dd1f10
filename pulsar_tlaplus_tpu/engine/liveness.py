"""Liveness checking (SURVEY.md §2.2-E10): ``<>goal`` properties over the
reachable state graph, e.g. ``Termination`` (compaction.tla:303-307).

TPU/host split (SURVEY.md §7-L6): the TPU generates the behavior graph —
the exhaustive BFS plus a vectorized edge-materialization sweep over all
discovered states — and the graph analysis (reachability under the
not-goal restriction, Kahn-peeling cycle detection) runs on the host as
vectorized numpy level sweeps.

Round-4 scaling (VERDICT r3 #5): the round-3 sweep round-tripped every
successor key through host ``np.searchsorted`` per 2048-state chunk —
fine at 253k states, hopeless at millions: every chunk paid a host
round trip.  Now the whole gid lookup runs on device against the engine's
own HBM-resident row store:

- a key->gid table is built once: state keys (straight from the packed
  rows, no unpack) sorted with their gid as payload;
- each sweep chunk expands successors, makes their keys, and joins them
  against the table with ONE merged sort + a log-shift gid propagation
  through equal-key runs — no gathers (latency-bound on TPU), no host
  in the loop;
- only the final int32 dst-gid lanes stream to the host (the edge list
  the analysis needs), plus one bool per state for the goal predicate.

Semantics (matching the oracle, pyeval.check_eventually):

- ``fairness="none"``: ``Spec == Init /\\ [][Next]_vars`` admits infinite
  stuttering anywhere, so ``<>P`` holds iff every initial state satisfies
  P; otherwise the counterexample is "stutter forever at a violating
  initial state" — which is exactly what TLC reports for unfair specs.
- ``fairness="wf_next"`` (``Spec /\\ WF_vars(Next)``): WF constrains only
  ``<Next>_vars`` steps — Next steps that *change* the state.  Stuttering
  disjuncts cannot discharge the fairness obligation, so the property is
  violated iff some only-not-P path from an initial state reaches a not-P
  state with no var-changing successor, or a cycle of var-changing not-P
  transitions (self-loops are stutters by definition and excluded).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL
from pulsar_tlaplus_tpu.utils import ckpt, faults

TAG = jnp.uint32(1 << 31)


class _Preempted(Exception):
    """Internal: SIGTERM/SIGINT landed and a resumable frame is on
    disk — unwind to run() with the states-examined count."""

    def __init__(self, n: int, phase: str):
        super().__init__(phase)
        self.n = n
        self.phase = phase


@dataclass
class LivenessResult:
    holds: bool
    reason: str
    distinct_states: int
    # a lasso skeleton when violated under wf_next (state gids)
    lasso_prefix: Optional[List[int]] = None
    lasso_cycle: Optional[List[int]] = None
    # expected number of key collisions in the edge join at this state
    # count (ADVICE r4): the join keys come from the SAME KeySpec the
    # explorer deduped with, so the probabilistic regime is stated once
    # — 0.0 for exact keys; for hashed keys a collision could alias two
    # visited states and make the sweep assign a query the wrong dst
    # gid (the -2 incomplete-exploration guard cannot catch that case)
    fp_collision_prob: float = 0.0
    # survivability (r9): a preempted/interrupted run carries NO
    # verdict — ``holds`` is meaningless while truncated is True;
    # ``run(resume=True)`` continues from the last frame
    truncated: bool = False
    stop_reason: Optional[str] = None
    # the behaviour graph the verdict was computed on, level by level
    # (``graph_summary``); None where the run was cut before it had one
    graph: Optional[dict] = None


class LivenessChecker:
    """Checks ``<>goal`` for a compiled model's named goal predicate.

    ``n_devices > 1`` runs the EXPLORATION on the mesh-sharded engine
    (its per-shard row stores are concatenated — gids densely remapped
    — before the sweep, which is a single-device program)."""

    def __init__(
        self,
        model: CompactionModel,
        goal: str = "Termination",
        fairness: str = "none",
        frontier_chunk: int = 2048,
        visited_cap: int = 1 << 14,
        max_states: int = 50_000_000,
        sweep_chunk: Optional[int] = None,
        sweep_group: Optional[int] = None,
        hbm_budget=None,
        spill_compress: Optional[bool] = None,
        n_devices: int = 1,
        explorer_kw: Optional[dict] = None,
        max_run: int = 1 << 14,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 4,
        telemetry=None,
        heartbeat_s: Optional[float] = None,
        progress: bool = False,
    ):
        goals = getattr(model, "liveness_goals", {})
        if goal not in goals:
            raise ValueError(
                f"unknown liveness property: {goal} "
                f"(model defines: {sorted(goals) or 'none'})"
            )
        if fairness not in ("none", "wf_next"):
            raise ValueError(f"unknown fairness: {fairness}")
        self.model = model
        self.goal_name = goal
        self.goal_fn = goals[goal]
        self.fairness = fairness
        self.F = frontier_chunk
        # the edge sweep's cost is dominated by the per-chunk join sort
        # of the FULL key->gid table (width n + chunk*A); a bigger
        # sweep chunk amortizes the table term ~linearly, so it is
        # decoupled from the exploration sub_batch (round 5: the 9.4M-
        # state round-4 run paid ~4600 full-table sorts at F=2048)
        self.SF = sweep_chunk or max(frontier_chunk, 1 << 14)
        # the goal scan chunks by F and the sweep by SF over the same
        # SENTINEL-padded table width, so SF must be a multiple of F
        self.SF = -(-self.SF // self.F) * self.F
        # Fused+grouped sweep (round 10, VERDICT r5 #5): one jitted
        # program runs the whole per-chunk join pipeline (merge sort +
        # capped log-shift gid propagation + payload sort + compaction)
        # for G consecutive chunks via lax.scan, and the host reads
        # back three plane transfers PER GROUP instead of three per
        # chunk — the host round trip amortizes across G chunks.
        # None = auto from HBM headroom at sweep time (the scan body's
        # join temps stay one-chunk-sized; only the compacted output
        # accumulator scales with G, bounded at the same 2^22-lane
        # threshold the round-5 prefetch gate used).
        if sweep_group is not None and sweep_group < 1:
            raise ValueError(f"sweep_group must be >= 1: {sweep_group}")
        self.sweep_group = sweep_group
        # pointer-jumping cap for the sweep's equal-key gid propagation
        # (ADVICE r5): doubling shifts d = 1, 2, ..., p (p = the
        # largest power of two <= max_run) cover a fill distance of
        # 2p - 1 equal-key queries per chunk — 32767 at the 2^14
        # default.  Exposed so the error message's remediation ("raise
        # max_run") is actionable; each extra doubling materializes one
        # more set of full-width temps, so very large values trade HBM
        # for run coverage.
        if max_run < 1:
            raise ValueError(f"max_run must be positive: {max_run}")
        self.max_run = max_run
        p = 1
        while p * 2 <= min(max_run, self.SF * model.A):
            p *= 2
        self._run_cover = 2 * p - 1
        self.n_devices = n_devices
        # survivability (r9): the exploration phase checkpoints through
        # the inner engine's own frame layer at the SAME path; once the
        # sweep starts, its chunk-boundary frames (which embed the
        # explored rows) overwrite the exploration frame — one file,
        # whichever phase died last owns it
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.progress = progress
        self._telemetry_arg = telemetry
        self.tel = obs.NULL
        self.heartbeat_s = heartbeat_s
        # checkpoint_every units differ by phase (inner: BFS levels;
        # sweep: chunks) but it is the same "frame cadence" knob —
        # forward it so a caller asking for tight frames gets them in
        # BOTH phases (explorer_kw can still override either)
        inner_kw = dict(
            checkpoint_path=checkpoint_path,
            checkpoint_every=checkpoint_every,
            # the explorer's per-level progress lines, as ``cli check``
            # prints them
            progress=progress,
        )
        # resolve the ctor-or-PTT_HBM_BUDGET budget HERE so the env
        # var gets the same gating/forwarding as the explicit knob
        from pulsar_tlaplus_tpu.store import budget as store_budget

        hbm_budget = store_budget.resolve_budget(hbm_budget)
        if hbm_budget is not None and n_devices > 1:
            raise ValueError(
                "hbm_budget needs the single-device explorer (the "
                "sharded engine has no tiered store yet)"
            )
        if n_devices <= 1 and hbm_budget is not None:
            # tiered exploration (r16): the inner explorer spills aged
            # rows to the host store; the sweep streams them back
            # tier by tier below (_explore)
            inner_kw.setdefault("hbm_budget", hbm_budget)
            if spill_compress is not None:
                inner_kw.setdefault("spill_compress", spill_compress)
        inner_kw.update(explorer_kw or {})
        if n_devices > 1:
            from pulsar_tlaplus_tpu.engine.sharded_device import (
                ShardedDeviceChecker,
            )

            self._checker = ShardedDeviceChecker(
                model,
                n_devices=n_devices,
                invariants=(),
                check_deadlock=False,
                sub_batch=max(256, frontier_chunk),
                visited_cap=visited_cap,
                max_states=max_states,
                **inner_kw,
            )
        else:
            from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

            # exploration runs on the device-resident engine (VERDICT
            # r2 #8); its append-only row store IS the packed state
            # matrix — it never leaves HBM.  rows_window stays "all":
            # the sweep re-keys every stored row.
            self._checker = DeviceChecker(
                model,
                invariants=(),
                check_deadlock=False,
                sub_batch=max(256, frontier_chunk),
                visited_cap=visited_cap,
                frontier_cap=visited_cap,
                max_states=max_states,
                **inner_kw,
            )
        self.keys = self._checker.keys  # shared KeySpec (ADVICE r4)
        self.K = self.keys.ncols
        self._explored = None  # (n, n_init) — rows stay on device
        self._rows_flat = None
        self._edge_cache = None  # (src, dst, out_deg) — goal-independent
        self._jits = {}
        self._diameter = 0
        self._level_sizes = None  # per BFS level, once explored
        self._watcher = None
        self._observer = None
        self._resume_explore = False
        # sweep-resume state: (src_parts, dst_parts, out_deg, chunk0)
        self._sweep_resume = None
        self._ckpt_frames = 0
        self._ckpt_bytes = 0
        self._ckpt_write_s = 0.0
        self._ckpt_retries = 0
        self._fetch_n = 0
        self._snap: dict = {}
        self._run_id: Optional[str] = None

    def _log(self, msg: str):
        if self.progress:
            import sys

            print(f"  {msg}", file=sys.stderr, flush=True)

    def _explore(self):
        """One exhaustive BFS, cached so several properties (cfg
        PROPERTIES) share the same reachable-set enumeration."""
        if self._explored is not None:
            return self._explored
        # the inner engine emits into the SAME stream (it never closes
        # a Telemetry instance it was handed) and runs its own
        # heartbeat for the exploration phase
        if self.tel.enabled:
            self._checker._telemetry_arg = self.tel
        if self.heartbeat_s and not self._checker.heartbeat_s:
            self._checker.heartbeat_s = self.heartbeat_s
        try:
            res = self._checker.run(resume=self._resume_explore)
        finally:
            self._resume_explore = False
            # the inner run() cleared the fault observer on exit;
            # re-install ours so sweep-phase drills keep breadcrumbs
            faults.set_observer(self._observer)
        if res.truncated and res.stop_reason == "preempted":
            # exploration wrote its own resumable frame on the way out
            raise _Preempted(res.distinct_states, "explore")
        if res.truncated:
            # a partial graph supports no liveness verdict — and the
            # remediation depends on WHY it is partial (r9: the inner
            # engines can now truncate for hbm/time_budget too, where
            # raising max_states would not help)
            why = res.stop_reason or "unknown"
            raise RuntimeError(
                "liveness exploration truncated before the state "
                f"space was exhausted (stop_reason={why}); "
                + (
                    "raise max_states"
                    if why == "max_states"
                    else "the verdict needs the full graph — rerun "
                    "with more memory/time or a smaller model"
                )
            )
        if res.violation is not None:
            # DeviceChecker force-appends __EvalError__ for compiled
            # specs even with invariants=(); ANY early stop means the
            # explored graph is partial, and a liveness verdict over a
            # partial graph would be silently wrong (ADVICE r3, medium)
            raise RuntimeError(
                "exploration stopped early on a violation "
                f"({res.violation}); liveness requires the full state "
                "graph — fix the safety violation first"
            )
        if self.n_devices > 1:
            # concatenate the per-shard row prefixes into one flat
            # array with densely remapped gids.  The analysis only
            # needs the INITIAL states to be gids [0, n_init), so the
            # flat order is: every shard's level-1 segment first, then
            # every shard's remainder.  The sweep is a single-device
            # program; at virtual-mesh scales this is host RAM, on
            # real hardware it requires the explored rows to fit one
            # device.
            bufs = self._checker.last_bufs
            counts = np.asarray(self._checker.last_stats_matrix[:, 0])
            c1 = np.asarray(self._checker.last_level1_counts)
            W = self.model.layout.W
            firsts = [
                np.asarray(bufs["rows"][s, : int(c1[s]) * W])
                for s in range(self._checker.N)
            ]
            rests = [
                np.asarray(
                    bufs["rows"][s, int(c1[s]) * W: int(counts[s]) * W]
                )
                for s in range(self._checker.N)
            ]
            self._rows_flat = jnp.asarray(np.concatenate(firsts + rests))
        elif (
            getattr(self._checker, "tiered", False)
            and self._checker.tstore is not None
            and self._checker.tstore.rows_spilled_hi > 0
        ):
            # tiered exploration (r16): the aged row ranges live in
            # the cold tiers — stream them back tier by tier, in gid
            # order, and append the device window's tail.  The
            # EXPLORER never had to keep every row in HBM; the sweep
            # itself still materializes the full matrix for its
            # key->gid table (chunking the sweep's own table is the
            # ROADMAP follow-up — at virtual-mesh scales this is host
            # RAM, like the sharded branch above).
            ck = self._checker
            base = ck.tstore.rows_spilled_hi
            W = self.model.layout.W
            n = res.distinct_states
            cold = ck.tstore.fetch_rows(0, base, W)
            devpart = np.asarray(
                ck.last_bufs["rows"][: (n - base) * W]
            )
            self._rows_flat = jnp.asarray(
                np.concatenate([cold, devpart])
            )
        else:
            self._rows_flat = self._checker.last_bufs["rows"]
        # the sweep only reads the flat rows: drop the explorer's
        # visited columns / accumulators / logs so their HBM is
        # available for the sweep's full-table join temps (in the
        # sharded branch the per-shard rows too — _rows_flat already
        # holds the copy)
        keep = (
            ()
            if self.n_devices > 1
            or self._rows_flat is not self._checker.last_bufs.get(
                "rows"
            )
            else ("rows",)
        )
        for k in list(self._checker.last_bufs):
            if k not in keep:
                del self._checker.last_bufs[k]
        self._explored = (res.distinct_states, res.level_sizes[0])
        self._diameter = res.diameter
        # gids are in discovery order on one device; the sharded
        # branch above remaps them shard by shard, so a level is no gid
        # range there and the graph summary gives no per-level columns
        if self.n_devices <= 1:
            self._level_sizes = [int(x) for x in res.level_sizes]
        return self._explored

    def run_goal(self, goal: str) -> LivenessResult:
        """Check another named goal over the same explored state space."""
        goals = getattr(self.model, "liveness_goals", {})
        if goal not in goals:
            raise ValueError(f"unknown liveness property: {goal}")
        self.goal_name = goal
        self.goal_fn = goals[goal]
        return self.run()

    # ------------------------------------------------------ device jits

    def _keys_of_rows(self, rows_flat, cap):
        """Key columns of the first ``cap`` packed rows (no unpack).
        Derived from the SAME KeySpec the explorer deduped with
        (ADVICE r4): the join inherits the explorer's exact-or-hashed
        regime and its collision probability is reported once, in
        ``LivenessResult.fp_collision_prob``."""
        W = self.model.layout.W
        packed = lax.dynamic_slice(rows_flat, (0,), (cap * W,)).reshape(
            cap, W
        )
        return self.keys.make(packed)

    def _table_jit(self, cap):
        """rows_flat, n -> sorted (key cols..., gid) key->gid table of
        static width ``cap`` (SENTINEL-padded past n)."""
        key = ("table", cap)
        if key in self._jits:
            return self._jits[key]
        K = self.K

        @spans.staged("live_table")
        def ptt_live_table(rows_flat, n):
            kc = self._keys_of_rows(rows_flat, cap)
            live = jnp.arange(cap, dtype=jnp.int32) < n
            kc = tuple(jnp.where(live, c, SENTINEL) for c in kc)
            gid = jnp.arange(cap, dtype=jnp.uint32)
            return lax.sort((*kc, gid), num_keys=K, is_stable=False)

        fn = jax.jit(ptt_live_table)
        self._jits[key] = fn
        return fn

    def _goal_jit(self, cap):
        """rows_flat, n -> bool[cap] goal-predicate values."""
        key = ("goal", cap, self.goal_fn)
        if key in self._jits:
            return self._jits[key]
        layout = self.model.layout
        W = layout.W
        F = self.F

        @spans.staged("live_goal")
        def ptt_live_goal(rows_flat, n):
            def chunk(c, _):
                rows = lax.dynamic_slice(
                    rows_flat, (c * F * W,), (F * W,)
                ).reshape(F, W)
                g = jax.vmap(
                    lambda w: self.goal_fn(layout.unpack(w))
                )(rows)
                return c + 1, g

            _, gs = lax.scan(
                chunk, jnp.int32(0), None, length=cap // F
            )
            return gs.reshape(cap)

        fn = jax.jit(ptt_live_goal)
        self._jits[key] = fn
        return fn

    def _sweep_jit(self, cap, G):
        """(rows_flat, off0, n_live, table cols) -> compacted
        ``<Next>_vars`` edges of ``G`` consecutive SF-state windows
        starting at ``off0``: ``(n_kept[G], lane_idx[G, NQ],
        dst[G, NQ])`` where only each row's first ``n_kept[g]`` entries
        are meaningful — invalid lanes and self-loops (stutters) are
        dropped ON DEVICE before anything crosses to the host (VERDICT
        r4 #6: the round-4 sweep streamed every F*A dst lane to the
        host, ~157 s of the 279 s total at 9.4M states).  A valid lane
        whose key misses the table keeps dst = -2 so the host still
        fails loudly on incomplete exploration.  ``src = off +
        lane_idx // A`` is reconstructed host-side, so exactly two
        plane transfers (group-prefix-sliced) move per GROUP.

        Round 10 (VERDICT r5 #5): the whole per-chunk join pipeline —
        one merged sort of (table, query keys) with the table's gid as
        payload (table entries order before equal-key queries via the
        payload tag bit), the capped log-shift gid propagation through
        equal-key runs, the payload sort back to query order, and the
        edge compaction — is FUSED into this one jitted program and
        batched over ``G`` chunks with ``lax.scan``, so the host round
        trip is paid once per group instead of per chunk.  The
        scan body's join temps stay one-chunk-sized; only the
        compacted output planes scale with G.  Chunks past the live
        prefix produce zero kept lanes (their query lanes are masked
        invalid), so a partial tail group is harmless."""
        key = ("sweep", cap, G)
        if key in self._jits:
            return self._jits[key]
        m, layout = self.model, self.model.layout
        W, A, SF = layout.W, self.model.A, self.SF
        from pulsar_tlaplus_tpu.ops import compact as compact_ops

        NQ = SF * A
        K = self.K

        @spans.staged("sweep_expand")
        def expand(rows_flat, off, n_live):
            rows = lax.dynamic_slice(
                rows_flat, (off * W,), (SF * W,)
            ).reshape(SF, W)
            states = jax.vmap(layout.unpack)(rows)
            succ, valid = jax.vmap(m.successors)(states)
            live = off + jnp.arange(SF, dtype=jnp.int32) < n_live
            valid = valid & live[:, None]
            sp = jax.vmap(jax.vmap(layout.pack))(succ).reshape(NQ, W)
            qc = self.keys.make(sp)
            vq = valid.reshape(NQ)
            return tuple(jnp.where(vq, c, SENTINEL) for c in qc), vq

        @spans.staged("sweep_join")
        def merge(tcols, tg, qc):
            qpay = jnp.arange(NQ, dtype=jnp.uint32) | TAG
            cols = tuple(
                jnp.concatenate([t, q]) for t, q in zip(tcols, qc)
            )
            pay = jnp.concatenate([tg, qpay])
            out = lax.sort((*cols, pay), num_keys=K + 1, is_stable=False)
            return out[:K], out[K]

        @spans.staged("sweep_prop")
        def propagate(scols, sp_):
            # carried gid: table rows expose their gid; query rows start
            # unknown (-1) and take it from the nearest preceding
            # equal-key row via log-shift propagation
            is_q = (sp_ & TAG) != 0
            gid = jnp.where(is_q, -1, sp_.astype(jnp.int32))
            # pointer-jumping: a run = 1 unique table entry + its
            # equal-key queries; doubling shifts d = 1..MAXRUN cover a
            # fill distance of 2*MAXRUN - 1 (capped — each unrolled
            # pass materializes full-width temps, and covering the
            # theoretical NQ worst case OOMed at 2^20-state chunks).
            # A key with more equal-key queries in one chunk leaves
            # gids at -1, which map to -2 below — the host fails
            # LOUDLY (same contract as incomplete exploration), never
            # silently.  ``max_run`` (constructor) raises the cap.
            MAXRUN = min(NQ, self.max_run)
            d = 1
            while d <= MAXRUN:
                # shift forward by d: rows [d:] see row [i-d]
                pks = tuple(
                    jnp.concatenate([jnp.full((d,), SENTINEL), c[:-d]])
                    for c in scols
                )
                pg = jnp.concatenate(
                    [jnp.full((d,), -1, jnp.int32), gid[:-d]]
                )
                same = pks[0] == scols[0]
                for pk, c in zip(pks[1:], scols[1:]):
                    same = same & (pk == c)
                gid = jnp.where((gid < 0) & same, pg, gid)
                d <<= 1
            return gid

        @spans.staged("sweep_join")
        def unmerge(sp_, gid, vq):
            # back to query order: payload sort; queries (TAG set) sort
            # after every table gid and ascend by lane index
            _, gq = lax.sort(
                (sp_, lax.bitcast_convert_type(gid, jnp.uint32)),
                num_keys=1, is_stable=False,
            )
            dst = lax.bitcast_convert_type(gq[cap:], jnp.int32)
            return jnp.where(vq, jnp.where(dst < 0, -2, dst), -1)

        @spans.staged("sweep_compact")
        def keep_edges(dst, off):
            # device-side compaction: keep valid non-stutter lanes
            # (dst == -2 kept so the host sees incomplete exploration)
            lane = jnp.arange(NQ, dtype=jnp.int32)
            src = off + lane // A
            keep = (dst != -1) & (dst != src)
            (idxc, dstc), _ = compact_ops.compact_by_flag(
                (~keep).astype(jnp.uint32),
                (lane.astype(jnp.uint32),
                 lax.bitcast_convert_type(dst, jnp.uint32)),
                need_idx=False,
            )
            n_kept = jnp.sum(keep.astype(jnp.int32))
            return n_kept, idxc, dstc

        def one_chunk(rows_flat, off, n_live, targs):
            qc, vq = expand(rows_flat, off, n_live)
            scols, sp_ = merge(targs[:K], targs[K], qc)
            gid = propagate(scols, sp_)
            return keep_edges(unmerge(sp_, gid, vq), off)

        # the scan's own work (its counter, the stacking of each
        # chunk's compacted planes) is part of writing the kept edges
        @spans.staged("sweep_compact")
        def ptt_sweep(rows_flat, off0, n_live, *targs):
            def body(carry, g):
                out = one_chunk(
                    rows_flat, off0 + g * SF, n_live, targs
                )
                return carry, out

            _, (nk, idxc, dstc) = lax.scan(
                body, 0, jnp.arange(G, dtype=jnp.int32)
            )
            return nk, idxc, dstc

        fn = jax.jit(ptt_sweep)
        self._jits[key] = fn
        return fn

    def _sweep_group_size(self) -> int:
        """Chunks per sweep dispatch: the ctor's ``sweep_group``, else
        auto from HBM headroom — the scan body's join temps are
        one-chunk-sized regardless, so the only G-scaling buffers are
        the compacted output planes; bound them at the same 2^22-lane
        threshold the round-5 prefetch gate used (with double-buffering
        that is two groups ≈ 64 MB of planes), capped at 8."""
        if self.sweep_group is not None:
            return int(self.sweep_group)
        NQ = self.SF * self.model.A
        return max(1, min(8, (1 << 22) // max(NQ, 1)))

    # ----------------------------------------------------- edge harvest

    def _edges(self, n):
        """Goal-independent <Next>_vars edge list (CSR-ready numpy
        int32 arrays) + out-degree per state.  Only the compacted
        (lane_idx, dst) prefixes cross to the host.

        Survivability (r9): sweep-chunk boundaries are the liveness
        engine's frame sites — every ``checkpoint_every`` chunks the
        accumulated edges (plus the explored rows, so a resumed
        process needs no re-exploration) go to ``checkpoint_path``
        through the shared atomic writer; ``kill@sweep:N`` /
        ``sigterm@sweep:N`` drills fire here, and a preemption request
        exits resumably after the frame lands."""
        if self._edge_cache is not None:
            return self._edge_cache
        A = self.model.A
        cap = self._table_cap(n)
        SF = self.SF
        G = self._sweep_group_size()
        # sweep work units (r14, fused-era cost attribution): the
        # per-chunk join pipeline's costs are trace-time constants —
        # two (cap + NQ)-wide sorts (merge + payload), ``passes``
        # doubling-shift gid-propagation sweeps over the same width,
        # and one NQ-lane edge compaction — so the host accumulates
        # them as each chunk is consumed (zero extra syncs; the
        # per-chunk ``sweep`` records carry the cumulative totals and
        # ``--attribution`` prices them per sub-stage)
        NQ = SF * A
        maxrun = min(NQ, self.max_run)
        passes = 0
        d_ = 1
        while d_ <= maxrun:
            passes += 1
            d_ <<= 1
        chunk_sort = 2 * (cap + NQ)
        chunk_prop = passes * (cap + NQ)
        # the last group's scan windows may run past the table cap;
        # pad the flat rows so no dynamic_slice can clamp (the overrun
        # chunks' lanes are masked dead and compact to zero kept)
        clock = self._clock
        with clock.phase("live_table"):
            rows = self._rows_padded(cap + (G - 1) * SF)
            with clock.upload("ptt_live_table", 1):
                n_d = jnp.int32(n)
            with clock.call("ptt_live_table"):
                targs = self._table_jit(cap)(rows, n_d)
        jitted = self._sweep_jit(cap, G)
        starts = list(range(0, n, SF))
        src_parts, dst_parts = [], []
        out_deg = np.zeros((n,), np.int64)
        c0 = 0
        if self._sweep_resume is not None:
            src_parts, dst_parts, out_deg, c0 = self._sweep_resume
            self._sweep_resume = None
            self._log(
                f"resumed sweep at chunk {c0}/{len(starts)} "
                f"({sum(len(p) for p in src_parts)} edges so far)"
            )
        n_edges = sum(len(p) for p in src_parts)
        # double-buffer: dispatch group g+1 before materializing group
        # g, so device compute overlaps the host readback (groups
        # are independent).  At big sweep chunks two
        # in-flight join programs double the full-table sort + shift
        # transients — that OOMed the 29.4M-state tier at SF=2^19 —
        # so prefetch is disabled there (the per-group readback is a
        # smaller fraction of group time at that size anyway).
        prefetch = G * SF * A <= (1 << 22)
        gstarts = list(range(c0, len(starts), G))
        self._sweep_n.update(
            chunks=len(starts) - c0, groups=len(gstarts),
            query_lanes=(len(starts) - c0) * NQ,
        )
        pending = []
        sent = 0  # groups dispatched so far: gstarts[:sent]
        for gi, g0 in enumerate(gstarts):
            # this group, if it is not in flight yet, and with
            # prefetch the next one: ONE dispatch site for both
            ahead = gi + (2 if prefetch else 1)
            while sent < min(ahead, len(gstarts)):
                with clock.phase("sweep_dispatch"):
                    with clock.upload("ptt_sweep", 2):
                        off_d = jnp.int32(starts[gstarts[sent]])
                        n_d = jnp.int32(n)
                    with clock.call("ptt_sweep"):
                        pending.append(jitted(rows, off_d, n_d, *targs))
                sent += 1
            nk_g, idx_g, dst_g = pending.pop(0)
            # three transfers per GROUP: the counts, then the two
            # edge planes sliced to the group's max kept prefix — the
            # per-chunk round trip this loop used to pay 3x per chunk
            # now amortizes across the G chunks of the group
            with clock.phase("sweep_fetch"):
                nk_host = np.asarray(nk_g)
                self._fetch_n += 1
                last = min(g0 + G, len(starts))
                kmax = int(nk_host[: last - g0].max()) if last > g0 else 0
                self._sweep_n["d2h_bytes"] += nk_host.nbytes
                if kmax:
                    idx_all = np.asarray(idx_g[:, :kmax])
                    dst_all = np.asarray(dst_g[:, :kmax])
                    self._sweep_n["d2h_bytes"] += (
                        idx_all.nbytes + dst_all.nbytes
                    )
            with clock.phase("sweep_account"):
                for i in range(g0, last):
                    start = starts[i]
                    # deterministic fault site: sweep chunk i+1 is about
                    # to be consumed (kill/sigterm fire inside poll; an
                    # injected oom raises — the sweep has no
                    # degraded-capacity rebuild)
                    kinds = faults.poll("sweep", i + 1)
                    if "oom" in kinds:
                        raise faults.oom_error("sweep", i + 1)
                    k = int(nk_host[i - g0])
                    if k:
                        idx = idx_all[i - g0, :k].astype(np.int64)
                        dst = dst_all[i - g0, :k].view(np.int32).astype(
                            np.int64
                        )
                        if (dst == -2).any():
                            raise RuntimeError(
                                "edge sweep could not resolve a successor "
                                "gid: either BFS exploration was "
                                "incomplete, or one state has more than "
                                f"{self._run_cover} equal-key predecessors "
                                "inside a single sweep chunk — shrink "
                                "sweep_chunk or raise max_run "
                                f"(currently {self.max_run})"
                            )
                        uu = start + idx // A
                        src_parts.append(uu)
                        dst_parts.append(dst)
                        np.add.at(out_deg, uu, 1)
                        n_edges += k
                    # progress for the heartbeat (zero extra device syncs:
                    # the group planes were already materialized above) +
                    # the stream record
                    swept = min(start + SF, n)
                    self._work_sweep["sort_lanes"] += chunk_sort
                    self._work_sweep["prop_lanes"] += chunk_prop
                    self._work_sweep["prop_passes"] += passes
                    self._work_sweep["compact_elems"] += NQ
                    self._snap.update(
                        distinct_states=n, level=i + 1, generated=n_edges
                    )
                    self.tel.emit(
                        "sweep",
                        chunk=i + 1,
                        chunks=len(starts),
                        swept=swept,
                        edges=n_edges,
                        group=G,
                        wall_s=round(time.time() - self._t0, 3),
                        # cumulative sweep work units (v7)
                        sort_lanes=self._work_sweep["sort_lanes"],
                        prop_lanes=self._work_sweep["prop_lanes"],
                        prop_passes=self._work_sweep["prop_passes"],
                        compact_elems=self._work_sweep["compact_elems"],
                    )
                    done = i + 1 >= len(starts)
                    preempt = (
                        self._watcher is not None
                        and self._watcher.requested
                    )
                    if self.checkpoint_path and not done and (
                        preempt
                        or (i + 1 - c0) % self.checkpoint_every == 0
                    ):
                        self._save_sweep_frame(
                            n, src_parts, dst_parts, out_deg, i + 1
                        )
                        if preempt:
                            raise _Preempted(n, "sweep")
        with clock.phase("sweep_account"):
            src = (
                np.concatenate(src_parts) if src_parts
                else np.zeros(0, np.int64)
            )
            dst = (
                np.concatenate(dst_parts) if dst_parts
                else np.zeros(0, np.int64)
            )
        self._edge_cache = (src, dst, out_deg)
        return self._edge_cache

    # ----------------------------------------------- checkpoint/resume

    def _config_sig(self) -> str:
        """Everything a sweep frame must agree on to be resumable
        here.  Goal and fairness are NOT part of it: the edge list is
        goal-independent (run_goal reuses it), and the verdict is
        recomputed from the restored edges."""
        return ckpt.config_sig(
            model=ckpt.model_sig(self._checker.model),
            state_bits=self.model.layout.total_bits,
            key_cols=self.K,
            key_exact=self.keys.exact,
            sweep_chunk=self.SF,
            engine="liveness_r9",
        )

    def _save_sweep_frame(
        self, n, src_parts, dst_parts, out_deg, next_chunk
    ):
        """One atomic sweep frame: the explored rows (so resume needs
        no re-exploration), the accumulated edge list, and the next
        chunk index.  ``sweep_chunk`` is in the signature because the
        chunk index is only meaningful at the same SF."""
        t_stall = time.perf_counter()
        W = self.model.layout.W
        n_init = self._explored[1]
        arrays = {
            "n": np.int64(n),
            "n_init": np.int64(n_init),
            "diameter": np.int64(self._diameter),
            "level_sizes": np.asarray(self._level_sizes or [], np.int64),
            "next_chunk": np.int64(next_chunk),
            "rows": np.asarray(self._rows_flat[: n * W]),
            "src": (
                np.concatenate(src_parts)
                if src_parts else np.zeros(0, np.int64)
            ),
            "dst": (
                np.concatenate(dst_parts)
                if dst_parts else np.zeros(0, np.int64)
            ),
            "out_deg": out_deg,
        }
        nbytes, write_s, retries = ckpt.save_frame(
            self.checkpoint_path, self._config_sig(), arrays,
            wall_s=time.time() - self._t0,
            meta={
                "run_id": self._run_id,
                "frame_seq": self._ckpt_frames + 1,
                "phase": "sweep",
                "engine": "liveness",
            },
        )
        stall_s = time.perf_counter() - t_stall
        self._ckpt_frames += 1
        self._ckpt_bytes += nbytes
        self._ckpt_write_s += stall_s
        self._ckpt_retries += retries
        self.tel.emit(
            "ckpt_frame",
            frame_seq=self._ckpt_frames,
            bytes=nbytes,
            write_s=round(write_s, 3),
            stall_s=round(stall_s, 3),
            retries=retries,
            phase="sweep",
            chunk=next_chunk,
            distinct_states=n,
        )
        self._log(
            f"sweep checkpoint: chunk {next_chunk}, {n} states "
            f"({nbytes >> 10} KiB, {stall_s:.2f}s stall) -> "
            f"{self.checkpoint_path}"
        )

    def _try_resume_sweep(self) -> bool:
        """Load a sweep-phase frame if that is what ``checkpoint_path``
        holds; an exploration-phase frame (the inner engine's
        signature) returns False so the caller resumes exploration
        instead.  A missing file raises FileNotFoundError untouched."""
        try:
            d = ckpt.load_frame(self.checkpoint_path, self._config_sig())
        except FileNotFoundError:
            raise
        except ValueError:
            return False  # an exploration-phase (inner-engine) frame
        n = int(d["n"])
        self._explored = (n, int(d["n_init"]))
        self._diameter = int(d["diameter"])
        if "level_sizes" in d:  # a frame from before PR 36 has none
            self._level_sizes = [int(x) for x in d["level_sizes"]]
        self._rows_flat = jnp.asarray(np.asarray(d["rows"], np.uint32))
        src = np.asarray(d["src"], np.int64)
        dst = np.asarray(d["dst"], np.int64)
        self._sweep_resume = (
            [src] if len(src) else [],
            [dst] if len(dst) else [],
            np.asarray(d["out_deg"], np.int64),
            int(d["next_chunk"]),
        )
        self._resume_meta = ckpt.frame_meta(d)
        self._log(
            f"resuming the edge sweep from chunk {int(d['next_chunk'])}"
            f" ({n} explored states restored, no re-exploration)"
        )
        return True

    def _table_cap(self, n: int) -> int:
        # round up to a multiple of the sweep chunk (itself a multiple
        # of the goal chunk F)
        return max(self.SF, -(-n // self.SF) * self.SF)

    # -------------------------------------------------------------- run

    def _rows_padded(self, cap):
        """The goal/sweep programs slice fixed F/SF-state windows, so
        the flat rows buffer must cover the SENTINEL-padded table cap
        (the exploration store can be smaller when SF exceeds its
        capacity tier)."""
        W = self.model.layout.W
        need = cap * W
        if self._rows_flat.shape[0] < need:
            self._rows_flat = jnp.concatenate(
                [
                    self._rows_flat,
                    jnp.zeros(
                        (need - self._rows_flat.shape[0],), jnp.uint32
                    ),
                ]
            )
        return self._rows_flat

    def run(self, resume: bool = False) -> LivenessResult:
        """Check the current goal.  ``resume=True`` continues an
        interrupted run from ``checkpoint_path``: a sweep-phase frame
        restores the explored rows + accumulated edges (no
        re-exploration); an exploration-phase frame resumes the inner
        engine's BFS first.  SIGTERM/SIGINT during the run exit
        resumably with ``stop_reason="preempted"``."""
        # this run's exclusive host phases (spans.LIVE_PHASES) and the
        # compile meter's reading before it, as DeviceChecker.run()
        # keeps its own; ptt:run is the container the phases lie in
        clock = self._clock = spans.PhaseClock(obs.new_run_id())
        self._jit0 = spans.compile_meter().snapshot()
        with spans.span("run", run_id=clock.run_id):
            return self._run(resume)

    def _run(self, resume: bool) -> LivenessResult:
        self._t0 = time.time()
        rid = self._clock.run_id
        self.tel = obs.as_telemetry(self._telemetry_arg, run_id=rid)
        self._run_id = self._clock.run_id = self.tel.run_id or rid
        self._resume_meta = {}
        self._snap = {"distinct_states": 0}
        self._fetch_n = 0
        # a fresh run() must not inherit a previous run's frame counts
        # (run_goal reuses this checker across properties)
        self._ckpt_frames = 0
        self._ckpt_bytes = 0
        self._ckpt_write_s = 0.0
        self._ckpt_retries = 0
        # per-run sweep work units (r14) — restart on resume, like the
        # engine work counters
        self._work_sweep = {
            "sort_lanes": 0, "prop_lanes": 0, "prop_passes": 0,
            "compact_elems": 0,
        }
        # what this run's sweep did and fetched (0 where the edge list
        # came from an earlier goal's run), and its analysis
        self._sweep_n = {
            "chunks": 0, "groups": 0, "query_lanes": 0, "d2h_bytes": 0,
        }
        self._peel_rounds = 0
        # a crash mid-frame-write can leave a dead tmp file behind
        ckpt.cleanup_stale_tmp(self.checkpoint_path)
        # crash breadcrumbs FIRST: fault events flush before the fault
        # fires (kill@sweep leaves no other trace)
        self._observer = (
            lambda kind, site, count: self.tel.emit(
                "fault", kind=kind, site=site, count=count
            )
        )
        faults.set_observer(self._observer)
        # the liveness heartbeat covers the SWEEP phase (started after
        # exploration, whose own engine heartbeats itself) — reporting
        # from _snap, which the chunk loop updates: zero extra syncs
        self._hb = (
            obs.Heartbeat(
                self.heartbeat_s, self._snap, telemetry=self.tel
            )
            if self.heartbeat_s
            else None
        )
        watcher = ckpt.PreemptionWatcher(
            enabled=bool(self.checkpoint_path), log=self._log
        )
        self._watcher = watcher
        try:
            with watcher:
                if resume:
                    if not self.checkpoint_path:
                        raise ValueError(
                            "resume requires checkpoint_path"
                        )
                    if not self._try_resume_sweep():
                        # the frame on disk is an exploration-phase
                        # one — resume the inner engine's BFS instead
                        self._resume_explore = True
                self._emit_header(resume)
                try:
                    lres = self._check()
                except _Preempted as p:
                    import os

                    # the promise must be honest: a preemption before
                    # the first frame landed is NOT resumable
                    has_frame = bool(self.checkpoint_path) and (
                        os.path.exists(self.checkpoint_path)
                    )
                    lres = LivenessResult(
                        False,
                        "preempted (SIGTERM/SIGINT) during the "
                        f"{p.phase} phase — "
                        + (
                            "a resumable frame is on disk; continue "
                            "with run(resume=True)"
                            if has_frame
                            else "no frame was written yet; the run "
                            "is NOT resumable"
                        ),
                        p.n,
                        truncated=True,
                        stop_reason="preempted",
                    )
                with self._clock.phase("result"):
                    self._emit_result(lres)
                return lres
        except BaseException as e:
            self.tel.emit("error", error=repr(e)[:300])
            raise
        finally:
            if self._hb is not None:
                self._hb.stop()
                self._hb = None
            faults.set_observer(None)
            self._observer = None
            self._watcher = None
            if obs.owns_stream(self._telemetry_arg):
                self.tel.close()
            self.tel = obs.NULL

    def _emit_result(self, lres: LivenessResult):
        if any(self._work_sweep.values()):
            # the sweep's per-stage work totals, machine-readable for
            # the attribution layer (r14)
            self.tel.emit(
                "attribution",
                stages={
                    f"sweep_{k}": int(v)
                    for k, v in self._work_sweep.items()
                },
            )
        # stats: the explorer's own (its phases, growth and fpset
        # counters, as its result event carries them) and on top the
        # liveness run's phases, which sum with host_unaccounted_s to
        # this run's wall, what the sweep and the analysis counted, and
        # the compile meter over the whole run (obs/spans.py)
        g = lres.graph or {}
        stats = dict(
            self._checker.last_stats,
            distinct_states=lres.distinct_states,
            sweep_chunks=self._sweep_n["chunks"],
            sweep_groups=self._sweep_n["groups"],
            sweep_edges=g.get("edges"),
            sweep_query_lanes=self._sweep_n["query_lanes"],
            sweep_sort_lanes=self._work_sweep["sort_lanes"],
            sweep_prop_lanes=self._work_sweep["prop_lanes"],
            sweep_d2h_bytes=self._sweep_n["d2h_bytes"],
            live_goal_states=g.get("goal_states"),
            live_dead_ends=g.get("dead_ends"),
            analyse_peel_rounds=self._peel_rounds,
            fp_collision_prob=lres.fp_collision_prob,
            **spans.compile_meter().since(self._jit0),
        )
        stats.update(self._clock.host_seconds(spans.LIVE_PHASES))
        # what the sweep's dispatch phase is made of (sweep_dispatch_*,
        # sweep_calls_by_phase); the explorer's own ride in ITS stats
        stats.update(self._clock.call_stats("sweep_dispatch"))
        self.tel.emit(
            "result",
            distinct_states=lres.distinct_states,
            diameter=self._diameter,
            wall_s=round(self._clock.elapsed(), 3),
            truncated=lres.truncated,
            stop_reason=lres.stop_reason,
            holds=None if lres.truncated else lres.holds,
            reason=lres.reason,
            goal=self.goal_name,
            fairness=self.fairness,
            ckpt_frames=self._ckpt_frames,
            ckpt_retries=self._ckpt_retries,
            **{
                f"work_sweep_{k}": int(v)
                for k, v in self._work_sweep.items()
                if v
            },
            graph=lres.graph,
            stats={
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in stats.items()
            },
        )

    def _emit_header(self, resume: bool):
        if not self.tel.enabled:
            return
        try:
            dev = str(jax.devices()[0])
        except Exception:  # noqa: BLE001 — headers must never kill a run
            dev = "unknown"
        f = dict(
            engine="liveness",
            device=dev,
            **obs.IMPL_FIELDS,
            config_sig=self._config_sig(),
            # REQUIRED since schema v8, a constant null
            profile_sig=None,
            hbm_budget=getattr(self._checker, "hbm_budget", None),
            # v10: tenant identity (None outside the daemon)
            tenant=getattr(self, "tenant", None),
            warm=getattr(self, "warm", None),
            # v15: distributed-trace identity (None outside the daemon)
            trace_id=getattr(self, "trace_id", None),
            # v11: workload class (two-phase liveness check)
            mode="liveness",
            wall_unix=round(time.time(), 3),
            goal=self.goal_name,
            fairness=self.fairness,
            n_devices=self.n_devices,
            sweep_chunk=self.SF,
            sweep_group=self._sweep_group_size(),
            resume=resume,
        )
        rm = self._resume_meta
        if resume and rm:
            if rm.get("run_id"):
                f["resume_of"] = rm["run_id"]
            if rm.get("frame_seq") is not None:
                f["resume_frame_seq"] = rm["frame_seq"]
        self.tel.emit("run_header", **f)

    def _check(self) -> LivenessResult:
        clock = self._clock
        with clock.phase("explore"):
            n, n_init = self._explore()
        if self._watcher is not None and self._watcher.requested:
            # preemption landed during/after exploration: the inner
            # engine already wrote its frame on the way out — exit
            # before starting a sweep nobody will read
            raise _Preempted(n, "explore")
        if self._hb is not None:
            self._snap["distinct_states"] = n
            self._hb.start()
        with clock.phase("live_goal"):
            cap = self._table_cap(n)
            rows = self._rows_padded(cap)
            with clock.upload("ptt_live_goal", 1):
                n_d = jnp.int32(n)
            with clock.call("ptt_live_goal"):
                goal_d = self._goal_jit(cap)(rows, n_d)
            goal = np.asarray(goal_d)[:n]
        cprob = self.keys.collision_prob(n)

        out_deg = None
        if self.fairness == "wf_next":
            # materialize the edge list (cached across goals)
            src, dst, out_deg = self._edges(n)
        with clock.phase("analyse"):
            if out_deg is None:
                lres = self._unfair(n, n_init, goal, cprob)
            else:
                lres = self._fair(
                    n, n_init, goal, src, dst, out_deg, cprob
                )
            lres.graph = self._graph_summary(n, goal, out_deg)
        return lres

    def _graph_summary(self, n, goal, out_deg) -> dict:
        """The graph the verdict was computed on: states, goal states
        and, where the edge sweep ran, ``<Next>_vars`` edges and dead
        ends (not-goal states with no state-changing successor), in all
        and by BFS level.  The sweep walks gids in discovery order, so a
        level is a gid range."""
        out = {
            "states": int(n), "levels": int(self._diameter),
            "goal_states": int(goal.sum()),
            "edges": None, "dead_ends": None, "by_level": None,
        }
        cols = {"goal": goal.astype(np.int64)}
        if out_deg is not None:
            dead = (out_deg == 0) & ~goal
            out.update(
                edges=int(out_deg.sum()), dead_ends=int(dead.sum())
            )
            cols.update(edges=out_deg, dead_ends=dead.astype(np.int64))
        sizes = self._level_sizes
        if sizes and sum(sizes) == n:
            at = np.cumsum([0] + list(sizes[:-1]))
            out["by_level"] = dict(
                {k: np.add.reduceat(v, at).tolist() for k, v in cols.items()},
                size=[int(x) for x in sizes],
            )
        return out

    @staticmethod
    def _unfair(n, n_init, goal, cprob) -> LivenessResult:
        bad = np.nonzero(~goal[:n_init])[0]
        if len(bad):
            return LivenessResult(
                False,
                "stuttering counterexample: initial state "
                f"#{int(bad[0])} may stutter forever without reaching "
                "the goal (no fairness assumed)",
                n,
                lasso_prefix=[int(bad[0])],
                lasso_cycle=[int(bad[0])],
                fp_collision_prob=cprob,
            )
        return LivenessResult(
            True, "every initial state satisfies the goal", n,
            fp_collision_prob=cprob,
        )

    def _fair(
        self, n, n_init, goal, src, dst, out_deg, cprob
    ) -> LivenessResult:
        """The fair-cycle search under ``WF_vars(Next)`` on the host."""
        # restrict to not-goal -> not-goal edges; CSR over sources
        keep = ~goal[src] & ~goal[dst]
        rsrc, rdst = src[keep], dst[keep]
        order_adj = np.argsort(rsrc, kind="stable")
        rsrc, rdst = rsrc[order_adj], rdst[order_adj]
        starts = np.searchsorted(rsrc, np.arange(n + 1))

        # reach R from not-goal initial states: vectorized BFS sweeps
        # (the round-3 python-loop DFS was the scale limit)
        in_r = np.zeros((n,), bool)
        parent = np.full((n,), -1, np.int64)
        frontier = np.nonzero(~goal[:n_init])[0]
        in_r[frontier] = True
        while len(frontier):
            # all out-edges of the frontier, via CSR ranges
            cnt = starts[frontier + 1] - starts[frontier]
            total = int(cnt.sum())
            if total == 0:
                break
            base = np.repeat(starts[frontier], cnt)
            offs = np.arange(total) - np.repeat(
                np.cumsum(cnt) - cnt, cnt
            )
            eidx = base + offs
            vs = rdst[eidx]
            us = rsrc[eidx]
            fresh = ~in_r[vs]
            if not fresh.any():
                break
            vf = vs[fresh]
            uf = us[fresh]
            # first writer wins is irrelevant — any parent is a valid
            # predecessor for the lasso prefix
            parent[vf] = uf
            in_r[vf] = True
            frontier = np.unique(vf)
        r_nodes = np.nonzero(in_r)[0]
        if len(r_nodes) == 0:
            return LivenessResult(
                True, "all fair behaviors reach the goal", n,
                fp_collision_prob=cprob,
            )
        dead = r_nodes[out_deg[r_nodes] == 0]
        if len(dead):
            g = int(dead[0])
            return LivenessResult(
                False,
                "fair stuttering at a not-goal state with no var-changing "
                "successor",
                n,
                lasso_prefix=self._path_to(parent, g, n_init),
                lasso_cycle=[g],
                fp_collision_prob=cprob,
            )
        # Kahn peel within R — wave-vectorized
        indeg = np.zeros((n,), np.int64)
        both = in_r[rsrc] & in_r[rdst]
        np.add.at(indeg, rdst[both], 1)
        alive = in_r.copy()
        wave = r_nodes[indeg[r_nodes] == 0]
        while len(wave):
            self._peel_rounds += 1
            alive[wave] = False
            cnt = starts[wave + 1] - starts[wave]
            total = int(cnt.sum())
            if total == 0:
                break
            base = np.repeat(starts[wave], cnt)
            offs = np.arange(total) - np.repeat(
                np.cumsum(cnt) - cnt, cnt
            )
            vs = rdst[base + offs]
            am = alive[vs]
            np.subtract.at(indeg, vs[am], 1)
            cand = np.unique(vs[am])
            wave = cand[(indeg[cand] == 0) & alive[cand]]
        cyc_nodes = np.nonzero(alive)[0]
        if len(cyc_nodes):
            # Kahn peeling (in-degree) can leave acyclic tail nodes that
            # dangle off a cycle; one backward Kahn pass on OUT-degree
            # (via the reverse adjacency) removes them so every
            # surviving node has an alive successor and the
            # cycle-recovery walk is total.
            both = alive[rsrc] & alive[rdst]
            odeg = np.zeros((n,), np.int64)
            np.add.at(odeg, rsrc[both], 1)
            rorder = np.argsort(rdst, kind="stable")
            bsrc, bdst = rsrc[rorder], rdst[rorder]
            bstarts = np.searchsorted(bdst, np.arange(n + 1))
            wave = cyc_nodes[odeg[cyc_nodes] == 0]
            while len(wave):
                self._peel_rounds += 1
                alive[wave] = False
                cnt = bstarts[wave + 1] - bstarts[wave]
                total = int(cnt.sum())
                if total == 0:
                    break
                base = np.repeat(bstarts[wave], cnt)
                offs = np.arange(total) - np.repeat(
                    np.cumsum(cnt) - cnt, cnt
                )
                ps = bsrc[base + offs]
                am = alive[ps]
                np.subtract.at(odeg, ps[am], 1)
                cand = np.unique(ps[am])
                wave = cand[(odeg[cand] == 0) & alive[cand]]
            cyc_nodes = np.nonzero(alive)[0]
        if len(cyc_nodes):
            # recover one cycle: walk alive-successors until a repeat
            u = int(cyc_nodes[0])
            seen_at = {}
            walk = []
            while u not in seen_at:
                seen_at[u] = len(walk)
                walk.append(u)
                nxt = [
                    int(v)
                    for v in rdst[starts[u]: starts[u + 1]]
                    if alive[v]
                ]
                u = nxt[0]
            cycle = walk[seen_at[u]:]
            return LivenessResult(
                False,
                "cycle of not-goal states is fairly traversable",
                n,
                lasso_prefix=self._path_to(parent, cycle[0], n_init),
                lasso_cycle=cycle,
                fp_collision_prob=cprob,
            )
        return LivenessResult(
            True, "all fair behaviors reach the goal", n,
            fp_collision_prob=cprob,
        )

    @staticmethod
    def _path_to(parent, g, n_init) -> List[int]:
        path = [g]
        while path[-1] >= n_init and parent[path[-1]] >= 0:
            path.append(int(parent[path[-1]]))
        return list(reversed(path))
