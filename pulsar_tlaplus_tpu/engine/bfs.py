"""Single-chip BFS model-checking engine (SURVEY.md §7-L2).

The implied-TLC engine (SURVEY.md §1-L1) re-architected for XLA:

- the frontier is a padded ``uint32[F, W]`` array of packed states;
- one jitted *expand* step per frontier chunk runs vmapped successor
  generation (all ``Next`` lanes at once), packs, fingerprints, sorts,
  binary-searches the visited set, compacts the new states to the front,
  merges them into the sorted visited set, and evaluates the selected
  invariants on exactly the new states — all on device;
- the host driver only orchestrates chunks/levels, tracks global state ids
  and the ``(parent, action)`` log for counterexample reconstruction
  (SURVEY.md §2.2-E7), and makes the termination decision (one scalar sync
  per chunk, mirroring the per-level host boundary in SURVEY.md §3.3).

Within-level cross-chunk duplicates need no extra pass: each chunk's new
states are merged into the visited set before the next chunk's lookup, and
every state discovered in level N is at BFS depth N regardless of which
chunk emitted it, so shortest-counterexample semantics are preserved.

Deadlock checking follows TLC's default-on behavior: a state deadlocks iff
no ``Next`` disjunct — including the stuttering Consumer/Terminating lanes
(compaction.tla:185-186, 205-214) — is enabled.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.engine.core import (
    build_trace,
    dedup_core,
    dedup_core_hash,
)
from pulsar_tlaplus_tpu.engine.statelog import FileLog, MemoryLog
from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.ops import hashtable
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL
from pulsar_tlaplus_tpu.ref import pyeval


@dataclass
class CheckerResult:
    distinct_states: int
    diameter: int  # BFS levels; initial states = level 1 (matches oracle)
    violation: Optional[str] = None
    trace: Optional[list] = None  # list[pyeval.State]
    trace_actions: Optional[list] = None  # action names along the trace
    deadlock: bool = False
    states_per_sec: float = 0.0
    wall_s: float = 0.0
    level_sizes: List[int] = field(default_factory=list)
    truncated: bool = False  # stopped by time/state budget, not exhaustion
    # why a truncated run stopped: "max_states" | "time_budget" | "hbm"
    # | "row_window" (frontier-window rows exhausted at a completed
    # level) | "preempted" (SIGTERM/SIGINT requested a resumable stop)
    # | None for non-truncated runs or engines predating this
    stop_reason: Optional[str] = None
    # how many times the run recovered from HBM exhaustion by
    # rebuilding device state from the last checkpoint frame and
    # continuing at degraded capacity (device engine)
    hbm_recovered: int = 0
    # gid of the violating/deadlocked state (engine-local numbering) —
    # lets differential tests pin interrupted+resumed runs to the
    # uninterrupted run's exact discovery order, not just its verdict
    violation_gid: Optional[int] = None
    # expected fingerprint collisions at this state count (birthday
    # bound); 0.0 when dedup keys are exact.  TLC prints the analogous
    # "calculated (optimistic) probability" after every run.
    fp_collision_prob: float = 0.0


class Checker:
    """BFS checker for a compiled spec model on a single device."""

    def __init__(
        self,
        model,
        invariants: Optional[Tuple[str, ...]] = None,
        check_deadlock: bool = True,
        frontier_chunk: int = 4096,
        visited_cap: int = 1 << 13,
        max_states: int = 200_000_000,
        time_budget_s: Optional[float] = None,
        progress: bool = False,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 5,
        metrics_path: Optional[str] = None,
        keep_log: bool = False,
        state_log_path: Optional[str] = None,
        dedup: str = "hash",
        telemetry=None,
        heartbeat_s: Optional[float] = None,
    ):
        if dedup not in ("hash", "sort"):
            raise ValueError(f"dedup must be 'hash' or 'sort': {dedup}")
        if dedup == "hash" and visited_cap & (visited_cap - 1):
            raise ValueError(
                f"hash dedup needs a power-of-two visited_cap: {visited_cap}"
            )
        self.dedup_mode = dedup
        self.model = model
        self.layout = model.layout
        if invariants is None:
            invariants = getattr(
                model, "default_invariants", pyeval.DEFAULT_INVARIANTS
            )
        self.invariant_names = tuple(invariants)
        self.check_deadlock = check_deadlock
        self.F = frontier_chunk
        self.max_states = max_states
        self.time_budget_s = time_budget_s
        self.progress = progress
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.metrics_path = metrics_path
        self.keep_log = keep_log
        # disk-backed state log (native C++ store) for runs beyond host RAM
        self.state_log_path = state_log_path
        self.last_run_state: Optional[_RunState] = None
        self._cap = visited_cap
        self._jit_cache: Dict[Tuple[str, int], object] = {}
        self._unpack1 = jax.jit(self.layout.unpack)
        # unified telemetry (round 8): JSONL stream + progress heartbeat
        self._telemetry_arg = telemetry
        self.tel = obs.NULL
        self.heartbeat_s = heartbeat_s
        self._run_id: Optional[str] = None
        self._snap: Dict[str, object] = {}
        self._resume_meta: Dict[str, object] = {}
        self._ckpt_frames = 0
        self._ckpt_retries = 0
        self._ckpt_bytes = 0
        self._ckpt_write_s = 0.0

    # ------------------------------------------------------------------
    # jitted steps (cached per visited capacity tier)
    # ------------------------------------------------------------------

    def _parse_out(self, out):
        """Step output -> (packed, parent, action, n_new, vk, viol,
        n_failed, tail) uniformly across dedup modes."""
        if self.dedup_mode == "hash":
            packed, parent, action, n_new = out[:4]
            vk, viol, n_failed = out[4:8], out[8], int(out[9])
            tail = out[10:]
        else:
            packed, parent, action, n_new = out[:4]
            vk, viol, n_failed = out[4:7], out[7], 0
            tail = out[8:]
        if n_failed:
            raise RuntimeError(
                "hash-table probe overflow — raise visited_cap "
                f"({n_failed} unresolved lanes at capacity {self._cap})"
            )
        return packed, parent, action, n_new, vk, viol, tail

    def _get_step(self, kind: str):
        key = (kind, self._cap)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        m = self.model
        is_hash = self.dedup_mode == "hash"

        def core(packed, valid, parent, action, vk, n_visited):
            if is_hash:
                return dedup_core_hash(
                    m, self.invariant_names, packed, valid, parent, action,
                    *vk,
                )
            return dedup_core(
                m, self.invariant_names, packed, valid, parent, action,
                *vk, n_visited,
            )

        if kind == "insert":

            def step(packed, valid, *rest):
                vk, n_visited = rest[:-1], rest[-1]
                n = packed.shape[0]
                parent = jnp.full((n,), -1, jnp.int32)
                action = jnp.full((n,), -1, jnp.int32)
                return core(packed, valid, parent, action, vk, n_visited)

        else:

            def step(frontier, n, *rest):
                vk, n_visited = rest[:-1], rest[-1]
                f = frontier.shape[0]
                row_live = jnp.arange(f, dtype=jnp.int32) < n
                states = jax.vmap(self.layout.unpack)(frontier)
                succ, valid = jax.vmap(m.successors)(states)  # [F, A]
                valid = valid & row_live[:, None]
                packed = jax.vmap(jax.vmap(self.layout.pack))(succ)
                fa = f * m.A
                packed = packed.reshape(fa, self.layout.W)
                parent = jnp.repeat(jnp.arange(f, dtype=jnp.int32), m.A)
                action = jnp.tile(jnp.asarray(m.action_ids), f)
                out = core(
                    packed, valid.reshape(fa), parent, action, vk, n_visited
                )
                if self.check_deadlock:
                    stutter = jax.vmap(m.stutter_enabled)(states)
                    dead = row_live & ~jnp.any(valid, axis=1) & ~stutter
                    dead_idx = jnp.min(
                        jnp.where(dead, jnp.arange(f, dtype=jnp.int32), f)
                    )
                else:
                    dead_idx = jnp.int32(f)
                return out + (dead_idx,)

        fn = jax.jit(step)
        self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    # host driver
    # ------------------------------------------------------------------

    def _grow_visited(self, vk, need: int):
        """Ensure capacity for ``need`` total entries.

        Sorted mode: columns must hold every entry (cap >= need).  Hash
        mode: keep load factor <= 1/2 (cap >= 2 * need) and rehash the
        occupied entries into the bigger table."""
        cap = self._cap
        target = 2 * need if self.dedup_mode == "hash" else need
        while cap < target:
            cap *= 4
        if cap == self._cap:
            return vk
        if self.dedup_mode == "hash":
            vk = hashtable.rehash_into(vk, hashtable.empty_table(cap))
        else:
            pad = cap - self._cap
            vk = tuple(
                jnp.concatenate([col, jnp.full((pad,), SENTINEL, jnp.uint32)])
                for col in vk
            )
        self._cap = cap
        return vk

    def _config_sig(self) -> str:
        model_sig = getattr(self.model, "config_sig", None) or repr(
            getattr(self.model, "c", None)
        )
        return repr(
            (
                model_sig,
                self.invariant_names,
                self.layout.total_bits,
                self.dedup_mode,
            )
        )

    def _save_checkpoint(self, rs):
        """Snapshot the full checker state (SURVEY.md §2.2-E8): sorted
        visited keys + frontier + trace log; resume continues BFS.  With a
        disk-backed state log only the (path, count) pair is recorded — the
        log file itself is the durable storage.  The atomic frame writer
        is shared with the device engines (utils/ckpt.py)."""
        from pulsar_tlaplus_tpu.utils import ckpt

        t_stall = time.perf_counter()
        log = rs.log
        if isinstance(log, FileLog):
            log.sync()
            log_arrays = dict(
                log_path=np.frombuffer(log.path.encode(), dtype=np.uint8),
                log_len=np.int64(len(log)),
            )
        else:
            log_arrays = dict(
                packed=log.packed_matrix(),
                parent=log.parents(),
                action=log.actions(),
            )
        nbytes, write_s, retries = ckpt.save_frame(
            self.checkpoint_path,
            self._config_sig(),
            dict(
                {
                    f"vk{i}": np.asarray(col)
                    for i, col in enumerate(rs.vk)
                },
                n_visited=np.int64(rs.n_visited),
                level_sizes=np.asarray(rs.level_sizes, np.int64),
                frontier=rs.frontier,
                frontier_gids=rs.frontier_gids,
                **log_arrays,
            ),
            wall_s=time.time() - rs.t0,
            meta={
                "run_id": self._run_id,
                "frame_seq": self._ckpt_frames + 1,
                "level": len(rs.level_sizes),
                "engine": "bfs_host",
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
            level=len(rs.level_sizes),
            distinct_states=rs.n_total,
        )

    def load_checkpoint(self):
        """Load a checkpoint dict (validates the config signature)."""
        from pulsar_tlaplus_tpu.utils import ckpt

        return ckpt.load_frame(
            self.checkpoint_path,
            self._config_sig(),
            what="model configuration",
        )

    def run(self, resume: bool = False) -> CheckerResult:
        rid = obs.new_run_id()
        self.tel = obs.as_telemetry(self._telemetry_arg, run_id=rid)
        self._run_id = self.tel.run_id or rid
        self._snap = {"distinct_states": 0}
        self._resume_meta = {}
        self._ckpt_frames = 0
        self._ckpt_retries = 0
        self._ckpt_bytes = 0
        self._ckpt_write_s = 0.0
        # a crash mid-frame-write can leave a dead tmp file behind
        from pulsar_tlaplus_tpu.utils import ckpt

        ckpt.cleanup_stale_tmp(self.checkpoint_path)
        hb = None
        if self.heartbeat_s:
            hb = obs.Heartbeat(
                self.heartbeat_s, self._snap, telemetry=self.tel,
                capacity=self.max_states,
            )
        try:
            if hb is not None:
                hb.start()
            return self._run_impl(resume)
        except BaseException as e:
            self.tel.emit("error", error=repr(e)[:300])
            raise
        finally:
            if hb is not None:
                hb.stop()
            if obs.owns_stream(self._telemetry_arg):
                self.tel.close()
            self.tel = obs.NULL

    def _emit_header(self, resume: bool):
        if not self.tel.enabled:
            return
        try:
            dev = str(jax.devices()[0])
        except Exception:  # noqa: BLE001 — headers must never kill a run
            dev = "unknown"
        f = dict(
            engine="bfs_host",
            device=dev,
            visited_impl=self.dedup_mode,
            config_sig=self._config_sig(),
            # REQUIRED since schema v8, a constant null
            profile_sig=None,
            hbm_budget=None,
            # v10: tenant identity (None outside the daemon)
            tenant=getattr(self, "tenant", None),
            warm=getattr(self, "warm", None),
            # v15: distributed-trace identity (fleet dispatcher ->
            # backend -> engine; None outside the daemon)
            trace_id=getattr(self, "trace_id", None),
            # v16: the kernel fields of the device engines' headers
            # (obs/telemetry.py IMPL_FIELDS) — null here
            probe_impl=None,
            expand_impl=None,
            sieve_impl=None,
            # v11: workload class (exhaustive BFS)
            mode="check",
            wall_unix=round(time.time(), 3),
            max_states=self.max_states,
            invariants=list(self.invariant_names),
            resume=resume,
        )
        rm = self._resume_meta
        if resume and rm:
            if rm.get("run_id"):
                f["resume_of"] = rm["run_id"]
            if rm.get("frame_seq") is not None:
                f["resume_frame_seq"] = rm["frame_seq"]
        self.tel.emit("run_header", **f)

    def _run_impl(self, resume: bool = False) -> CheckerResult:
        rs = _RunState()
        rs.t0 = time.time()
        if resume:
            from pulsar_tlaplus_tpu.utils import ckpt

            d = self.load_checkpoint()
            self._resume_meta = ckpt.frame_meta(d)
            if "wall_s" in d:
                # carry cumulative wall time across resume so wall_s /
                # states_per_sec stay meaningful for the whole run
                rs.t0 = time.time() - float(d["wall_s"])
            ncols = 4 if self.dedup_mode == "hash" else 3
            self._cap = len(d["vk0"]) - (1 if self.dedup_mode == "hash" else 0)
            rs.vk = tuple(
                jnp.asarray(d[f"vk{i}"]) for i in range(ncols)
            )
            rs.n_visited = int(d["n_visited"])
            if "log_path" in d:
                path = d["log_path"].tobytes().decode()
                rs.log = FileLog(path, self.layout.W)
                if len(rs.log) < int(d["log_len"]):
                    raise ValueError("state log shorter than checkpoint records")
                rs.log.truncate(int(d["log_len"]))
            else:
                rs.log = MemoryLog(self.layout.W)
                if len(d["packed"]):
                    rs.log.append(d["packed"], d["parent"], d["action"])
            rs.n_total = rs.n_visited
            rs.level_sizes = [int(x) for x in d["level_sizes"]]
            rs.frontier = d["frontier"]
            rs.frontier_gids = d["frontier_gids"]
            self._log(
                rs,
                f"resumed at level {len(rs.level_sizes)}: "
                f"{rs.n_total} states, frontier {len(rs.frontier)}",
            )
            self._rewind_metrics(len(rs.level_sizes))
            self._emit_header(resume=True)
            return self._bfs_loop(rs)
        self._emit_header(resume=False)
        if self.dedup_mode == "hash":
            rs.vk = hashtable.empty_table(self._cap)
        else:
            rs.vk = tuple(
                jnp.full((self._cap,), SENTINEL, jnp.uint32) for _ in range(3)
            )
        rs.log = (
            FileLog(self.state_log_path, self.layout.W, fresh=True)
            if self.state_log_path
            else MemoryLog(self.layout.W)
        )
        res = self._insert_initial(rs)
        if res is not None:
            return res
        return self._bfs_loop(rs)

    def _log(self, rs, msg):
        if self.progress:
            import sys

            print(f"  {msg}", file=sys.stderr, flush=True)

    def _flush_chunk(self, rs, parsed, frontier_gids, base_row):
        """Copy a step's new states to the state log; returns
        (n_new, violation, packed rows of the new states)."""
        packed, parent, action, n_new, _vk, viol, _tail = parsed
        n_new = int(n_new)
        np_packed = None
        if n_new:
            np_packed = np.asarray(packed[:n_new])
            np_parent = np.asarray(parent[:n_new])
            np_action = np.asarray(action[:n_new])
            if frontier_gids is None:
                gids = np.full((n_new,), -1, np.int64)
            else:
                gids = frontier_gids[base_row + np_parent]
            rs.log.append(np_packed, gids, np_action)
        violation = None
        viol = np.asarray(viol)
        for i, name in enumerate(self.invariant_names):
            if int(viol[i]) < n_new:
                violation = (name, rs.n_total + int(viol[i]))
                break
        rs.n_total += n_new
        rs.n_visited += n_new
        return n_new, violation, np_packed

    def _rewind_metrics(self, resumed_level: int):
        """Drop metrics records for levels the resumed run will re-discover
        (the aborted run may have progressed past the last checkpoint)."""
        import json
        import os

        if not self.metrics_path or not os.path.exists(self.metrics_path):
            return
        kept = []
        with open(self.metrics_path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("level", 0) <= resumed_level:
                    kept.append(line)
        kept.append(json.dumps({"resumed_at_level": resumed_level}) + "\n")
        with open(self.metrics_path, "w") as f:
            f.writelines(kept)

    def _emit_metrics(self, rs, level_count):
        """Structured observability (SURVEY.md §5): one JSONL record per BFS
        level, mirroring TLC's progress lines (states/sec, queue depth).
        ``frontier`` is the queue depth at level start (states expanded);
        ``new_states`` is the discovery count (= next level's depth)."""
        wall = time.time() - rs.t0
        self._snap.update(
            level=len(rs.level_sizes),
            frontier=int(len(rs.frontier)),
            distinct_states=rs.n_total,
        )
        self.tel.emit(
            "level",
            level=len(rs.level_sizes),
            new_states=int(level_count),
            distinct_states=rs.n_total,
            frontier=int(len(rs.frontier)),
            wall_s=round(wall, 3),
            states_per_sec=round(rs.n_total / max(wall, 1e-9), 1),
        )
        if not self.metrics_path:
            return
        import json
        with open(self.metrics_path, "a") as f:
            f.write(
                json.dumps(
                    {
                        "level": len(rs.level_sizes),
                        "new_states": level_count,
                        "distinct_states": rs.n_total,
                        "frontier": int(len(rs.frontier)),  # pre-swap: expanded
                        "wall_s": round(wall, 3),
                        "states_per_sec": round(rs.n_total / max(wall, 1e-9), 1),
                        "visited_cap": self._cap,
                    }
                )
                + "\n"
            )

    def _build_result(
        self, rs, violation, deadlock_gid=None, deadlock=False, truncated=False
    ):
        if self.keep_log:
            self.last_run_state = rs
        wall = time.time() - rs.t0
        res = CheckerResult(
            distinct_states=rs.n_total,
            diameter=len(rs.level_sizes),
            deadlock=deadlock,
            wall_s=wall,
            states_per_sec=rs.n_total / max(wall, 1e-9),
            level_sizes=rs.level_sizes,
            truncated=truncated,
        )
        gid = None
        if violation is not None:
            res.violation = violation[0]
            gid = violation[1]
        elif deadlock:
            res.violation = "Deadlock"
            gid = deadlock_gid
        if gid is not None:
            res.violation_gid = gid
            res.trace, res.trace_actions = build_trace(
                self.model, self._unpack1, gid, rs.log
            )
        self.tel.emit(
            "result",
            distinct_states=rs.n_total,
            diameter=len(rs.level_sizes),
            wall_s=round(wall, 3),
            states_per_sec=round(rs.n_total / max(wall, 1e-9), 1),
            truncated=truncated,
            stop_reason=res.stop_reason,
            violation=res.violation,
            violation_gid=res.violation_gid,
            deadlock=res.deadlock,
            level_sizes=[int(x) for x in rs.level_sizes],
            stats={
                "ckpt_frames": self._ckpt_frames,
                "ckpt_bytes": self._ckpt_bytes,
                "ckpt_write_s": round(self._ckpt_write_s, 3),
                "ckpt_retries": self._ckpt_retries,
                "visited_cap": self._cap,
            },
        )
        return res

    def _insert_initial(self, rs) -> Optional[CheckerResult]:
        """Level 1: enumerate and insert Init states (compaction.tla:188-202).

        Returns a result only on an invariant violation in an initial state.
        """
        m = self.model
        n_init = m.n_initial
        gen = jax.jit(jax.vmap(lambda i: self.layout.pack(m.gen_initial(i))))
        for start in range(0, n_init, self.F):
            idx = jnp.arange(start, start + self.F, dtype=jnp.int32)
            packed = gen(idx)
            valid = np.arange(start, start + self.F) < n_init
            rs.vk = self._grow_visited(rs.vk, rs.n_visited + self.F + 1)
            out = self._get_step("insert")(
                packed, jnp.asarray(valid), *rs.vk, jnp.int32(rs.n_visited)
            )
            parsed = self._parse_out(out)
            rs.vk = parsed[4]
            _n_new, violation, _np_new = self._flush_chunk(rs, parsed, None, 0)
            if violation is not None:
                rs.level_sizes.append(rs.n_total)
                return self._build_result(rs, violation)
        rs.level_sizes.append(rs.n_total)
        rs.frontier = rs.log.packed_matrix()
        rs.frontier_gids = np.arange(rs.n_total, dtype=np.int64)
        return None

    def _bfs_loop(self, rs) -> CheckerResult:
        m = self.model
        while len(rs.frontier):
            level_new_packed: List[np.ndarray] = []
            level_base = rs.n_total
            frontier, frontier_gids = rs.frontier, rs.frontier_gids
            for start in range(0, len(frontier), self.F):
                chunk = frontier[start : start + self.F]
                nc = len(chunk)
                if nc < self.F:
                    chunk = np.concatenate(
                        [chunk, np.zeros((self.F - nc, self.layout.W), np.uint32)]
                    )
                rs.vk = self._grow_visited(
                    rs.vk, rs.n_visited + self.F * m.A + 1
                )
                out = self._get_step("expand")(
                    jnp.asarray(chunk), jnp.int32(nc), *rs.vk,
                    jnp.int32(rs.n_visited),
                )
                parsed = self._parse_out(out)
                rs.vk = parsed[4]
                dead_idx = int(parsed[6][0])
                n_new, violation, np_new = self._flush_chunk(
                    rs, parsed, frontier_gids, start
                )
                if n_new:
                    level_new_packed.append(np_new)
                if violation is not None:
                    rs.level_sizes.append(rs.n_total - level_base)
                    return self._build_result(rs, violation)
                if dead_idx < nc:
                    rs.level_sizes.append(rs.n_total - level_base)
                    return self._build_result(
                        rs,
                        None,
                        deadlock_gid=int(frontier_gids[start + dead_idx]),
                        deadlock=True,
                    )
                if self._over_budget(rs) and self.checkpoint_path is None:
                    # no checkpoint configured: stop immediately (bench mode)
                    rs.level_sizes.append(rs.n_total - level_base)
                    return self._build_result(rs, None, truncated=True)
            level_count = rs.n_total - level_base
            if level_count == 0:
                break
            rs.level_sizes.append(level_count)
            wall = time.time() - rs.t0
            self._log(
                rs,
                f"level {len(rs.level_sizes)}: +{level_count} "
                f"(total {rs.n_total}, {rs.n_total/max(wall,1e-9):.0f} st/s)",
            )
            self._emit_metrics(rs, level_count)
            rs.frontier = np.concatenate(level_new_packed)
            rs.frontier_gids = np.arange(level_base, rs.n_total, dtype=np.int64)
            over = self._over_budget(rs)
            if self.checkpoint_path and (
                over or len(rs.level_sizes) % self.checkpoint_every == 0
            ):
                # level boundaries are the consistent snapshot points: the
                # frontier is exactly the set of unexpanded states
                self._save_checkpoint(rs)
            if over:
                return self._build_result(rs, None, truncated=True)
        return self._build_result(rs, None)

    def _over_budget(self, rs) -> bool:
        return rs.n_visited > self.max_states or (
            self.time_budget_s is not None
            and time.time() - rs.t0 > self.time_budget_s
        )


class _RunState:
    """Mutable per-run state of the checker (checkpointable)."""

    def __init__(self):
        self.t0 = 0.0
        self.vk = None
        self.n_visited = 0
        self.log = None  # MemoryLog | FileLog
        self.n_total = 0
        self.level_sizes: List[int] = []
        self.frontier = None
        self.frontier_gids = None
