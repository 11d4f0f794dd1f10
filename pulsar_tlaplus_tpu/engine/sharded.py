"""Mesh-sharded BFS checker (SURVEY.md §7-L3, §2.2-E3/E6/E11).

TLC's worker threads + shared FPSet become, TPU-natively:

- **frontier data-parallelism**: each device expands its own frontier shard
  with the same vmapped successor/invariant kernels (the DP analog);
- **fingerprint-space sharding**: the visited set is partitioned by
  ``key % n_shards``; every candidate successor is routed to its owning
  device with one ``all_to_all`` over the mesh axis (ICI within a slice,
  DCN across slices), then deduped locally with the exact same
  ``dedup_core`` as the single-chip engine (the TP analog);
- newly discovered states *stay on their owner* and form that device's
  next-level frontier shard — hash ownership doubles as load balancing, so
  no rebalancing pass is needed.

Determinism: for any device count, the reachable state set, counts, levels,
and invariant verdicts are identical (tested over a virtual CPU mesh with
n in {1, 2, 4, 8}); only which shortest counterexample gets reported may
vary, as with TLC's ``-workers N``.

Routing buffers are provably overflow-free: each sender contributes at most
its own lane count to any one destination, so per-destination capacity =
the sender's lane count suffices.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from pulsar_tlaplus_tpu.engine.bfs import CheckerResult
from pulsar_tlaplus_tpu.engine.core import (
    build_trace,
    dedup_core,
    dedup_core_hash,
)
from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.ops import dedup, hashtable
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL
from pulsar_tlaplus_tpu.parallel.mesh import make_mesh
from pulsar_tlaplus_tpu.ref import pyeval


class ShardedChecker:
    """BFS checker sharded over a device mesh.

    A 1-D ``("shard",)`` mesh routes candidates straight to their
    key-owner chip with one ``all_to_all``.  A 2-D ``("dcn", "ici")``
    mesh (``parallel.mesh.make_mesh2d``) routes hierarchically:
    owner-slice first over the dcn axis (aggregating all cross-slice
    traffic into one collective per level round), then owner-chip over
    ici — so cross-slice bandwidth carries each candidate exactly once.
    Owner shard = ``key % n_shards`` either way, so counts are
    identical across mesh shapes (tested 1/2/4/8 flat and 2x4)."""

    def __init__(
        self,
        model,
        n_devices: int | None = None,
        invariants: Optional[Tuple[str, ...]] = None,
        check_deadlock: bool = True,
        frontier_chunk: int = 1024,
        visited_cap: int = 1 << 13,
        max_states: int = 1_000_000_000,
        mesh=None,
        dedup_mode: str = "sort",
        time_budget_s: Optional[float] = None,
        metrics_path: Optional[str] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 5,
        telemetry=None,
        heartbeat_s: Optional[float] = None,
    ):
        if dedup_mode not in ("sort", "hash"):
            raise ValueError(
                f"dedup_mode must be 'sort' or 'hash', got {dedup_mode!r}"
            )
        if dedup_mode == "hash" and visited_cap & (visited_cap - 1):
            raise ValueError("hash dedup needs a power-of-two visited_cap")
        self.dedup_mode = dedup_mode
        self.model = model
        self.layout = model.layout
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.axes = tuple(self.mesh.axis_names)
        self.n_shards = self.mesh.devices.size
        if invariants is None:
            invariants = getattr(
                model, "default_invariants", pyeval.DEFAULT_INVARIANTS
            )
        self.invariant_names = tuple(invariants)
        self.check_deadlock = check_deadlock
        self.F = frontier_chunk
        if max_states >= 2**31:
            # gids travel to the device as int32 (routed with each candidate
            # lane); >2^31 states needs a two-word gid encoding (future work)
            raise ValueError("sharded checker supports max_states < 2**31")
        self.max_states = max_states
        self.time_budget_s = time_budget_s
        self.metrics_path = metrics_path
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self._cap = visited_cap
        self._ncols = 4 if dedup_mode == "hash" else 3
        self._viol_i = 4 + self._ncols
        self._dead_i = self._viol_i + (2 if dedup_mode == "hash" else 1)
        self._jit_cache: Dict[Tuple[str, int], object] = {}
        self._unpack1 = jax.jit(self.layout.unpack)
        # unified telemetry (round 8)
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
    # device code
    # ------------------------------------------------------------------

    def _bucket(self, dest, valid, arrays, n_dest: int):
        """Sort lanes by destination, scatter into dense ``[n_dest * L]``
        send buffers (invalid lanes dropped).  Returns (valid', arrays')."""
        L = dest.shape[0]
        d = jnp.where(valid, dest, n_dest)
        iota = jnp.arange(L, dtype=jnp.uint32)
        sd, perm_u = jax.lax.sort(
            (d.astype(jnp.uint32), iota), num_keys=1, is_stable=True
        )
        perm = perm_u.astype(jnp.int32)
        sv = valid[perm]
        starts = jnp.searchsorted(
            sd, jnp.arange(n_dest + 1, dtype=jnp.uint32)
        ).astype(jnp.int32)
        pos = jnp.arange(L, dtype=jnp.int32) - starts[
            jnp.clip(sd.astype(jnp.int32), 0, n_dest)
        ]
        flat = jnp.where(sv, sd.astype(jnp.int32) * L + pos, n_dest * L)
        outs = []
        for a in arrays:
            sa = a[perm]
            z = jnp.zeros((n_dest * L,) + a.shape[1:], a.dtype)
            outs.append(z.at[flat].set(sa, mode="drop"))
        sv_out = (
            jnp.zeros((n_dest * L,), jnp.bool_).at[flat].set(sv, mode="drop")
        )
        return sv_out, outs

    @staticmethod
    def _a2a(x, axis_name, rows: int):
        L = x.shape[0] // rows
        return jax.lax.all_to_all(
            x.reshape((rows, L) + x.shape[1:]), axis_name, 0, 0
        ).reshape((rows * L,) + x.shape[1:])

    def _route(self, packed, valid, parent, action):
        """Route candidate lanes to their key-owner shard.

        1-D mesh: one ``all_to_all`` over the shard axis.  2-D mesh:
        hierarchical — owner-slice over the dcn axis first (cross-slice
        bandwidth carries each lane once), then owner-chip over ici.
        """
        nd = self.n_shards
        k1, _, _ = dedup.make_keys(packed, self.layout.total_bits)
        owner = (k1 % nd).astype(jnp.int32)
        if len(self.axes) == 1:
            v, (p, par, act) = self._bucket(
                owner, valid, (packed, parent, action), nd
            )
            ax = self.axes[0]
            return (
                self._a2a(p, ax, nd),
                self._a2a(v, ax, nd),
                self._a2a(par, ax, nd),
                self._a2a(act, ax, nd),
            )
        dcn_ax, ici_ax = self.axes
        n_dcn, n_ici = self.mesh.devices.shape
        # stage 1: to the owner SLICE (carry the owner id along)
        v, (p, par, act, own) = self._bucket(
            owner // n_ici, valid, (packed, parent, action, owner), n_dcn
        )
        p = self._a2a(p, dcn_ax, n_dcn)
        v = self._a2a(v, dcn_ax, n_dcn)
        par = self._a2a(par, dcn_ax, n_dcn)
        act = self._a2a(act, dcn_ax, n_dcn)
        own = self._a2a(own, dcn_ax, n_dcn)
        # stage 2: within the slice, to the owner CHIP
        v2, (p2, par2, act2) = self._bucket(
            own % n_ici, v, (p, par, act), n_ici
        )
        return (
            self._a2a(p2, ici_ax, n_ici),
            self._a2a(v2, ici_ax, n_ici),
            self._a2a(par2, ici_ax, n_ici),
            self._a2a(act2, ici_ax, n_ici),
        )

    def _get_step(self, kind: str):
        key = (kind, self._cap)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        m = self.model
        nd = self.n_shards

        def core(rp, rv, rpar, ract, vk, n_visited):
            if self.dedup_mode == "hash":
                return dedup_core_hash(
                    m, self.invariant_names, rp, rv, rpar, ract, *vk
                )
            return dedup_core(
                m, self.invariant_names, rp, rv, rpar, ract, *vk, n_visited
            )

        def insert_body(packed, valid, gids, *rest):
            vk, n_visited = rest[:-1], rest[-1]
            parent = jnp.full(valid.shape, -1, jnp.int32)
            action = jnp.full(valid.shape, -1, jnp.int32)
            rp, rv, rpar, ract = self._route(packed, valid, parent, action)
            return core(rp, rv, rpar, ract, vk, n_visited) + (jnp.int32(0),)

        def expand_body(frontier, n, gids, *rest):
            vk, n_visited = rest[:-1], rest[-1]
            f = frontier.shape[0]
            row_live = jnp.arange(f, dtype=jnp.int32) < n
            states = jax.vmap(self.layout.unpack)(frontier)
            succ, valid = jax.vmap(m.successors)(states)
            valid = valid & row_live[:, None]
            packed = jax.vmap(jax.vmap(self.layout.pack))(succ).reshape(
                f * m.A, self.layout.W
            )
            parent_gid = jnp.repeat(gids, m.A)
            action = jnp.tile(jnp.asarray(m.action_ids), f)
            rp, rv, rpar, ract = self._route(
                packed, valid.reshape(f * m.A), parent_gid, action
            )
            out = core(rp, rv, rpar, ract, vk, n_visited)
            if self.check_deadlock:
                stutter = jax.vmap(m.stutter_enabled)(states)
                dead = row_live & ~jnp.any(valid, axis=1) & ~stutter
                dead_idx = jnp.min(
                    jnp.where(dead, jnp.arange(f, dtype=jnp.int32), f)
                )
            else:
                dead_idx = jnp.int32(f)
            return out + (dead_idx,)

        body = insert_body if kind == "insert" else expand_body

        def shard_fn(stacked_args):
            args = [
                x[0] if isinstance(x, jax.Array) or hasattr(x, "shape") else x
                for x in stacked_args
            ]
            out = body(*args)
            return tuple(o[None] for o in out)

        axes_spec = self.axes if len(self.axes) > 1 else self.axes[0]
        in_spec = (P(axes_spec),)
        out_spec = P(axes_spec)
        mapped = jax.shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=in_spec,
            out_specs=out_spec,
            check_vma=False,
        )
        fn = jax.jit(mapped)
        self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    # host driver
    # ------------------------------------------------------------------

    def _empty_vk(self):
        nd = self.n_shards
        if self.dedup_mode == "hash":
            z = jnp.zeros((nd, self._cap + 1), jnp.uint32)
            return (z, z, z, jnp.zeros((nd, self._cap + 1), jnp.int32))
        return tuple(
            jnp.full((nd, self._cap), SENTINEL, jnp.uint32) for _ in range(3)
        )

    def _grow_visited(self, vk, need_per_shard: int):
        cap = self._cap
        target = (
            2 * need_per_shard if self.dedup_mode == "hash" else need_per_shard
        )
        while cap < target:
            cap *= 4
        if cap == self._cap:
            return vk
        if self.dedup_mode == "hash":
            # rehash each shard's table into the bigger capacity
            nd = self.n_shards
            news = [hashtable.empty_table(cap) for _ in range(nd)]
            for d in range(nd):
                news[d] = hashtable.rehash_into(
                    tuple(col[d] for col in vk), news[d]
                )
            vk = tuple(
                jnp.stack([news[d][i] for d in range(nd)])
                for i in range(4)
            )
        else:
            pad = cap - self._cap
            vk = tuple(
                jnp.concatenate(
                    [col, jnp.full((col.shape[0], pad), SENTINEL, jnp.uint32)],
                    axis=1,
                )
                for col in vk
            )
        self._cap = cap
        return vk

    def _config_sig(self) -> str:
        return repr(
            (
                getattr(self.model, "c", None),
                self.invariant_names,
                self.layout.total_bits,
                self.dedup_mode,
                self.n_shards,
                tuple(self.axes),
            )
        )

    def _over_budget(self, n_total: int, t0: float) -> bool:
        return n_total > self.max_states or (
            self.time_budget_s is not None
            and time.time() - t0 > self.time_budget_s
        )

    def _rewind_metrics(self, resumed_level: int):
        """Drop metric records for levels the resumed run re-discovers
        (mirrors engine.bfs.Checker._rewind_metrics)."""
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

    def _emit_metrics(self, t0, level, level_count, n_total, frontier_len):
        wall = time.time() - t0
        self._snap.update(
            level=level, frontier=int(frontier_len),
            distinct_states=int(n_total),
        )
        self.tel.emit(
            "level",
            level=level,
            new_states=int(level_count),
            distinct_states=int(n_total),
            frontier=int(frontier_len),
            wall_s=round(wall, 3),
            states_per_sec=round(n_total / max(wall, 1e-9), 1),
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
                        "distinct_states": n_total,
                        "frontier": frontier_len,
                        "wall_s": round(wall, 3),
                        "states_per_sec": round(
                            n_total / max(wall, 1e-9), 1
                        ),
                        "visited_cap_per_shard": self._cap,
                        "n_shards": self.n_shards,
                    }
                )
                + "\n"
            )

    def _save_checkpoint(
        self, vk, n_visited, log, level_sizes, frontier, fgids, t0
    ):
        """Level-boundary snapshot (SURVEY.md §2.2-E8, sharded): per-shard
        visited columns + per-shard frontier + trace log.  The atomic
        frame writer is shared with the device engines (utils/ckpt.py)."""
        from pulsar_tlaplus_tpu.utils import ckpt

        t_stall = time.perf_counter()
        total = sum(len(f) for f in frontier)
        nbytes, write_s, retries = ckpt.save_frame(
            self.checkpoint_path,
            self._config_sig(),
            dict(
                {
                    f"vk{i}": np.asarray(col)
                    for i, col in enumerate(vk)
                },
                n_visited=n_visited,
                level_sizes=np.asarray(level_sizes, np.int64),
                fr=(
                    np.concatenate(frontier)
                    if total
                    else np.zeros((0, self.layout.W), np.uint32)
                ),
                fr_lens=np.asarray(
                    [len(f) for f in frontier], np.int64
                ),
                fgids=(
                    np.concatenate(fgids)
                    if total
                    else np.zeros((0,), np.int64)
                ),
                packed=log.packed_matrix(),
                parent=log.parents(),
                action=log.actions(),
            ),
            wall_s=time.time() - t0,
            meta={
                "run_id": self._run_id,
                "frame_seq": self._ckpt_frames + 1,
                "level": len(level_sizes),
                "engine": "sharded_host",
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
            level=len(level_sizes),
            distinct_states=int(np.asarray(n_visited).sum()),
        )

    def load_checkpoint(self):
        from pulsar_tlaplus_tpu.utils import ckpt

        return ckpt.load_frame(
            self.checkpoint_path, self._config_sig()
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
            engine="sharded_host",
            device=dev,
            n_devices=self.n_shards,
            visited_impl=self.dedup_mode,
            config_sig=self._config_sig(),
            # REQUIRED since schema v8, a constant null
            profile_sig=None,
            hbm_budget=None,
            # v10: tenant identity (None outside the daemon)
            tenant=getattr(self, "tenant", None),
            warm=getattr(self, "warm", None),
            # v15: distributed-trace identity (None outside the daemon)
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
        m = self.model
        nd = self.n_shards
        t0 = time.time()
        # ``t0`` is rewound on resume so wall_s/states_per_sec stay
        # cumulative across the whole logical run; the time budget gets
        # its own fresh clock (``budget_t0``) so a resumed run always
        # has ``time_budget_s`` of fresh runway instead of being
        # immediately over budget and crawling one level per resume
        budget_t0 = t0
        vk = self._empty_vk()
        n_visited = np.zeros((nd,), np.int64)
        from pulsar_tlaplus_tpu.engine.statelog import MemoryLog

        log = MemoryLog(self.layout.W)
        n_total = 0
        level_sizes: List[int] = []
        # per-shard next-level frontier accumulators (host)
        next_parts: List[List[np.ndarray]] = [[] for _ in range(nd)]
        next_gid_parts: List[List[np.ndarray]] = [[] for _ in range(nd)]

        viol_i = self._viol_i

        def flush(out) -> Tuple[int, Optional[Tuple[str, int]]]:
            """Harvest all shards' new states into the log and the
            next-level accumulators; returns (n_new_total, violation)."""
            nonlocal n_total
            packed, parent, action, n_new = out[0], out[1], out[2], out[3]
            if self.dedup_mode == "hash":
                n_failed = int(np.asarray(out[viol_i + 1]).sum())
                if n_failed:
                    raise RuntimeError(
                        "sharded hash-table probe overflow — raise "
                        f"visited_cap ({n_failed} unresolved lanes)"
                    )
            viol = np.asarray(out[viol_i])
            n_new = np.asarray(n_new)
            violation = None
            total_new = 0
            for d in range(nd):
                nn = int(n_new[d])
                n_visited[d] += nn
                if nn == 0:
                    continue
                np_packed = np.asarray(packed[d][:nn])
                log.append(
                    np_packed,
                    np.asarray(parent[d][:nn]).astype(np.int64),
                    np.asarray(action[d][:nn]),
                )
                next_parts[d].append(np_packed)
                next_gid_parts[d].append(
                    np.arange(n_total, n_total + nn, dtype=np.int64)
                )
                for i, name in enumerate(self.invariant_names):
                    vi = int(viol[d][i])
                    if vi < nn and violation is None:
                        violation = (name, n_total + vi)
                n_total += nn
                total_new += nn
            return total_new, violation

        def take_next():
            """Drain accumulators -> per-shard frontier arrays."""
            fr, gd = [], []
            for d in range(nd):
                fr.append(
                    np.concatenate(next_parts[d])
                    if next_parts[d]
                    else np.zeros((0, self.layout.W), np.uint32)
                )
                gd.append(
                    np.concatenate(next_gid_parts[d])
                    if next_gid_parts[d]
                    else np.zeros((0,), np.int64)
                )
                next_parts[d] = []
                next_gid_parts[d] = []
            return fr, gd

        def build_result(violation, deadlock_gid=None, truncated=False):
            wall = time.time() - t0
            res = CheckerResult(
                distinct_states=n_total,
                diameter=len(level_sizes),
                deadlock=deadlock_gid is not None,
                wall_s=wall,
                states_per_sec=n_total / max(wall, 1e-9),
                level_sizes=level_sizes,
                truncated=truncated,
            )
            gid = None
            if violation is not None:
                res.violation = violation[0]
                gid = violation[1]
            elif deadlock_gid is not None:
                res.violation = "Deadlock"
                gid = deadlock_gid
            if gid is not None:
                res.trace, res.trace_actions = build_trace(
                    m, self._unpack1, gid, log
                )
            self.tel.emit(
                "result",
                distinct_states=n_total,
                diameter=len(level_sizes),
                wall_s=round(wall, 3),
                states_per_sec=round(n_total / max(wall, 1e-9), 1),
                truncated=truncated,
                stop_reason=res.stop_reason,
                violation=res.violation,
                deadlock=res.deadlock,
                level_sizes=[int(x) for x in level_sizes],
                stats={
                    "ckpt_frames": self._ckpt_frames,
                    "ckpt_bytes": self._ckpt_bytes,
                    "ckpt_write_s": round(self._ckpt_write_s, 3),
                    "ckpt_retries": self._ckpt_retries,
                    "n_shards": self.n_shards,
                },
            )
            return res

        if resume:
            from pulsar_tlaplus_tpu.utils import ckpt

            d = self.load_checkpoint()
            self._resume_meta = ckpt.frame_meta(d)
            self._emit_header(resume=True)
            if "wall_s" in d:
                t0 = time.time() - float(d["wall_s"])
            self._cap = d["vk0"].shape[1] - (
                1 if self.dedup_mode == "hash" else 0
            )
            self._jit_cache.clear()
            vk = tuple(
                jnp.asarray(d[f"vk{i}"]) for i in range(self._ncols)
            )
            n_visited = d["n_visited"].astype(np.int64)
            if len(d["packed"]):
                log.append(d["packed"], d["parent"], d["action"])
            n_total = len(log)
            level_sizes = [int(x) for x in d["level_sizes"]]
            lens = d["fr_lens"]
            offs = np.concatenate([[0], np.cumsum(lens)])
            fr_all, fg_all = d["fr"], d["fgids"]  # decompress once
            frontier = [fr_all[offs[i]: offs[i + 1]] for i in range(nd)]
            fgids = [fg_all[offs[i]: offs[i + 1]] for i in range(nd)]
            self._rewind_metrics(len(level_sizes))
        else:
            self._emit_header(resume=False)
            # ---- level 1: initial states, routed to owners ----
            n_init = m.n_initial
            gen = jax.jit(
                jax.vmap(lambda i: self.layout.pack(m.gen_initial(i)))
            )
            per_round = nd * self.F
            dummy_gids = jnp.zeros((nd, self.F), jnp.int32)
            for start in range(0, n_init, per_round):
                idx = np.arange(start, start + per_round, dtype=np.int64)
                packed = np.asarray(
                    gen(jnp.asarray(idx % max(n_init, 1), jnp.int32))
                )
                valid = idx < n_init
                vk = self._grow_visited(
                    vk, int(n_visited.max()) + nd * self.F + 1
                )
                out = self._get_step("insert")(
                    (
                        jnp.asarray(
                            packed.reshape(nd, self.F, self.layout.W)
                        ),
                        jnp.asarray(valid.reshape(nd, self.F)),
                        dummy_gids,
                        *vk,
                        jnp.asarray(n_visited, jnp.int32),
                    )
                )
                vk = out[4:4 + self._ncols]
                _nn, violation = flush(out)
                if violation is not None:
                    level_sizes.append(n_total)
                    return build_result(violation)
            level_sizes.append(n_total)
            frontier, fgids = take_next()

        # ---- BFS levels ----
        while any(len(f) for f in frontier):
            rounds = max((len(f) + self.F - 1) // self.F for f in frontier)
            level_base = n_total
            for r in range(rounds):
                chunk = np.zeros((nd, self.F, self.layout.W), np.uint32)
                ns = np.zeros((nd,), np.int32)
                gid_chunk = np.zeros((nd, self.F), np.int64)
                for d in range(nd):
                    part = frontier[d][r * self.F : (r + 1) * self.F]
                    ns[d] = len(part)
                    chunk[d, : len(part)] = part
                    gid_chunk[d, : len(part)] = fgids[d][
                        r * self.F : (r + 1) * self.F
                    ]
                vk = self._grow_visited(
                    vk, int(n_visited.max()) + nd * self.F * m.A + 1
                )
                out = self._get_step("expand")(
                    (
                        jnp.asarray(chunk),
                        jnp.asarray(ns),
                        jnp.asarray(gid_chunk, jnp.int32),
                        *vk,
                        jnp.asarray(n_visited, jnp.int32),
                    )
                )
                vk = out[4:4 + self._ncols]
                dead = np.asarray(out[self._dead_i])
                _nn, violation = flush(out)
                if violation is not None:
                    level_sizes.append(n_total - level_base)
                    return build_result(violation)
                for d in range(nd):
                    if int(dead[d]) < int(ns[d]):
                        level_sizes.append(n_total - level_base)
                        return build_result(
                            None,
                            deadlock_gid=int(gid_chunk[d][int(dead[d])]),
                        )
                over = self._over_budget(n_total, budget_t0)
                if over and self.checkpoint_path is None:
                    # no checkpoint configured: stop immediately
                    level_sizes.append(n_total - level_base)
                    return build_result(None, truncated=True)
            if n_total == level_base:
                break
            level_sizes.append(n_total - level_base)
            self._emit_metrics(
                t0, len(level_sizes), n_total - level_base, n_total,
                sum(len(f) for f in frontier),
            )
            frontier, fgids = take_next()
            over = self._over_budget(n_total, budget_t0)
            if self.checkpoint_path and (
                over or len(level_sizes) % self.checkpoint_every == 0
            ):
                # level boundaries are the consistent snapshot points
                self._save_checkpoint(
                    vk, n_visited, log, level_sizes, frontier, fgids, t0
                )
            if over:
                return build_result(None, truncated=True)

        return build_result(None)
