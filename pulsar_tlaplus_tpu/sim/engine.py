"""Streaming device simulation engine — TLC's ``-simulate`` as a
first-class budgeted workload (round 18; docs/simulation.md).

The round-2 one-shot ``engine/simulate.py`` rolled a fixed-depth batch
of walkers once and returned.  This engine runs the walker swarm
CONTINUOUSLY under state/time budgets, the way the exhaustive engines
run BFS:

- **Segmented rollouts.**  One jitted ``lax.scan`` advances every
  walker ``segment_len`` steps per dispatch; the host-side *epoch*
  counter advances per segment.  Per-walker PRNG keys are derived
  FUNCTIONALLY from ``(seed, global step, walker)`` via ``fold_in`` —
  never carried — so the walk stream is deterministic given ``seed``
  and resumable from ``(walker states, epoch)`` alone.
- **Lockstep behaviors.**  All walkers restart a fresh behavior every
  ``depth`` steps (``segment_len`` is clamped to a divisor of
  ``depth``, so restarts land exactly on segment boundaries and the
  restart variant of the kernel is a second static compile, not a
  traced branch).  One *round* = ``depth`` steps + the fresh initial
  states; a completed round counts ``n_walkers`` finished walks.
- **In-kernel work counters** (the r14 style): stutter steps,
  enabled-lane evaluations (hi/lo u32 carry), walker-steps with
  invariant failures, the earliest violation's ``(step, walker,
  invariant)``, and the duplicate-estimator hits — all returned in
  ONE small stats vector per dispatch, so a segment costs exactly
  1 dispatch + 1 fetch.  Steps/states/invariant-check totals are
  host-derived (they are functions of ``B``/``segment_len``/epoch).
- **Sampled-duplicate estimator.**  A fixed walker subsample hashes
  each visited state into a small device-resident table; the hit
  ratio estimates how much of the swarm's work revisits old states —
  ADVISORY ONLY (simulation never dedups on the hot path; that is
  the point of the workload).
- **On-violation device replay.**  The offending walker's key stream
  is replayed from its behavior start, materializing every state;
  the behavior is then re-verified step-for-step through an
  independent single-state evaluation (chosen lane enabled, successor
  equal, invariant holding until the final state) before it is
  reported — ``result.verified``.
- **Survivability.**  Checkpoint frames carry (walker states, epoch,
  dup table, cumulative counters, a keys-digest over the PRNG
  position) so kill/SIGTERM/suspend resume continues the IDENTICAL
  walk stream; the daemon time-slices simulation jobs through the
  same cooperative ``suspend_hook`` as BFS jobs.

Telemetry: schema v11 ``sim`` records (cumulative steps / walkers /
violations + the estimator), ``run_header.mode = "simulate"``, the
standard ckpt_frame/fault/result records, heartbeat walks/s.
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.utils import ckpt, faults

# in-kernel counter vector layout (u32): per-SEGMENT deltas, reset
# every dispatch — the host accumulates into Python ints, so no
# cross-segment carry machinery is needed
CTR_STUTTER = 0   # stutter lanes chosen
CTR_EN_LO = 1     # enabled-lane evaluations, low word
CTR_EN_HI = 2     # enabled-lane evaluations, carry word
CTR_VIOL = 3      # walker-steps with >= 1 invariant failure
CTR_VKEY = 4      # min (code * B + walker); 0xFFFFFFFF = clean
CTR_VINV = 5      # invariant index of the min key
CTR_DUP_ATT = 6   # duplicate-estimator insert attempts
CTR_DUP_HITS = 7  # duplicate-estimator hits (tag already present)
CTR_N = 8

_CLEAN = np.uint32(0xFFFFFFFF)

# checkpoint frame format revision for this engine's sig
_SIM_CKPT_REV = 1


def _model_sig(model) -> str:
    """Model identity for the frame signature (the engines'
    shared contract: hand models carry their Constants in ``.c``)."""
    c = getattr(model, "c", None)
    if c is not None:
        return repr(c)
    spec = getattr(model, "spec", None)
    if spec is not None:
        return repr(
            (
                getattr(spec.module, "name", "?"),
                sorted((k, repr(v)) for k, v in spec.constants.items()),
            )
        )
    return type(model).__name__


@dataclass
class SimulationResult:
    """One simulation run.  The first six fields are the legacy
    ``engine/simulate.py`` contract (preserved by the shim); the rest
    are the streaming engine's budget/throughput story."""

    n_walkers: int
    depth: int
    states_visited: int  # walkers x (steps + behavior starts), not distinct
    violation: Optional[str] = None
    trace: Optional[list] = None
    trace_actions: Optional[List[str]] = None
    # streaming-era fields (r18)
    steps: int = 0            # random steps taken across the swarm
    walks: int = 0            # completed behaviors (B per finished round)
    segments: int = 0         # dispatches run
    epoch: int = 0            # next segment index (resume cursor)
    wall_s: float = 0.0
    truncated: bool = False   # suspended/preempted/cancelled mid-stream
    stop_reason: Optional[str] = None
    steps_per_sec: float = 0.0
    walks_per_sec: float = 0.0
    states_per_sec: float = 0.0
    dup_ratio_est: Optional[float] = None  # advisory sampled estimate
    verified: Optional[bool] = None  # replayed behavior re-verified
    violation_walker: Optional[int] = None
    violation_step: Optional[int] = None  # global step of the bad state
    stats: Dict[str, object] = field(default_factory=dict)


class StreamingSimulator:
    """Continuous walker-swarm simulation of a compiled model.

    Budgets (the run ends at whichever binds first):

    - ``max_steps``: total random steps across the swarm;
    - ``max_rounds``: completed behaviors-per-walker rounds;
    - ``time_budget_s``: wall clock.

    With NO budget given the engine runs exactly one round (the legacy
    one-shot semantics — a finite default; the daemon/bench callers
    always pass a budget).
    """

    def __init__(
        self,
        model,
        invariants: Optional[Tuple[str, ...]] = None,
        n_walkers: Optional[int] = None,
        depth: int = 64,
        segment_len: Optional[int] = None,
        seed: int = 0,
        max_steps: Optional[int] = None,
        max_rounds: Optional[int] = None,
        time_budget_s: Optional[float] = None,
        dup_sample: int = 256,
        dup_table_bits: int = 16,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 8,  # segments between frames
        sim_event_every: int = 1,   # segments between `sim` records
        telemetry=None,
        heartbeat_s: Optional[float] = None,
        progress: bool = False,
        suspend_hook=None,
        tenant: Optional[str] = None,
    ):
        self.model = model
        if invariants is None:
            invariants = tuple(getattr(model, "default_invariants", ()))
        self.invariant_names = tuple(invariants)
        unknown = [
            n for n in self.invariant_names
            if n not in getattr(model, "invariants", {})
        ]
        if unknown:
            raise ValueError(f"unknown invariant(s): {unknown}")
        if n_walkers is None:
            n_walkers = 1024
        if depth < 1:
            raise ValueError(f"depth must be >= 1: {depth}")
        if n_walkers < 1:
            raise ValueError(f"n_walkers must be >= 1: {n_walkers}")
        self.B = int(n_walkers)
        self.T = int(depth)
        # segment_len is clamped to the largest divisor of depth <= the
        # request, so behavior restarts land exactly on segment
        # boundaries (module docstring)
        want = int(segment_len) if segment_len else min(self.T, 32)
        want = max(1, min(want, self.T))
        while self.T % want:
            want -= 1
        self.L = want
        self.segs_per_round = self.T // self.L
        # the violation key packs (2 * step + phase) * B + walker into
        # one u32 min-reduction
        if self.B * (2 * self.L + 2) >= 1 << 31:
            raise ValueError(
                f"n_walkers * segment_len too large for the violation "
                f"key encoding ({self.B} x {self.L})"
            )
        self.seed = int(seed)
        self.max_steps = max_steps
        self.max_rounds = max_rounds
        # remember whether the CALLER chose a budget: a resume that
        # passes none adopts the frame's persisted budgets instead of
        # silently falling back to the one-round default (which would
        # end a recovered long run immediately, reported clean)
        self._budget_explicit = not (
            max_steps is None
            and max_rounds is None
            and time_budget_s is None
        )
        if not self._budget_explicit:
            self.max_rounds = 1  # finite default: one behavior round
        self.time_budget_s = time_budget_s
        self.S = max(1, min(int(dup_sample), self.B))
        self.dup_table_bits = int(dup_table_bits)
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.sim_event_every = max(1, int(sim_event_every))
        self._telemetry_arg = telemetry
        self.tel = obs.NULL
        self.heartbeat_s = heartbeat_s
        self.progress = progress
        self.suspend_hook = suspend_hook
        self.tenant = tenant
        self.last_stats: Dict[str, object] = {}
        self._run_id: Optional[str] = None
        self._snap: Dict[str, object] = {}
        self._jits: Dict[str, object] = {}
        self._fetch_n = 0
        self._frame_seq = 0
        self._inv_fns = [
            model.invariants[n] for n in self.invariant_names
        ]
        self.A = int(model.A)

    # ------------------------------------------------------------ sig

    def _config_sig(self) -> str:
        return ckpt.config_sig(
            kind="sim",
            rev=_SIM_CKPT_REV,
            model=_model_sig(self.model),
            invariants=self.invariant_names,
            n_walkers=self.B,
            depth=self.T,
            segment_len=self.L,
            seed=self.seed,
        )

    # -------------------------------------------------- kernel pieces

    def _bases(self):
        base = jax.random.PRNGKey(self.seed)
        k_init, k_step = jax.random.split(base)
        return k_init, k_step

    def _init_one(self, k):
        m = self.model
        sampler = getattr(m, "sample_initial", None)
        if sampler is not None:
            return sampler(k)
        if m.n_initial > 2**31 - 1:
            raise ValueError(
                f"n_initial = {m.n_initial} exceeds int32: the model "
                "must provide sample_initial(key) for simulation mode"
            )
        idx = jax.random.randint(k, (), 0, m.n_initial, jnp.int32)
        return m.gen_initial(idx)

    def _step_one(self, state, k):
        """One random step of one walker: uniform over enabled lanes
        plus the stutter lane (TLC behavior-space semantics; no
        enabled lane at all -> stay put).  Returns (next_state, lane
        or -1 for stutter, enabled-lane count)."""
        m = self.model
        succ, valid = m.successors(state)
        stutter = m.stutter_enabled(state)
        weights = jnp.concatenate(
            [valid.astype(jnp.float32), stutter.astype(jnp.float32)[None]]
        )
        total = jnp.sum(weights)
        fallback = jnp.zeros((self.A + 1,)).at[self.A].set(1.0)
        probs = jnp.where(
            total > 0, weights / jnp.maximum(total, 1.0), fallback
        )
        lane = jax.random.choice(k, self.A + 1, p=probs)
        is_stutter = lane >= self.A
        lane_c = jnp.minimum(lane, self.A - 1)
        nxt = jax.tree.map(
            lambda cur, s: jnp.where(is_stutter, cur, s[lane_c]),
            state,
            succ,
        )
        n_enabled = jnp.sum(valid.astype(jnp.uint32)) + stutter.astype(
            jnp.uint32
        )
        return (
            nxt,
            jnp.where(is_stutter, -1, lane_c).astype(jnp.int32),
            n_enabled,
        )

    def _inv_ok(self, state):
        """bool[n_inv] — True = satisfied."""
        if not self._inv_fns:
            return jnp.ones((0,), bool)
        return jnp.stack([f(state) for f in self._inv_fns])

    def _fingerprints(self, states_sub):
        """u32[S] mixed fingerprints of the sampled walkers' states
        (collisions only perturb the ADVISORY duplicate estimate)."""
        h = jnp.zeros((self.S,), jnp.uint32)
        for leaf in jax.tree_util.tree_leaves(states_sub):
            x = leaf.astype(jnp.uint32).reshape(self.S, -1)
            mult = (
                2 * jnp.arange(x.shape[1], dtype=jnp.uint32) + 1
            ) * jnp.uint32(0x9E3779B9)
            h = h * jnp.uint32(0x85EBCA6B) + jnp.sum(
                x * mult, axis=1, dtype=jnp.uint32
            )
        h ^= h >> 16
        h = h * jnp.uint32(0x7FEB352D)
        h ^= h >> 15
        return h

    def _dup_insert(self, table, states):
        """Hash the walker subsample into the fixed estimator table;
        returns (table, hits).  No dedup — advisory sampling only."""
        sub = jax.tree.map(lambda x: x[: self.S], states)
        h = self._fingerprints(sub)
        idx = (h >> jnp.uint32(32 - self.dup_table_bits)).astype(
            jnp.int32
        )
        tag = h | jnp.uint32(1)
        hits = jnp.sum((table[idx] == tag).astype(jnp.uint32))
        return table.at[idx].set(tag), hits

    def _viol_update(self, ctrs, ok, code):
        """Fold one batch of invariant results [B, n_inv] into the
        counter vector at violation code ``code`` (2*step for a fresh
        initial state, 2*step+1 for a post-step state)."""
        if ok.shape[1] == 0:
            return ctrs
        bad = ~jnp.all(ok, axis=1)  # [B]
        n_bad = jnp.sum(bad.astype(jnp.uint32))
        w = jnp.argmax(bad).astype(jnp.uint32)  # first violating walker
        inv = jnp.argmax(~ok[w]).astype(jnp.uint32)
        cand = jnp.where(
            n_bad > 0,
            code.astype(jnp.uint32) * jnp.uint32(self.B) + w,
            _CLEAN,
        )
        better = cand < ctrs[CTR_VKEY]
        ctrs = ctrs.at[CTR_VIOL].add(n_bad)
        ctrs = ctrs.at[CTR_VKEY].set(
            jnp.where(better, cand, ctrs[CTR_VKEY])
        )
        ctrs = ctrs.at[CTR_VINV].set(
            jnp.where(better, inv, ctrs[CTR_VINV])
        )
        return ctrs

    def _segment_fn(self, restart: bool):
        """The segment megakernel: (states, table, epoch) -> (states,
        table, counters).  ``restart`` is a STATIC flag — the variant
        that opens a fresh behavior round draws new initial states
        before the step scan (restarts only ever land at segment
        boundaries because segment_len divides depth)."""
        k_init, k_step = self._bases()
        widx = jnp.arange(self.B, dtype=jnp.uint32)

        def seg(states, table, epoch):
            ctrs = jnp.zeros((CTR_N,), jnp.uint32).at[CTR_VKEY].set(
                _CLEAN
            )
            g0 = epoch.astype(jnp.int32) * jnp.int32(self.L)
            if restart:
                kr = jax.random.fold_in(k_init, g0)
                keys = jax.vmap(
                    lambda w: jax.random.fold_in(kr, w)
                )(widx)
                states = jax.vmap(self._init_one)(keys)
                ok0 = jax.vmap(self._inv_ok)(states)
                ctrs = self._viol_update(ctrs, ok0, jnp.uint32(0))
                table, hits = self._dup_insert(table, states)
                ctrs = ctrs.at[CTR_DUP_ATT].add(jnp.uint32(self.S))
                ctrs = ctrs.at[CTR_DUP_HITS].add(hits)

            def step(carry, i):
                st, tbl, c = carry
                g = g0 + i
                ks = jax.random.fold_in(k_step, g)
                keys = jax.vmap(
                    lambda w: jax.random.fold_in(ks, w)
                )(widx)
                nxt, lanes, n_en = jax.vmap(self._step_one)(st, keys)
                en = jnp.sum(n_en, dtype=jnp.uint32)
                lo = c[CTR_EN_LO] + en
                c = c.at[CTR_EN_HI].add(
                    (lo < c[CTR_EN_LO]).astype(jnp.uint32)
                )
                c = c.at[CTR_EN_LO].set(lo)
                c = c.at[CTR_STUTTER].add(
                    jnp.sum((lanes < 0).astype(jnp.uint32))
                )
                ok = jax.vmap(self._inv_ok)(nxt)
                c = self._viol_update(
                    c, ok, (2 * i + 1).astype(jnp.uint32)
                )
                tbl, hits = self._dup_insert(tbl, nxt)
                c = c.at[CTR_DUP_ATT].add(jnp.uint32(self.S))
                c = c.at[CTR_DUP_HITS].add(hits)
                return (nxt, tbl, c), None

            (states, table, ctrs), _ = jax.lax.scan(
                step, (states, table, ctrs),
                jnp.arange(self.L, dtype=jnp.int32),
            )
            return states, table, ctrs

        return seg

    def _segment_jit(self, restart: bool):
        key = f"segment_restart{int(restart)}"
        fn = self._jits.get(key)
        if fn is None:
            fn = jax.jit(
                self._segment_fn(restart), donate_argnums=(0, 1)
            )
            self._jits[key] = fn
        return fn

    def _replay_jit(self):
        fn = self._jits.get("replay")
        if fn is None:
            k_init, k_step = self._bases()

            def replay(w, r0):
                kw = jax.random.fold_in(
                    jax.random.fold_in(k_init, r0), w
                )
                s0 = self._init_one(kw)

                def step(s, j):
                    ks = jax.random.fold_in(
                        jax.random.fold_in(k_step, r0 + j), w
                    )
                    nxt, lane, _n = self._step_one(s, ks)
                    return nxt, (nxt, lane)

                _, (states, lanes) = jax.lax.scan(
                    step, s0, jnp.arange(self.T, dtype=jnp.int32)
                )
                return s0, states, lanes

            fn = jax.jit(replay)
            self._jits["replay"] = fn
        return fn

    def warmup(self) -> float:
        """Compile both segment variants up front; returns wall
        seconds spent (the daemon's sim pool calls this once)."""
        t0 = time.perf_counter()
        states, table = self._fresh_buffers()
        for restart in (True, False):
            s2, t2, c = self._segment_jit(restart)(
                states, table, jnp.int32(0)
            )
            np.asarray(c)
            states, table = s2, t2
        return time.perf_counter() - t0

    # ------------------------------------------------------- buffers

    def _fresh_buffers(self):
        # zero-filled walker planes: the first segment is always a
        # restart segment (epoch 0), which overwrites them with fresh
        # initial states before any step runs
        states = jax.tree.map(
            lambda x: jnp.zeros((self.B,) + tuple(x.shape), x.dtype),
            jax.eval_shape(
                lambda: self._init_one(jax.random.PRNGKey(0))
            ),
        )
        table = jnp.zeros((1 << self.dup_table_bits,), jnp.uint32)
        return states, table

    # ---------------------------------------------------- checkpoints

    def _keys_digest(self, leaves: List[np.ndarray], epoch: int) -> str:
        """Digest anchoring the PRNG position + swarm state: a resumed
        run continues the identical walk stream or refuses."""
        h = hashlib.sha256()
        h.update(
            repr((self.seed, int(epoch), self.B, self.T, self.L)).encode()
        )
        for leaf in leaves:
            h.update(np.ascontiguousarray(leaf).tobytes())
        return h.hexdigest()

    def _save_frame(self, states, table, epoch, cum, wall_s) -> None:
        if not self.checkpoint_path:
            return
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(states)]
        arrays = {f"w{i}": leaf for i, leaf in enumerate(leaves)}
        arrays["dup_table"] = np.asarray(table)
        arrays["epoch"] = np.int64(epoch)
        arrays["cum"] = np.asarray(
            [
                cum["steps"], cum["states"], cum["violations"],
                cum["stutter"], cum["enabled"], cum["dup_att"],
                cum["dup_hits"], cum["segments"],
            ],
            np.int64,
        )
        arrays["budgets"] = np.asarray(
            [
                -1 if self.max_steps is None else self.max_steps,
                -1 if self.max_rounds is None else self.max_rounds,
            ],
            np.int64,
        )
        arrays["keys_digest"] = np.frombuffer(
            self._keys_digest(leaves, epoch).encode(), dtype=np.uint8
        )
        self._frame_seq += 1
        nbytes, write_s, retries = ckpt.save_frame(
            self.checkpoint_path,
            self._config_sig(),
            arrays,
            wall_s=wall_s,
            meta={
                "run_id": self._run_id,
                "frame_seq": self._frame_seq,
                "epoch": int(epoch),
            },
        )
        self.last_stats["ckpt_frames"] = (
            int(self.last_stats.get("ckpt_frames", 0)) + 1
        )
        self.last_stats["ckpt_bytes"] = (
            int(self.last_stats.get("ckpt_bytes", 0)) + nbytes
        )
        self.last_stats["ckpt_write_s"] = round(
            float(self.last_stats.get("ckpt_write_s", 0.0)) + write_s, 4
        )
        self.last_stats["ckpt_retries"] = (
            int(self.last_stats.get("ckpt_retries", 0)) + retries
        )
        self.tel.emit(
            "ckpt_frame",
            frame_seq=self._frame_seq,
            bytes=nbytes,
            write_s=round(write_s, 4),
            retries=retries,
            distinct_states=None,
            epoch=int(epoch),
            steps=int(cum["steps"]),
        )

    def _load_frame(self):
        d = ckpt.load_frame(
            self.checkpoint_path, self._config_sig(),
            what="simulation configuration",
        )
        meta = ckpt.frame_meta(d)
        epoch = int(d["epoch"])
        leaves = [d[f"w{i}"] for i in range(
            sum(1 for k in d.files if k.startswith("w")
                and k[1:].isdigit())
        )]
        want = d["keys_digest"].tobytes().decode()
        got = self._keys_digest(
            [np.asarray(x) for x in leaves], epoch
        )
        if want != got:
            raise ValueError(
                "simulation checkpoint keys-digest mismatch — the "
                "frame does not anchor this walk stream"
            )
        template = jax.eval_shape(
            lambda: self._init_one(jax.random.PRNGKey(0))
        )
        treedef = jax.tree_util.tree_structure(template)
        # COPIES, not jnp.asarray views: the restored buffers are
        # donated to the next segment dispatch, and the CPU backend
        # can zero-copy-alias host numpy memory — donating an aliased
        # npz-backed array is a use-after-free (the r7 fpset-restore
        # lesson, re-learned here the hard way)
        states = jax.tree_util.tree_unflatten(
            treedef, [jnp.array(np.asarray(x)) for x in leaves]
        )
        table = jnp.array(np.asarray(d["dup_table"]))
        c = np.asarray(d["cum"], np.int64)
        cum = {
            "steps": int(c[0]), "states": int(c[1]),
            "violations": int(c[2]), "stutter": int(c[3]),
            "enabled": int(c[4]), "dup_att": int(c[5]),
            "dup_hits": int(c[6]), "segments": int(c[7]),
        }
        wall_s = float(d["wall_s"]) if "wall_s" in d else 0.0
        # budget restore: a resume constructed WITHOUT explicit budgets
        # continues the frame's persisted ones (-1 = unset) — never the
        # one-round default, which would end a recovered long run at
        # the first loop check and report it clean
        if not self._budget_explicit and "budgets" in d:
            b = np.asarray(d["budgets"], np.int64)
            if int(b[0]) >= 0:
                self.max_steps = int(b[0])
                self.max_rounds = None
            if int(b[1]) >= 0:
                self.max_rounds = int(b[1])
        return states, table, epoch, cum, wall_s, meta

    # ----------------------------------------------------------- run

    def _emit_header(self, resume: bool, resume_meta: dict) -> None:
        if not self.tel.enabled:
            return
        try:
            dev = str(jax.devices()[0])
        except Exception:  # noqa: BLE001 — headers must never kill a run
            dev = "unknown"
        f = dict(
            engine="sim",
            mode="simulate",
            device=dev,
            visited_impl=None,
            config_sig=self._config_sig(),
            profile_sig=None,
            hbm_budget=None,
            tenant=self.tenant,
            warm=getattr(self, "warm", None),
            # v15: distributed-trace identity (None outside the daemon)
            trace_id=getattr(self, "trace_id", None),
            # v16: the kernel fields of the device engines' headers
            # (obs/telemetry.py IMPL_FIELDS) — null here
            probe_impl=None,
            expand_impl=None,
            sieve_impl=None,
            wall_unix=round(time.time(), 3),
            n_walkers=self.B,
            depth=self.T,
            segment_len=self.L,
            seed=self.seed,
            invariants=list(self.invariant_names),
            resume=resume,
        )
        if resume and resume_meta:
            if resume_meta.get("run_id"):
                f["resume_of"] = resume_meta["run_id"]
            if resume_meta.get("frame_seq") is not None:
                f["resume_frame_seq"] = resume_meta["frame_seq"]
        self.tel.emit("run_header", **f)

    def _log(self, msg: str) -> None:
        if self.progress:
            import sys

            print(f"  {msg}", file=sys.stderr, flush=True)

    def run(self, resume: bool = False) -> SimulationResult:
        rid = obs.new_run_id()
        self.tel = obs.as_telemetry(self._telemetry_arg, run_id=rid)
        self._run_id = self.tel.run_id or rid
        self.last_stats = {}
        self._fetch_n = 0
        self._frame_seq = 0
        self._snap = {"distinct_states": 0}
        ckpt.cleanup_stale_tmp(self.checkpoint_path)
        faults.set_observer(
            lambda kind, site, count: self.tel.emit(
                "fault", kind=kind, site=site, count=count
            )
        )
        hb = None
        if self.heartbeat_s:
            hb = obs.Heartbeat(
                self.heartbeat_s, self._snap, telemetry=self.tel,
            )
        try:
            if hb is not None:
                hb.start()
            return self._run_impl(resume)
        except BaseException as e:
            self.tel.emit("error", error=repr(e)[:300])
            raise
        finally:
            faults.set_observer(None)
            if hb is not None:
                hb.stop()
            if obs.owns_stream(self._telemetry_arg):
                self.tel.close()
            self.tel = obs.NULL

    def _run_impl(self, resume: bool) -> SimulationResult:
        resume_meta: dict = {}
        if resume:
            if not self.checkpoint_path:
                raise ValueError("resume=True needs a checkpoint_path")
            states, table, epoch, cum, prior_wall, resume_meta = (
                self._load_frame()
            )
            t0 = time.time() - prior_wall
        else:
            states, table = self._fresh_buffers()
            epoch = 0
            cum = {
                "steps": 0, "states": 0, "violations": 0,
                "stutter": 0, "enabled": 0, "dup_att": 0,
                "dup_hits": 0, "segments": 0,
            }
            t0 = time.time()
        self._emit_header(resume, resume_meta)
        self._log(
            f"simulation: {self.B} walkers, depth {self.T}, "
            f"segment {self.L} step(s)"
            + (f" (resumed at epoch {epoch})" if resume else "")
        )
        watcher = ckpt.PreemptionWatcher(log=self._log)
        stop_reason: Optional[str] = None
        truncated = False
        viol = None  # (epoch, code, walker, inv_idx)
        t_deadline = (
            None
            if self.time_budget_s is None
            else time.monotonic() + self.time_budget_s
        )
        n_inv = len(self.invariant_names)
        with watcher:
            while True:
                # budget / cooperative-stop checks FIRST: the segment
                # about to run is all-or-nothing
                if watcher.requested:
                    stop_reason, truncated = "preempted", True
                    break
                if self.suspend_hook is not None:
                    why = self.suspend_hook()
                    if why == "cancelled":
                        stop_reason, truncated = "cancelled", True
                        self._log("run cancelled")
                        break
                    if why == "suspended":
                        stop_reason, truncated = "suspended", True
                        break
                if (
                    self.max_steps is not None
                    and cum["steps"] >= self.max_steps
                ):
                    stop_reason = "step_budget"
                    break
                if (
                    self.max_rounds is not None
                    # steps are SWARM-TOTAL: one round = B * depth
                    and cum["steps"] >= self.max_rounds * self.T * self.B
                ):
                    stop_reason = "round_budget"
                    break
                if (
                    t_deadline is not None
                    and time.monotonic() >= t_deadline
                ):
                    stop_reason = "time_budget"
                    break
                faults.poll("segment", epoch)
                restart = (epoch % self.segs_per_round) == 0
                states, table, ctrs = self._segment_jit(restart)(
                    states, table, jnp.int32(epoch)
                )
                c = np.asarray(ctrs)  # THE one fetch per dispatch
                self._fetch_n += 1
                cum["segments"] += 1
                cum["steps"] += self.B * self.L
                cum["states"] += self.B * self.L + (
                    self.B if restart else 0
                )
                cum["stutter"] += int(c[CTR_STUTTER])
                cum["enabled"] += (
                    int(c[CTR_EN_HI]) << 32
                ) + int(c[CTR_EN_LO])
                cum["violations"] += int(c[CTR_VIOL])
                cum["dup_att"] += int(c[CTR_DUP_ATT])
                cum["dup_hits"] += int(c[CTR_DUP_HITS])
                wall = time.time() - t0
                walks = self.B * (cum["steps"] // (self.B * self.T))
                self._snap.update(
                    distinct_states=cum["states"],
                    generated=cum["steps"],
                    level=epoch + 1,
                    walks=walks,
                )
                if (
                    cum["segments"] % self.sim_event_every == 0
                    or int(c[CTR_VIOL])
                ):
                    self._emit_sim_event(cum, epoch + 1, wall)
                if int(c[CTR_VIOL]) and int(c[CTR_VKEY]) != int(_CLEAN):
                    viol = (
                        epoch,
                        int(c[CTR_VKEY]) // self.B,
                        int(c[CTR_VKEY]) % self.B,
                        int(c[CTR_VINV]) if n_inv else 0,
                    )
                    epoch += 1
                    stop_reason = "violation"
                    break
                epoch += 1
                if (
                    self.checkpoint_path
                    and cum["segments"] % self.checkpoint_every == 0
                ):
                    self._save_frame(states, table, epoch, cum, wall)
        wall = time.time() - t0
        if stop_reason in ("suspended", "preempted"):
            self._save_frame(states, table, epoch, cum, wall)
            self._log(
                f"simulation {stop_reason} at epoch {epoch} "
                f"({cum['steps']} steps banked)"
            )
        res = self._mk_result(
            cum, epoch, t0, truncated=truncated, stop_reason=stop_reason
        )
        if viol is not None:
            self._attach_violation(res, viol)
        self._emit_result(res)
        return res

    def _emit_sim_event(self, cum, epoch, wall) -> None:
        walks = self.B * (cum["steps"] // (self.B * self.T))
        dup = (
            round(cum["dup_hits"] / cum["dup_att"], 6)
            if cum["dup_att"]
            else None
        )
        self.tel.emit(
            "sim",
            steps=cum["steps"],
            walkers=self.B,
            violations=cum["violations"],
            states=cum["states"],
            walks=walks,
            stutter_steps=cum["stutter"],
            enabled_lanes=cum["enabled"],
            dup_attempts=cum["dup_att"],
            dup_hits=cum["dup_hits"],
            dup_ratio_est=dup,
            epoch=epoch,
            segments=cum["segments"],
            wall_s=round(wall, 3),
            steps_per_sec=round(cum["steps"] / max(wall, 1e-9), 1),
        )

    def _mk_result(
        self, cum, epoch, t0, truncated: bool, stop_reason
    ) -> SimulationResult:
        wall = max(time.time() - t0, 1e-9)
        walks = self.B * (cum["steps"] // (self.B * self.T))
        dup = (
            round(cum["dup_hits"] / cum["dup_att"], 6)
            if cum["dup_att"]
            else None
        )
        res = SimulationResult(
            n_walkers=self.B,
            depth=self.T,
            states_visited=cum["states"],
            steps=cum["steps"],
            walks=walks,
            segments=cum["segments"],
            epoch=epoch,
            wall_s=round(wall, 3),
            truncated=truncated,
            stop_reason=stop_reason,
            steps_per_sec=round(cum["steps"] / wall, 1),
            walks_per_sec=round(walks / wall, 2),
            states_per_sec=round(cum["states"] / wall, 1),
            dup_ratio_est=dup,
        )
        res.stats = self.last_stats
        self.last_stats.update(
            sim_steps=cum["steps"],
            sim_states=cum["states"],
            sim_walks=walks,
            sim_walkers=self.B,
            sim_violations=cum["violations"],
            sim_stutter_steps=cum["stutter"],
            sim_enabled_lanes=cum["enabled"],
            sim_dup_attempts=cum["dup_att"],
            sim_dup_hits=cum["dup_hits"],
            sim_dup_ratio_est=dup,
            sim_segments=cum["segments"],
            sim_epoch=epoch,
            walks_per_sec=res.walks_per_sec,
            steps_per_sec=res.steps_per_sec,
            steps_per_state=(
                round(cum["steps"] / cum["states"], 4)
                if cum["states"]
                else None
            ),
            stats_fetches=self._fetch_n,
        )
        return res

    def _emit_result(self, res: SimulationResult) -> None:
        self.tel.emit(
            "result",
            distinct_states=None,
            diameter=None,
            wall_s=res.wall_s,
            truncated=res.truncated,
            stop_reason=res.stop_reason,
            violation=res.violation,
            states_visited=res.states_visited,
            steps=res.steps,
            walks=res.walks,
            stats=dict(self.last_stats),
        )

    # ------------------------------------------------ violation replay

    def _attach_violation(self, res: SimulationResult, viol) -> None:
        epoch_v, code, walker, inv_idx = viol
        m = self.model
        res.violation = (
            self.invariant_names[inv_idx]
            if self.invariant_names
            else None
        )
        res.violation_walker = walker
        g_state = epoch_v * self.L + code // 2  # violating state's step
        is_init = code % 2 == 0
        r0 = (g_state // self.T) * self.T  # behavior-round start
        n_steps = 0 if is_init else g_state - r0 + 1
        res.violation_step = None if is_init else g_state
        s0, states, lanes = self._replay_jit()(
            jnp.uint32(walker), jnp.int32(r0)
        )
        lane_log = np.asarray(lanes)
        names = getattr(m, "action_names", ())
        action_ids = getattr(m, "action_ids", None)
        take = lambda tree, i: jax.tree.map(
            lambda x: np.asarray(x)[i], tree
        )
        trace = [m.to_pystate(jax.tree.map(np.asarray, s0))]
        actions: List[str] = []
        for step in range(n_steps):
            lane = int(lane_log[step])
            if lane < 0:
                continue  # stutter: state unchanged, not in the trace
            trace.append(m.to_pystate(take(states, step)))
            aid = (
                int(action_ids[lane]) if action_ids is not None else lane
            )
            actions.append(names[aid] if aid < len(names) else str(aid))
        res.trace = trace
        res.trace_actions = actions
        res.verified = self._verify_replay(
            s0, states, lane_log, n_steps, inv_idx
        )
        self.tel.emit(
            "sim_violation",
            invariant=res.violation,
            walker=walker,
            step=res.violation_step,
            trace_len=len(trace),
            verified=res.verified,
        )

    def _verify_replay(
        self, s0, states, lane_log, n_steps: int, inv_idx: int
    ) -> bool:
        """Independent re-verification of the replayed behavior: every
        chosen lane was enabled, every successor matches a single-state
        re-evaluation, and the violated invariant holds on every state
        but the last."""
        m = self.model
        succ_fn = self._jits.get("verify_succ")
        if succ_fn is None:
            succ_fn = jax.jit(m.successors)
            self._jits["verify_succ"] = succ_fn
        inv_fn = None
        if self._inv_fns:
            inv_fn = self._jits.get("verify_inv")
            if inv_fn is None:
                inv_fn = jax.jit(self._inv_ok)
                self._jits["verify_inv"] = inv_fn
        take = lambda tree, i: jax.tree.map(lambda x: x[i], tree)
        cur = s0
        seq = [s0] + [take(states, j) for j in range(n_steps)]
        # transition checks along the non-stutter chain
        for j in range(n_steps):
            lane = int(lane_log[j])
            nxt = seq[j + 1]
            if lane < 0:
                cur = nxt
                continue
            succ, valid = succ_fn(cur)
            if not bool(np.asarray(valid)[lane]):
                return False
            want = jax.tree.map(lambda x: np.asarray(x)[lane], succ)
            got = jax.tree.map(np.asarray, nxt)
            eq = all(
                np.array_equal(a, b)
                for a, b in zip(
                    jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got),
                )
            )
            if not eq:
                return False
            cur = nxt
        if inv_fn is None:
            return True
        # the violated invariant: True everywhere but the final state
        for j, s in enumerate(seq):
            ok = bool(np.asarray(inv_fn(s))[inv_idx])
            if j < len(seq) - 1 and not ok:
                return False
            if j == len(seq) - 1 and ok:
                return False
        return True
