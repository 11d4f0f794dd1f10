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
- **Behaviours on request** (PR 52; TLC's ``-simulate file=F,num=N``).
  After a budget that ends on a round boundary, ``dump_num`` walkers
  of the last completed round are replayed by the same program and
  written as counterexamples are rendered; each replay's last state is
  compared on the device with the state the timed scan carried for
  that walker (``sim_dump_mismatches``, 0 in a sound run).
- **A step's memory does not grow with lanes times swarm** (PR 52):
  the scan's step walks the swarm in chunks of walkers (one module
  constant a form of the step, below), and the programs are
  module-level units whose static argument is a value
  (``SimKernel``), the seed's keys traced arguments: a second
  simulation of one binding traces nothing.
- **A walker's step builds the ONE successor it drew** (PR 53):
  choose, then apply.  ``model.successors`` is called for ``valid``
  alone, the draw picks a lane, and where the model has
  ``successor_at(state, lane)`` (the optional method of the model
  protocol, docs/simulation.md) it builds that lane's successor and
  no other: the DRAWN form of the step.  A model without it keeps the
  LANES form, every lane's successor built and the drawn one taken by
  a one-hot masked sum.  The two forms share the draw and are one
  walk stream; ``run_header.sim_step_form`` names the form and
  ``sim_drawn_steps`` counts the steps so built.
- **Survivability.**  Checkpoint frames carry (walker states, epoch,
  dup table, cumulative counters, a keys-digest over the PRNG
  position) so kill/SIGTERM/suspend resume continues the IDENTICAL
  walk stream; the daemon time-slices simulation jobs through the
  same cooperative ``suspend_hook`` as BFS jobs.

Telemetry: schema v11 ``sim`` records (cumulative steps / walkers /
violations + the estimator), ``run_header.mode = "simulate"``, the
standard ckpt_frame/fault/result records, heartbeat walks/s.  Tracing
(``obs/spans.py``): stage scopes ``ptt.sim_init`` / ``sim_expand`` /
``sim_choose`` / ``sim_inv`` / ``sim_dup`` / ``sim_replay``, a
``PhaseClock`` over ``spans.SIM_PHASES`` under one ``ptt:run``, the
compile meter's ``jit_*`` in ``result.stats``.
"""

from __future__ import annotations

import contextlib
import hashlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.engine import units
from pulsar_tlaplus_tpu.obs import spans
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

# walkers one step expands at once, a constant a form of the step.
#
# The LANES form (a model without ``successor_at``): ``model.successors``
# builds every lane's successor of every walker before the draw picks
# one, and XLA materialises them: at the scaled compaction binding (34
# lanes, 592 B a state) 20 KB of transient a walker, 44 KB where they
# are gathered from (the parent's step: 11.5 GB at 262,144 walkers by
# the TPU compiler's own reckoning, and a refusal at 2^20; PERF.md 6,
# PR 52).  So the scan's step walks the swarm in chunks of this many
# (``lax.map``), as ``DeviceChecker`` cuts its expand into
# ``expand_chunk``: the transient is this many walkers' whatever the
# swarm's width, and the walk stream is the unchunked one bit for bit
# (every walker's key is a function of (seed, step, walker) alone).
SIM_STEP_CHUNK = 1 << 14

# The DRAWN form (the model builds the drawn lane's successor alone):
# no lane axis, so the 20 KB a walker above are not this form's; a
# walker's transient is what the model's own actions and invariants
# hold (0.7 GB of temporaries at 262,144 walkers, 2.8 GB at 2^20, by
# the TPU compiler's reckoning).  Chosen again on the chip at 262,144
# walkers (PERF.md 6, PR 53), by two rules before speed: a traced 40 s
# window of the benchmark's cell has to stay under the 6.29M device
# events the profiler keeps (a step is 5,466 events in chunks of 4,096,
# 2,295 at 16,384, 974 at 65,536, 672 as ONE chunk of the swarm's own
# width and 502 with no chunk loop at all: the two smallest do not
# fit), and 2^20 walkers have to run (they do at every chunk).  Then
# speed, a check of four rounds: 5.44 s, 5.68, 6.62, 5.85 and 5.42.  A
# chunk ABOVE the swarm's width leaves ``lax.map`` no loop (at the
# width itself it leaves a loop of one turn, whose slicing and stacking
# stay), so this is the widest swarm measured: up to 2^20 walkers the
# step is unchunked (a round of 2^20 in 7.8 s, 9.1 in chunks of 2^18).
SIM_DRAWN_STEP_CHUNK = 1 << 20


def step_form(model) -> str:
    """``"drawn"`` where the model has ``successor_at(state, lane)``
    (docs/simulation.md), else ``"lanes"``: what the step adapts on."""
    has = getattr(model, "successor_at", None) is not None
    return "drawn" if has else "lanes"


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


def _draw(k, probs):
    """``jax.random.choice(k, len(probs), p=probs)``, draw for draw:
    the same cumulative sum, the same uniform, and the index of the
    first entry that reaches it — counted (entries below it) where
    ``choice`` binary-searches.  On a non-decreasing array the two are
    the same number, but the search is a loop of per-walker gathers,
    which on the chip were 38% of a step's device seconds (PERF.md 6,
    PR 52); ``tests/test_sim_cell.py`` holds the two equal."""
    p_cuml = jnp.cumsum(probs)
    r = p_cuml[-1] * (1 - jax.random.uniform(k, (), p_cuml.dtype))
    return jnp.sum(p_cuml < r, dtype=jnp.int32)


class SimKernel(NamedTuple):
    """What the simulator's programs read, by value: the model (hand
    models hash by their constants, ``models.ByConstants``), the
    invariants and the swarm's shape.  It is the static argument of
    the module-level units below, so a second simulation of one
    binding and shape in a process, whatever its seed (the two base
    keys are traced arguments), traces, lowers and loads nothing —
    the single-chip engine's rule since PR 33 (``engine/units.py``)."""

    model: object
    invariant_names: Tuple[str, ...]
    B: int   # walkers
    T: int   # depth: steps a behaviour
    L: int   # steps a segment (divides T)
    S: int   # walkers the duplicate estimator samples
    dup_table_bits: int
    chunk: int  # the form's chunk (a value a body reads is in its key)

    @property
    def A(self) -> int:
        return int(self.model.A)

    @property
    def form(self) -> str:
        """``"drawn"``: a step builds the drawn lane's successor alone
        (the model has ``successor_at``); ``"lanes"``: it picks it from
        all ``A``."""
        return step_form(self.model)

    def _pick(self, succ, lane_c):
        """``succ[lane_c]`` of every leaf by a one-hot masked sum over
        the lane axis, not by the gather: the same values bit for bit
        (every leaf is an integer), but under vmap the gather makes XLA
        lay every lane's successor out walker-major and pad it to the
        (8,128) tile (44 KB a walker at the scaled compaction binding
        where the lanes hold 20), while the sum reads them walker-minor
        (PERF.md 6, PR 52)."""
        A = self.A
        drawn = jnp.arange(A, dtype=jnp.int32) == lane_c

        def pick(s):
            mask = drawn.reshape((A,) + (1,) * (s.ndim - 1))
            if s.dtype == jnp.bool_:
                return jnp.any(mask & s, axis=0)
            return jnp.sum(
                jnp.where(mask, s, jnp.zeros((), s.dtype)),
                axis=0, dtype=s.dtype,
            )

        return jax.tree.map(pick, succ)

    def init_one(self, k):
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

    def step_one(self, state, k, stage=spans.stage):
        """One random step of one walker: uniform over enabled lanes
        plus the stutter lane (TLC behavior-space semantics; no
        enabled lane at all -> stay put).  Returns (next_state, lane
        or -1 for stutter, enabled-lane count).  ``stage`` opens the
        stage scopes of the timed step (``ptt.sim_expand``,
        ``ptt.sim_choose``); the replay passes none, so that its
        operations stay under its own ``ptt.sim_replay``."""
        m, A = self.model, self.A
        with stage("sim_expand"):
            succ, valid = m.successors(state)
            stutter = m.stutter_enabled(state)
        with stage("sim_choose"):
            weights = jnp.concatenate(
                [
                    valid.astype(jnp.float32),
                    stutter.astype(jnp.float32)[None],
                ]
            )
            total = jnp.sum(weights)
            fallback = jnp.zeros((A + 1,)).at[A].set(1.0)
            probs = jnp.where(
                total > 0, weights / jnp.maximum(total, 1.0), fallback
            )
            lane = _draw(k, probs)
            is_stutter = lane >= A
            lane_c = jnp.minimum(lane, A - 1)
            if self.form == "drawn":
                # choose, then apply: the model builds the drawn lane's
                # successor alone; ``succ`` above is dead code the
                # compiler drops, and ``valid`` all the draw needs
                got = m.successor_at(state, lane_c)
            else:
                got = self._pick(succ, lane_c)
            nxt = jax.tree.map(
                lambda cur, g: jnp.where(is_stutter, cur, g), state, got
            )
            n_enabled = jnp.sum(valid.astype(jnp.uint32)) + (
                stutter.astype(jnp.uint32)
            )
            return (
                nxt,
                jnp.where(is_stutter, -1, lane_c).astype(jnp.int32),
                n_enabled,
            )

    def advance_one(self, state, k):
        """One walker's step and the invariants of the state it lands
        on: what the scan's step runs for every walker of a chunk."""
        nxt, lane, n_en = self.step_one(state, k)
        with spans.stage("sim_inv"):
            ok = self.inv_ok(nxt)
        return nxt, lane, n_en, ok

    def inv_ok(self, state):
        """bool[n_inv] — True = satisfied."""
        if not self.invariant_names:
            return jnp.ones((0,), bool)
        return jnp.stack(
            [self.model.invariants[n](state) for n in self.invariant_names]
        )

    def fingerprints(self, states_sub):
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

    def dup_insert(self, table, states):
        """Hash the walker subsample into the fixed estimator table;
        returns (table, hits).  No dedup — advisory sampling only."""
        sub = jax.tree.map(lambda x: x[: self.S], states)
        h = self.fingerprints(sub)
        idx = (h >> jnp.uint32(32 - self.dup_table_bits)).astype(
            jnp.int32
        )
        tag = h | jnp.uint32(1)
        hits = jnp.sum((table[idx] == tag).astype(jnp.uint32))
        return table.at[idx].set(tag), hits

    def viol_update(self, ctrs, ok, code):
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

    def segment(self, states, table, epoch, k_init, k_step, restart):
        """The segment megakernel: (states, table, epoch, the two base
        keys) -> (states, table, counters).  ``restart`` is a STATIC
        flag — the variant that opens a fresh behavior round draws new
        initial states before the step scan (restarts only ever land
        at segment boundaries because segment_len divides depth)."""
        widx = jnp.arange(self.B, dtype=jnp.uint32)
        ctrs = jnp.zeros((CTR_N,), jnp.uint32).at[CTR_VKEY].set(_CLEAN)
        g0 = epoch.astype(jnp.int32) * jnp.int32(self.L)
        if restart:
            with spans.stage("sim_init"):
                kr = jax.random.fold_in(k_init, g0)
                keys = jax.vmap(lambda w: jax.random.fold_in(kr, w))(
                    widx
                )
                states = jax.vmap(self.init_one)(keys)
            with spans.stage("sim_inv"):
                ok0 = jax.vmap(self.inv_ok)(states)
                ctrs = self.viol_update(ctrs, ok0, jnp.uint32(0))
            with spans.stage("sim_dup"):
                table, hits = self.dup_insert(table, states)
                ctrs = ctrs.at[CTR_DUP_ATT].add(jnp.uint32(self.S))
                ctrs = ctrs.at[CTR_DUP_HITS].add(hits)

        def step(carry, i):
            st, tbl, c = carry
            g = g0 + i
            with spans.stage("sim_choose"):
                ks = jax.random.fold_in(k_step, g)
                keys = jax.vmap(lambda w: jax.random.fold_in(ks, w))(
                    widx
                )
                # the swarm in chunks of SIM_STEP_CHUNK walkers, one
                # after another (a swarm no wider is one chunk); the
                # loop's own slicing and stacking read under this
                # scope, a chunk's work under its own inner ones
                nxt, lanes, n_en, ok = jax.lax.map(
                    lambda a: self.advance_one(*a), (st, keys),
                    batch_size=self.chunk,
                )
                en = jnp.sum(n_en, dtype=jnp.uint32)
                lo = c[CTR_EN_LO] + en
                c = c.at[CTR_EN_HI].add(
                    (lo < c[CTR_EN_LO]).astype(jnp.uint32)
                )
                c = c.at[CTR_EN_LO].set(lo)
                c = c.at[CTR_STUTTER].add(
                    jnp.sum((lanes < 0).astype(jnp.uint32))
                )
            with spans.stage("sim_inv"):
                c = self.viol_update(
                    c, ok, (2 * i + 1).astype(jnp.uint32)
                )
            with spans.stage("sim_dup"):
                tbl, hits = self.dup_insert(tbl, nxt)
                c = c.at[CTR_DUP_ATT].add(jnp.uint32(self.S))
                c = c.at[CTR_DUP_HITS].add(hits)
            return (nxt, tbl, c), None

        (states, table, ctrs), _ = jax.lax.scan(
            step, (states, table, ctrs),
            jnp.arange(self.L, dtype=jnp.int32),
        )
        return states, table, ctrs

    def replay_one(self, w, r0, k_init, k_step):
        """Walker ``w``'s behaviour of the round that starts at step
        ``r0``, from its key stream alone: (s0, states [T], lanes
        [T])."""
        kw = jax.random.fold_in(jax.random.fold_in(k_init, r0), w)
        s0 = self.init_one(kw)
        unscoped = lambda _name: contextlib.nullcontext()

        def step(s, j):
            ks = jax.random.fold_in(
                jax.random.fold_in(k_step, r0 + j), w
            )
            nxt, lane, _n = self.step_one(s, ks, unscoped)
            return nxt, (nxt, lane)

        _, (states, lanes) = jax.lax.scan(
            step, s0, jnp.arange(self.T, dtype=jnp.int32)
        )
        return s0, states, lanes


# The simulator's programs: module-level units (engine/units.py), the
# kernel their static argument.  Scopes are HLO metadata and no part of
# the persistent cache's key, hence the ptt_ names (obs/spans.py).


@units.unit(static=("k", "restart"), donate=(0, 1))
def ptt_sim_segment(states, table, epoch, k_init, k_step, *, k, restart):
    return k.segment(states, table, epoch, k_init, k_step, restart)


@units.unit(scope="sim_replay", static=("k",))
def ptt_sim_replay(ws, r0, k_init, k_step, *, k):
    """(walkers u32[K], round start) -> every state and lane of those
    walkers' behaviours of that round: ``(s0 [K], states [K, depth],
    lanes [K, depth])``."""
    return jax.vmap(k.replay_one, in_axes=(0, None, None, None))(
        ws, r0, k_init, k_step
    )


@units.unit(scope="sim_replay")
def ptt_sim_replay_check(replayed, ws, swarm):
    """(replayed states [K, depth], walkers [K], the swarm's states
    [B]) -> how many of the K replays end in another state than the
    one the timed scan carried for that walker."""
    differs = jnp.zeros(ws.shape, bool)
    for got, want in zip(
        jax.tree_util.tree_leaves(replayed),
        jax.tree_util.tree_leaves(swarm),
    ):
        ne = got[:, -1] != want[ws]
        differs |= jnp.any(ne.reshape(ne.shape[0], -1), axis=1)
    return jnp.sum(differs.astype(jnp.uint32))


class DumpBudgetError(ValueError):
    """``dump_path`` with a budget that need not end on a round
    boundary: there is no last completed round to dump."""


def _peak_bytes(states) -> Optional[int]:
    """``peak_bytes_in_use`` of the device the swarm lives on, where
    the backend reports it (the CPU does not)."""
    dev = next(iter(jax.tree_util.tree_leaves(states)[0].devices()))
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


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
    # steps/s of the dispatches after each program's first (which
    # traces, lowers and loads it): None where there was none
    steady_steps_per_sec: Optional[float] = None
    dump_files: List[str] = field(default_factory=list)
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
        dump_path: Optional[str] = None,
        dump_num: int = 16,
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
        # behaviours on request (TLC's -simulate file=F,num=N): after
        # the budget, dump_num walkers of the LAST completed round are
        # replayed and written to <dump_path>_<round>_<walker>; the
        # budget has to end where a round does
        self.dump_path = dump_path
        self.dump_num = max(1, min(int(dump_num), self.B))
        if dump_path and (
            time_budget_s is not None
            or (max_steps is not None and max_steps % (self.B * self.T))
        ):
            raise DumpBudgetError(
                "a behaviour dump needs a budget that ends on a round "
                "boundary: "
                + (
                    "a time budget ends anywhere"
                    if time_budget_s is not None
                    else f"{max_steps} steps is not a multiple of "
                    f"walkers x depth = {self.B * self.T}"
                )
            )
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
        self._keys = None
        # a run's own clock replaces this one: it takes what a warm-up
        # calls before any run
        self._clock = spans.PhaseClock()
        drawn = step_form(model) == "drawn"
        self.k = SimKernel(
            model, self.invariant_names, self.B, self.T, self.L,
            self.S, self.dup_table_bits,
            SIM_DRAWN_STEP_CHUNK if drawn else SIM_STEP_CHUNK,
        )

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

    # ------------------------------------------------------ programs

    def _bases(self):
        """The walk stream's two base keys (initial states, steps):
        traced arguments of every program, so the seed is no part of a
        program's identity."""
        if self._keys is None:
            self._keys = tuple(
                jax.random.split(jax.random.PRNGKey(self.seed))
            )
        return self._keys

    def _segment(self, states, table, epoch: int, restart: bool):
        bases = self._bases()
        with self._clock.upload("ptt_sim_segment", 1):
            epoch_d = jnp.int32(epoch)
        with self._clock.call("ptt_sim_segment"):
            return ptt_sim_segment(
                states, table, epoch_d, *bases, k=self.k, restart=restart
            )

    def _replay(self, walkers, r0: int):
        """``ptt_sim_replay`` of walkers (u32[K]) of the round that
        starts at step ``r0``."""
        bases = self._bases()
        with self._clock.upload("ptt_sim_replay", 1):
            r0_d = jnp.int32(r0)
        with self._clock.call("ptt_sim_replay"):
            return ptt_sim_replay(walkers, r0_d, *bases, k=self.k)

    def warmup(self) -> float:
        """Compile both segment variants up front; returns wall
        seconds spent (the daemon's sim pool calls this once)."""
        t0 = time.perf_counter()
        states, table = self._fresh_buffers()
        for restart in (True, False):
            s2, t2, c = self._segment(states, table, 0, restart)
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
                lambda: self.k.init_one(jax.random.PRNGKey(0))
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
            lambda: self.k.init_one(jax.random.PRNGKey(0))
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
            sim_step_form=self.k.form,
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
        # this run's exclusive host phases (spans.SIM_PHASES) and the
        # compile meter's reading before it, as DeviceChecker.run()
        # keeps its own; ptt:run is the container the phases lie in
        clock = self._clock = spans.PhaseClock(obs.new_run_id())
        self._jit0 = spans.compile_meter().snapshot()
        with spans.span("run", run_id=clock.run_id):
            return self._run(resume)

    def _run(self, resume: bool) -> SimulationResult:
        rid = self._clock.run_id
        self.tel = obs.as_telemetry(self._telemetry_arg, run_id=rid)
        self._run_id = self._clock.run_id = self.tel.run_id or rid
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
        clock = self._clock
        resume_meta: dict = {}
        with clock.phase("init"):
            if resume:
                if not self.checkpoint_path:
                    raise ValueError(
                        "resume=True needs a checkpoint_path"
                    )
                states, table, epoch, cum, prior_wall, resume_meta = (
                    self._load_frame()
                )
            else:
                states, table = self._fresh_buffers()
                epoch = 0
                cum = {
                    "steps": 0, "states": 0, "violations": 0,
                    "stutter": 0, "enabled": 0, "dup_att": 0,
                    "dup_hits": 0, "segments": 0,
                }
                prior_wall = 0.0
            # the wall a result reports: this run's clock and, on a
            # resume, what the frame's run had banked
            t0 = time.time() - clock.elapsed() - prior_wall
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
        # the dispatches after each program's first, which traces,
        # lowers and loads it: their steps and their seconds
        met, steady = set(), [0, 0.0]
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
                t_seg = time.perf_counter()
                with clock.phase("dispatch", level=epoch):
                    states, table, ctrs = self._segment(
                        states, table, epoch, restart
                    )
                with clock.phase("fetch", level=epoch):
                    c = np.asarray(ctrs)  # THE one fetch per dispatch
                if restart in met:
                    steady[0] += self.B * self.L
                    steady[1] += time.perf_counter() - t_seg
                met.add(restart)
                with clock.phase("account", level=epoch):
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
                    with clock.phase("ckpt", level=epoch):
                        self._save_frame(states, table, epoch, cum, wall)
        wall = time.time() - t0
        if stop_reason in ("suspended", "preempted"):
            with clock.phase("ckpt", level=epoch):
                self._save_frame(states, table, epoch, cum, wall)
            self._log(
                f"simulation {stop_reason} at epoch {epoch} "
                f"({cum['steps']} steps banked)"
            )
        dumped: List[str] = []
        mismatches = 0
        if (
            self.dump_path
            and stop_reason in ("step_budget", "round_budget")
            and epoch >= self.segs_per_round
        ):
            with clock.phase("dump", level=epoch):
                dumped, mismatches = self._dump_behaviours(states, epoch)
        with clock.phase("result"):
            res = self._mk_result(
                cum, epoch, truncated=truncated, stop_reason=stop_reason
            )
            if steady[1] > 0:
                res.steady_steps_per_sec = round(steady[0] / steady[1], 1)
            res.dump_files = dumped
            if viol is not None:
                self._attach_violation(res, viol)
            leaves = [
                np.asarray(x) for x in jax.tree_util.tree_leaves(states)
            ]
            self.last_stats.update(
                sim_depth=self.T,
                sim_segment_len=self.L,
                sim_rounds=cum["steps"] // (self.B * self.T),
                sim_step_chunks=-(-self.B // self.k.chunk),
                sim_dump_s=clock.seconds_of("dump"),
                sim_dump_behaviours=len(dumped),
                sim_dump_mismatches=mismatches,
                sim_keys_digest=self._keys_digest(leaves, epoch),
                sim_peak_bytes=_peak_bytes(states),
                steady_steps_per_sec=res.steady_steps_per_sec,
                **spans.compile_meter().since(self._jit0),
            )
            self._set_rates(res, t0)
        self.last_stats.update(clock.host_seconds(spans.SIM_PHASES))
        self.last_stats.update(clock.call_stats())
        self._emit_result(res)
        return res

    def _dump_behaviours(self, states, epoch) -> Tuple[List[str], int]:
        """Replay ``dump_num`` walkers of the last completed round,
        spread evenly over the swarm and rotated by the seed, and write
        each behaviour (every state, the self-loop steps too) to
        ``<dump_path>_<round>_<walker>`` as a counterexample is
        printed.  Each replay's last state is compared on the device
        with the state the timed scan carried for that walker; returns
        the files and how many differed (0 in a sound run)."""
        from pulsar_tlaplus_tpu.utils.render import render_trace

        k, b = self.dump_num, self.B
        ws = [(i * b // k + self.seed) % b for i in range(k)]
        r0 = (epoch - self.segs_per_round) * self.L
        rnd = epoch // self.segs_per_round  # rounds completed
        with self._clock.upload("ptt_sim_replay", 1):
            ws_dev = jnp.asarray(ws, jnp.uint32)
        s0, replayed, lanes = self._replay(ws_dev, r0)
        with self._clock.call("ptt_sim_replay_check"):
            mismatches = ptt_sim_replay_check(replayed, ws_dev, states)
        s0, replayed, lanes = jax.tree.map(
            np.asarray, (s0, replayed, lanes)
        )
        files = []
        for i, w in enumerate(ws):
            trace, actions = self._behaviour(
                *jax.tree.map(lambda x: x[i], (s0, replayed, lanes)),
                self.T, keep_stutter=True,
            )
            path = f"{self.dump_path}_{rnd}_{w}"
            with open(path, "w", encoding="utf-8") as f:
                f.write(
                    render_trace(
                        trace, actions, getattr(self.model, "c", None)
                    )
                )
            files.append(path)
        return files, int(mismatches)

    def _behaviour(self, s0, states, lanes, n_steps, keep_stutter):
        """The first ``n_steps`` steps of one replayed walker (host
        arrays) as (states, action names).  A counterexample drops the
        self-loop steps (the state does not change); a dumped
        behaviour keeps them, each under the name the model gives the
        disjunct (``stutter_action``)."""
        m = self.model
        names = getattr(m, "action_names", ())
        action_ids = getattr(m, "action_ids", None)
        stutter_action = getattr(
            m, "stutter_action", lambda _ps: "Stuttering"
        )
        trace = [m.to_pystate(s0)]
        actions: List[str] = []
        for step in range(n_steps):
            lane = int(lanes[step])
            if lane < 0:
                if keep_stutter:
                    actions.append(stutter_action(trace[-1]))
                    trace.append(trace[-1])
                continue
            trace.append(
                m.to_pystate(jax.tree.map(lambda x: x[step], states))
            )
            aid = (
                int(action_ids[lane]) if action_ids is not None else lane
            )
            actions.append(names[aid] if aid < len(names) else str(aid))
        return trace, actions

    def _drawn_steps(self, cum) -> int:
        """Walker-steps whose successor the model built alone
        (``successor_at``): every step in the drawn form, none in the
        lanes form.  Host-derived, as ``steps`` is."""
        return cum["steps"] if self.k.form == "drawn" else 0

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
            drawn_steps=self._drawn_steps(cum),
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
        self, cum, epoch, truncated: bool, stop_reason
    ) -> SimulationResult:
        """The result and its counters; ``_set_rates`` closes it with
        the wall and the rates once the rest of the run's work (a
        violation's replay, the digest) is done."""
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
            truncated=truncated,
            stop_reason=stop_reason,
            dup_ratio_est=dup,
        )
        res.stats = self.last_stats
        self.last_stats.update(
            sim_steps=cum["steps"],
            sim_drawn_steps=self._drawn_steps(cum),
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
            steps_per_state=(
                round(cum["steps"] / cum["states"], 4)
                if cum["states"]
                else None
            ),
            stats_fetches=self._fetch_n,
        )
        return res

    def _set_rates(self, res: SimulationResult, t0) -> None:
        wall = max(time.time() - t0, 1e-9)
        res.wall_s = round(wall, 3)
        res.steps_per_sec = round(res.steps / wall, 1)
        res.walks_per_sec = round(res.walks / wall, 2)
        res.states_per_sec = round(res.states_visited / wall, 1)
        self.last_stats.update(
            walks_per_sec=res.walks_per_sec,
            steps_per_sec=res.steps_per_sec,
        )

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
        with self._clock.upload("ptt_sim_replay", 1):
            w_dev = jnp.asarray([walker], jnp.uint32)
        s0, states, lanes = jax.tree.map(
            lambda x: x[0], self._replay(w_dev, r0)
        )
        lane_log = np.asarray(lanes)
        res.trace, res.trace_actions = self._behaviour(
            *jax.tree.map(np.asarray, (s0, states)), lane_log, n_steps,
            keep_stutter=False,
        )
        res.verified = self._verify_replay(
            s0, states, lane_log, n_steps, inv_idx
        )
        self.tel.emit(
            "sim_violation",
            invariant=res.violation,
            walker=walker,
            step=res.violation_step,
            trace_len=len(res.trace),
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
        if self.invariant_names:
            inv_fn = self._jits.get("verify_inv")
            if inv_fn is None:
                inv_fn = jax.jit(self.k.inv_ok)
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
