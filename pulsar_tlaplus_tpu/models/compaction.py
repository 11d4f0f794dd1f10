"""TPU-native compiled model of the ``compaction`` spec.

This module is the hand-compiled equivalent of what the spec front end
(SURVEY.md §2.2-E1) will eventually generate from ``compaction.tla``: one
vectorizable kernel per action (compaction.tla:216-231), invariant kernels
(compaction.tla:236-294), and initial-state generation (compaction.tla:188-202),
all over the compressed ``SState`` encoding of :mod:`..ops.packing`.

Action lanes: successor generation returns a *static* branch axis ``A`` of
``(valid, state')`` lanes — the Producer's ``\\E inputKey, inputValue``
nondeterminism (compaction.tla:85) becomes ``|KeySet|*|ValueSet|`` enumerated
lanes; the six compactor phases and BrokerCrash are one lane each.  The two
stuttering disjuncts (Consumer, compaction.tla:185-186; Terminating,
compaction.tla:205-214) produce no new states and are exposed only as
enabledness flags for deadlock checking, exactly as TLC treats self-loops.

All kernels are pure functions of a single ``SState``; batch via ``jax.vmap``.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.models import ByConstants
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ops.packing import Layout, SState
from pulsar_tlaplus_tpu.ref import pyeval
from pulsar_tlaplus_tpu.ref.pyeval import Constants


class CompactionModel(ByConstants):
    """Compiled ``compaction`` spec for a fixed ``Constants`` binding."""

    def __init__(self, c: Constants):
        c.validate()
        self.c = c
        self.layout = Layout(c)
        self.M = c.message_sent_limit
        self.C = c.compaction_times_limit
        self.MW = self.layout.MW
        # Producer branch fanout: |KeySet| * |ValueSet| (compaction.tla:85).
        self.kv = (c.num_keys + 1) * (c.num_values + 1)
        self.n_producer_lanes = self.kv if c.model_producer else 0
        # Lane -> pyeval action id (pyeval.ACTION_NAMES order).
        self.action_ids = np.array(
            [0] * self.n_producer_lanes + [1, 2, 3, 4, 5, 6, 7], dtype=np.int32
        )
        self.A = len(self.action_ids)
        # generic engine protocol (engine/core.py, engine/liveness.py)
        self.action_names = pyeval.ACTION_NAMES
        self.default_invariants = pyeval.DEFAULT_INVARIANTS
        self._pos = jnp.arange(1, self.M + 1, dtype=jnp.int32)  # [M], 1-based
        self._kvals = jnp.arange(1, c.num_keys + 1, dtype=jnp.int32)  # [K]

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------

    def _max_led_id(self, led_present: jax.Array) -> jax.Array:
        """MaxCompactedLedgerId (compaction.tla:103-106); 0 if all Nil."""
        if self.C == 0:
            return jnp.int32(0)
        ids = jnp.arange(1, self.C + 1, dtype=jnp.int32)
        return jnp.max(ids * led_present)

    def _mask_bits(self, mask_words: jax.Array) -> jax.Array:
        """u32[MW] -> bool[M] (bit j-1 = position j kept)."""
        idx = np.arange(self.M)
        shifts = jnp.asarray(idx % 32, jnp.uint32)
        return ((mask_words[idx // 32] >> shifts) & 1).astype(jnp.bool_)

    def _bits_to_words(self, bits: jax.Array) -> jax.Array:
        """bool[M] -> u32[MW]."""
        padded = jnp.zeros((self.MW * 32,), jnp.uint32).at[: self.M].set(
            bits.astype(jnp.uint32)
        )
        shifted = padded.reshape(self.MW, 32) << jnp.arange(32, dtype=jnp.uint32)
        return shifted.sum(axis=1, dtype=jnp.uint32)

    def _latest_per_key(
        self, keys: jax.Array, sel: jax.Array
    ) -> jax.Array:
        """latestForKey as a dense [K] vector: latest[k-1] = max position i
        (1-based) with ``keys[i] = k`` among selected positions, else 0.

        O(M*K) — replaces the O(M^2) pairwise form (the dominant per-lane
        cost at the |Msgs|=64 stress config; K=|KeySet| is small)."""
        hit = (keys[None, :] == self._kvals[:, None]) & sel[None, :]  # [K, M]
        return jnp.max(jnp.where(hit, self._pos[None, :], 0), axis=1)  # [K]

    def _lookup_per_key(self, table_k: jax.Array, keys: jax.Array) -> jax.Array:
        """table_k[K] indexed by each position's key: out[i] = table_k[keys[i]-1]
        (0 where keys[i] = 0).  One-hot contraction, O(M*K)."""
        onehot = keys[None, :] == self._kvals[:, None]  # [K, M]
        return jnp.sum(jnp.where(onehot, table_k[:, None], 0), axis=0)

    def _compact_keep(self, keys: jax.Array, readpos: jax.Array) -> jax.Array:
        """CompactMessages as a position mask (compaction.tla:107-119).

        keep[i] over 1..readPosition: null-key kept iff RetainNullKey;
        otherwise kept iff i is the last occurrence of its key in the prefix
        (== ``latestForKey[key]``, compaction.tla:98,114).  O(M*K).
        """
        pos = self._pos
        in_range = pos <= readpos
        latest = self._latest_per_key(keys, in_range)  # [K]
        is_latest = (
            in_range & (keys != 0) & (self._lookup_per_key(latest, keys) == pos)
        )
        null_keep = in_range & (keys == 0) & self.c.retain_null_key
        return is_latest | null_keep

    # ------------------------------------------------------------------
    # initial states (compaction.tla:188-202)
    # ------------------------------------------------------------------

    @property
    def n_initial(self) -> int:
        if self.c.model_producer:
            return 1
        return self.kv ** self.M

    def gen_initial(self, idx: jax.Array) -> SState:
        """Initial state #idx (mixed-radix decode of the Init fanout).

        With ModelProducer=FALSE, Init draws ``messages`` from all
        id-consistent length-M sequences (compaction.tla:191-194); state #idx
        has position i's (key, value) given by digit i of idx in base
        ``|KeySet|*|ValueSet|``.  With ModelProducer=TRUE there is a single
        initial state with ``messages = <<>>`` (compaction.tla:189-190).
        """
        zero = jnp.int32(0)
        if self.c.model_producer:
            length = zero
            keys = jnp.zeros((self.M,), jnp.int32)
            vals = jnp.zeros((self.M,), jnp.int32)
        else:
            digits = []
            x = idx.astype(jnp.int32)
            for _ in range(self.M):
                digits.append(x % self.kv)
                x = x // self.kv
            d = jnp.stack(digits) if self.M else jnp.zeros((0,), jnp.int32)
            keys = d // (self.c.num_values + 1)
            vals = d % (self.c.num_values + 1)
            length = jnp.int32(self.M)
        return SState(
            length=length,
            keys=keys,
            vals=vals,
            led_present=jnp.zeros((self.C,), jnp.int32),
            led_mask=jnp.zeros((self.C, self.MW), jnp.uint32),
            cursor_present=zero,
            cursor_h=zero,
            cursor_c=zero,
            cstate=jnp.int32(pyeval.PHASE_ONE),
            p1_present=zero,
            p1_readpos=zero,
            horizon=zero,
            context=zero,
            crash=zero,
            consume=zero,
        )

    def sample_initial(self, k) -> SState:
        """Uniform random initial state (simulation mode protocol).

        Samples each position's (key, value) digit directly — uniform over
        the Init fanout without materializing ``n_initial``, which
        overflows any machine int at large MessageSentLimit."""
        if self.c.model_producer:
            return self.gen_initial(jnp.int32(0))
        digits = jax.random.randint(k, (self.M,), 0, self.kv, jnp.int32)
        base = self.gen_initial(jnp.int32(0))
        return base._replace(
            keys=digits // (self.c.num_values + 1),
            vals=digits % (self.c.num_values + 1),
        )

    # ------------------------------------------------------------------
    # actions (compaction.tla:216-231); each returns (valid, successor)
    # ------------------------------------------------------------------

    def _producer(self, s: SState, key, val) -> Tuple[jax.Array, SState]:
        """Producer, one (inputKey, inputValue) lane (compaction.tla:83-87).
        ``key``/``val`` may be Python ints or traced i32 scalars (the
        vmapped lane axis in :meth:`successors`)."""
        valid = s.length < self.M
        at_new = self._pos == s.length + 1
        return valid, s._replace(
            length=s.length + 1,
            keys=jnp.where(at_new, jnp.asarray(key, jnp.int32), s.keys),
            vals=jnp.where(at_new, jnp.asarray(val, jnp.int32), s.vals),
        )

    def _phase_one(self, s: SState) -> Tuple[jax.Array, SState]:
        """CompactorPhaseOne (compaction.tla:93-100).  latestForKey is not
        materialized — it is derivable from (messages, readPosition); only
        the snapshot position is recorded (see ops/packing.py docstring)."""
        valid = (
            (s.cstate == pyeval.PHASE_ONE) & (s.p1_present == 0) & (s.length > 0)
        )
        return valid, s._replace(
            p1_present=jnp.int32(1),
            p1_readpos=s.length,
            cstate=jnp.int32(pyeval.PHASE_TWO_WRITE),
        )

    def _phase_two_write(self, s: SState) -> Tuple[jax.Array, SState]:
        """CompactorPhaseTwoWrite (compaction.tla:121-132)."""
        max_id = self._max_led_id(s.led_present)
        new_id = max_id + 1
        valid = (
            (s.p1_present == 1)
            & (s.cstate == pyeval.PHASE_TWO_WRITE)
            & (new_id <= self.C)
        )
        keep = self._compact_keep(s.keys, s.p1_readpos)
        words = self._bits_to_words(keep)
        slot = jnp.clip(new_id - 1, 0, max(self.C - 1, 0))
        slot_onehot = jnp.arange(self.C, dtype=jnp.int32) == slot
        return valid, s._replace(
            led_present=jnp.where(slot_onehot, 1, s.led_present),
            led_mask=jnp.where(slot_onehot[:, None], words[None, :], s.led_mask),
            cstate=jnp.int32(pyeval.PHASE_TWO_UPDATE_CONTEXT),
        )

    def _update_context(self, s: SState) -> Tuple[jax.Array, SState]:
        """CompactorPhaseTwoUpdateContext (compaction.tla:135-139)."""
        valid = s.cstate == pyeval.PHASE_TWO_UPDATE_CONTEXT
        return valid, s._replace(
            context=self._max_led_id(s.led_present),
            cstate=jnp.int32(pyeval.PHASE_TWO_UPDATE_HORIZON),
        )

    def _update_horizon(self, s: SState) -> Tuple[jax.Array, SState]:
        """CompactorPhaseTwoUpdateHorizon (compaction.tla:141-145)."""
        valid = s.cstate == pyeval.PHASE_TWO_UPDATE_HORIZON
        return valid, s._replace(
            horizon=s.p1_readpos,
            cstate=jnp.int32(pyeval.PHASE_TWO_PERSIST_CURSOR),
        )

    def _persist_cursor(self, s: SState) -> Tuple[jax.Array, SState]:
        """CompactorPhaseTwoPersistCusror [sic] (compaction.tla:147-151)."""
        valid = s.cstate == pyeval.PHASE_TWO_PERSIST_CURSOR
        return valid, s._replace(
            cursor_present=jnp.int32(1),
            cursor_h=s.horizon,
            cursor_c=s.context,
            cstate=jnp.int32(pyeval.PHASE_TWO_DELETE_LEDGER),
        )

    def _delete_ledger(self, s: SState) -> Tuple[jax.Array, SState]:
        """CompactorPhaseTwoDeleteLedger (compaction.tla:153-165): deletes the
        second-to-last compacted ledger (explicit simplification at
        compaction.tla:159), resets to PhaseOne, clears phaseOneResult."""
        valid = s.cstate == pyeval.PHASE_TWO_DELETE_LEDGER
        max_id = self._max_led_id(s.led_present)
        old_slot = jnp.clip(max_id - 2, 0, max(self.C - 1, 0))  # 0-based
        do_del = max_id >= 2
        onehot = (jnp.arange(self.C, dtype=jnp.int32) == old_slot) & do_del
        return valid, s._replace(
            led_present=jnp.where(onehot, 0, s.led_present),
            led_mask=jnp.where(onehot[:, None], jnp.uint32(0), s.led_mask),
            cstate=jnp.int32(pyeval.PHASE_ONE),
            p1_present=jnp.int32(0),
            p1_readpos=jnp.int32(0),
        )

    def _broker_crash(self, s: SState) -> Tuple[jax.Array, SState]:
        """BrokerCrash (compaction.tla:169-182): fault injection + recovery
        from the durable cursor (0/0 cold start when cursor = Nil)."""
        valid = s.crash < self.c.max_crash_times
        return valid, s._replace(
            crash=s.crash + 1,
            cstate=jnp.int32(pyeval.PHASE_ONE),
            p1_present=jnp.int32(0),
            p1_readpos=jnp.int32(0),
            horizon=jnp.where(s.cursor_present == 1, s.cursor_h, 0),
            context=jnp.where(s.cursor_present == 1, s.cursor_c, 0),
        )

    def successors(self, s: SState) -> Tuple[SState, jax.Array]:
        """All non-stuttering Next lanes: (stacked SState [A], valid [A]).

        The Producer's |KeySet|*|ValueSet| branches are one vmapped lane
        axis (traced once), not unrolled — at the stress config this cuts
        the traced graph ~4x, which is most of the XLA compile time."""
        lanes: List[Tuple[jax.Array, SState]] = [
            self._phase_one(s),
            self._phase_two_write(s),
            self._update_context(s),
            self._update_horizon(s),
            self._persist_cursor(s),
            self._delete_ledger(s),
            self._broker_crash(s),
        ]
        valid = jnp.stack([v for v, _ in lanes])
        succ = jax.tree.map(lambda *xs: jnp.stack(xs), *[t for _, t in lanes])
        if self.c.model_producer:
            kvs = jnp.arange(self.kv, dtype=jnp.int32)
            pvalid, psucc = jax.vmap(
                lambda kv: self._producer(
                    s,
                    kv // (self.c.num_values + 1),
                    kv % (self.c.num_values + 1),
                )
            )(kvs)
            valid = jnp.concatenate([pvalid, valid])
            succ = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), psucc, succ
            )
        return succ, valid

    def successor_at(self, s: SState, lane: jax.Array) -> SState:
        """``successors(s)[0][lane]`` alone, leaf for leaf and bit for
        bit, for a traced i32 ``lane`` in ``[0, A)``, enabled or not
        (simulation mode protocol: a walker builds the one successor it
        drew, docs/simulation.md).  The same action methods
        :meth:`successors` stacks, selected among field by field: a
        leaf no action replaces is ``s``'s own, and one an action
        replaces is a select on the lane, so no array here has a lane
        axis."""
        lane = jnp.asarray(lane, jnp.int32)
        n_prod = self.n_producer_lanes
        steps: List[Tuple[jax.Array, SState]] = [
            (lane == n_prod + j, act(s)[1])
            for j, act in enumerate(
                (
                    self._phase_one,
                    self._phase_two_write,
                    self._update_context,
                    self._update_horizon,
                    self._persist_cursor,
                    self._delete_ledger,
                    self._broker_crash,
                )
            )
        ]
        if n_prod:
            nv1 = self.c.num_values + 1
            produced = self._producer(s, lane // nv1, lane % nv1)[1]
            steps.append((lane < n_prod, produced))
        leaves = []
        for i, cur in enumerate(s):
            for drawn, t in steps:
                if t[i] is not s[i]:  # the action replaces this leaf
                    cur = jnp.where(drawn, t[i], cur)
            leaves.append(cur)
        return SState(*leaves)

    def stutter_enabled(self, s: SState) -> jax.Array:
        """Enabledness of the stuttering disjuncts, for deadlock checking.

        Consumer (compaction.tla:185-186, gate 229-230) and the Terminating
        self-loop (compaction.tla:205-214).
        """
        consumer = jnp.bool_(self.c.model_consumer)
        return consumer | self.termination_goal(s)

    def stutter_action(self, ps: pyeval.State) -> str:
        """The name of the stuttering disjunct a self-loop step from
        ``ps`` took (a dumped behaviour names every step)."""
        return (
            "Terminating"
            if pyeval.termination_goal(self.c, ps)
            else "Consumer"
        )

    def termination_goal(self, s: SState) -> jax.Array:
        """The body of the Termination liveness property
        (compaction.tla:303-307): producer done, compactor parked in
        PhaseTwoWrite with all ledger slots used, consumer done.  (Same
        condition as the Terminating guard, compaction.tla:205-214.)"""
        return (
            (s.length == self.M)
            & (s.cstate == pyeval.PHASE_TWO_WRITE)
            & (self._max_led_id(s.led_present) == self.C)
            & (
                (not self.c.model_consumer)
                | (s.consume == self.c.consume_times_limit)
            )
        )

    # ------------------------------------------------------------------
    # invariants (compaction.tla:236-294); True = satisfied
    # ------------------------------------------------------------------

    def type_safe(self, s: SState) -> jax.Array:
        """TypeSafe (compaction.tla:236-248)."""
        pos = self._pos
        live = pos <= s.length
        msgs_ok = jnp.all(
            ~live
            | (
                (s.keys >= 0)
                & (s.keys <= self.c.num_keys)
                & (s.vals >= 0)
                & (s.vals <= self.c.num_values)
            )
        )
        # Ledger entries are (id=position, key, value) drawn from messages:
        # well-typed iff every kept position is within the live prefix.
        led_ok = jnp.bool_(True)
        for cc in range(self.C):
            bits = self._mask_bits(s.led_mask[cc])
            in_prefix = jnp.all(~bits | live)
            absent_clean = (s.led_present[cc] == 1) | ~jnp.any(bits)
            led_ok = led_ok & in_prefix & absent_clean
        p1_ok = (s.p1_present == 0) | (
            (s.p1_readpos >= 1) & (s.p1_readpos <= s.length)
        )
        cursor_ok = (s.cursor_present == 0) | (
            (s.cursor_h >= 1)
            & (s.cursor_h <= self.M)
            & (s.cursor_c >= 1)
            & (s.cursor_c <= self.C)
        )
        ranges_ok = (
            (s.cstate >= 0)
            & (s.cstate <= 5)
            & (s.horizon >= 0)
            & (s.horizon <= self.M)
            & (s.context >= 0)
            & (s.context <= self.C)
            & (s.crash >= 0)
            & (s.crash <= self.c.max_crash_times)
        )
        return msgs_ok & led_ok & p1_ok & cursor_ok & ranges_ok

    def compacted_ledger_leak(self, s: SState) -> jax.Array:
        """CompactedLedgerLeak (compaction.tla:251-253): <= 2 live ledgers."""
        return jnp.sum(s.led_present) <= 2

    def _context_ledger_bits(self, s: SState) -> jax.Array:
        """bool[M] kept-position mask of compactedLedgers[compactedTopicContext];
        all-false when context = 0 or the slot is Nil (the TLC out-of-domain
        case, never forced on reachable states — SURVEY.md C23).

        The slot is a value of the state, so it is read as it is written
        (_phase_two_write, _delete_ledger): a one-hot select over the C
        slots.  ``s.led_mask[slot]`` is a per-state gather under vmap."""
        if self.C == 0:
            return jnp.zeros((self.M,), jnp.bool_)
        slot = jnp.clip(s.context - 1, 0, self.C - 1)
        onehot = jnp.arange(self.C, dtype=jnp.int32) == slot
        words = jnp.max(
            jnp.where(onehot[:, None], s.led_mask, jnp.uint32(0)), axis=0
        )
        present = (s.context >= 1) & jnp.any(onehot & (s.led_present == 1))
        return self._mask_bits(words) & present

    def compaction_horizon_correctness(self, s: SState) -> jax.Array:
        """CompactionHorizonCorrectness (compaction.tla:259-274).

        For every message position i <= compactionHorizon that survives the
        null-key filter, some entry of the context ledger must have the same
        key and id >= i.  Ledger entry ids are positions, so the \\E j over
        the ledger becomes: exists kept position j with keys[j] = keys[i]
        and j >= i — i.e. the LATEST kept position with that key is >= i.
        O(M*K) via the per-key latest table.  The horizon = 0 case is
        vacuous by construction (the i-mask is empty), preserving TLC's
        lazy LET semantics.
        """
        pos = self._pos
        led = self._context_ledger_bits(s)
        needed = (pos <= s.horizon) & (
            (s.keys != 0) | jnp.bool_(self.c.retain_null_key)
        )
        latest_led = self._latest_per_key(s.keys, led)  # [K]
        latest_null = jnp.max(jnp.where(led & (s.keys == 0), pos, 0))
        lat_i = jnp.where(
            s.keys == 0, latest_null, self._lookup_per_key(latest_led, s.keys)
        )
        return jnp.all(~needed | (lat_i >= pos))

    def duplicate_null_key_message(self, s: SState) -> jax.Array:
        """DuplicateNullKeyMessage (compaction.tla:280-294).

        Spec form: no null-key entry of the context ledger may equal any
        messagesAfterHorizon[j].  Entry equality of message records includes
        the positional id, so ledger entry at position p equals a
        post-horizon message iff p > horizon (content at a position is
        immutable).  Hence: violated iff some kept null-key position of the
        context ledger lies beyond the horizon.
        """
        if not self.c.retain_null_key:
            return jnp.bool_(True)
        pos = self._pos
        led = self._context_ledger_bits(s)
        dup = jnp.any(led & (s.keys == 0) & (pos > s.horizon))
        return ~((s.context != 0) & dup)

    @property
    def invariants(self) -> Dict[str, Callable[[SState], jax.Array]]:
        return {
            "TypeSafe": self.type_safe,
            "CompactedLedgerLeak": self.compacted_ledger_leak,
            "CompactionHorizonCorrectness": self.compaction_horizon_correctness,
            "DuplicateNullKeyMessage": self.duplicate_null_key_message,
        }

    @property
    def liveness_goals(self) -> Dict[str, Callable[[SState], jax.Array]]:
        """Named ``<>goal`` predicates (engine/liveness.py protocol)."""
        return {"Termination": self.termination_goal}

    # ------------------------------------------------------------------
    # trace replay (device engine E7 protocol): action lanes are
    # deterministic functions, so a (init_idx, lane list) chain replays
    # through the Python oracle without shipping packed states back
    # ------------------------------------------------------------------

    def replay_trace(self, init_idx: int, lanes) -> Tuple[list, list]:
        """(pyeval.State list, action names) along a lane chain."""
        s0 = jax.jit(self.gen_initial)(jnp.int32(init_idx))
        ps = self.to_pystate(jax.device_get(s0))
        states = [ps]
        actions = []
        for lane in lanes:
            ps = self._apply_lane_py(ps, int(lane))
            states.append(ps)
            actions.append(pyeval.ACTION_NAMES[int(self.action_ids[lane])])
        return states, actions

    @spans.spanned("host_seed")
    def host_seed(
        self, max_level_states: int = 30_000, max_total: int = 32_000
    ):
        """Host-enumerated BFS prefix for ``DeviceChecker.run(seed=...)``.

        The device engine's full-size kernels have data-independent
        latency (sorts), so tiny early levels cost as much as huge ones;
        the Python oracle enumerates them at >100k states/s instead.
        Returns ``(packed_rows, parent_gids, action_lanes, level_sizes)``
        covering every BFS level that fits the caps — level-complete, so
        the engine can take over at the last included level's frontier.
        """
        c = self.c
        states: list = []
        gid_of: dict = {}
        parents: list = []
        lanes: list = []
        lsizes: list = []
        for s in pyeval.initial_states(c):
            if s in gid_of:
                continue
            gid_of[s] = len(states)
            states.append(s)
            # root marker encodes gen_initial's mixed-radix index (NOT
            # the enumeration position: pyeval enumerates position 0 as
            # the most-significant digit, gen_initial as the least)
            parents.append(-1 - self._init_index_of(s))
            lanes.append(0)
            if len(states) > max_total:
                raise ValueError("initial-state set exceeds the seed caps")
        lsizes.append(len(states))
        frontier = list(states)
        while True:
            new = []
            over = False
            for s in frontier:
                sg = gid_of[s]
                any_succ = False
                for aid, t in pyeval.successors(c, s):
                    any_succ = True
                    if t in gid_of:
                        continue
                    gid_of[t] = len(states)
                    states.append(t)
                    parents.append(sg)
                    lanes.append(self._lane_of(aid, t))
                    new.append(t)
                if not any_succ:
                    raise ValueError(
                        "deadlock state inside the seed prefix — check "
                        "without a seed"
                    )
                if (
                    len(new) > max_level_states
                    or len(states) > max_total
                ):
                    # this level will be dropped anyway (seeds must be
                    # level-complete): stop enumerating it NOW — fully
                    # expanding an over-cap level costs minutes at
                    # bench scale for states that get discarded
                    over = True
                    break
            if not new:
                break
            if over:
                # the level that overflowed is dropped: seeds must be
                # level-complete (partial levels would corrupt BFS depth)
                for t in new:
                    del gid_of[t]
                del states[-len(new):]
                del parents[-len(new):]
                del lanes[-len(new):]
                break
            lsizes.append(len(new))
            frontier = new
        rows = self._pack_pystates(states)
        return (
            rows,
            np.asarray(parents, np.int32),
            np.asarray(lanes, np.int32),
            lsizes,
        )

    SEED_PACK_CHUNK = 1 << 12

    def _seed_pack_fn(self):
        if not hasattr(self, "_seed_pack_cache"):
            self._seed_pack_cache = jax.jit(jax.vmap(self.layout.pack))
        return self._seed_pack_cache

    def warm_host_seed(self) -> None:
        """Precompile the fixed-chunk seed packer (engine warmup hook)."""
        z = SState(
            *[
                jnp.zeros(
                    (self.SEED_PACK_CHUNK,) + np.shape(getattr(
                        self.gen_initial(jnp.int32(0)), f
                    )),
                    jnp.uint32
                    if f == "led_mask"
                    else jnp.int32,
                )
                for f in SState._fields
            ]
        )
        np.asarray(self._seed_pack_fn()(z)[0, 0])

    def _pack_pystates(self, states) -> np.ndarray:
        """pyeval.States -> packed rows, via fixed-size chunks so the
        packer compiles once (and can be warmed up-front).  Stacks on
        the HOST — a per-state tree-map would create hundreds of
        thousands of tiny host-to-device transfers."""
        ss = [self.from_pystate(s) for s in states]
        n = len(ss)
        C = self.SEED_PACK_CHUNK
        out = np.zeros((n, self.layout.W), np.uint32)
        pack = self._seed_pack_fn()
        for c0 in range(0, n, C):
            cn = min(C, n - c0)
            cols = []
            for f in SState._fields:
                col = np.stack(
                    [getattr(s, f) for s in ss[c0: c0 + cn]]
                )
                if cn < C:
                    pad = np.zeros((C - cn,) + col.shape[1:], col.dtype)
                    col = np.concatenate([col, pad])
                cols.append(jnp.asarray(col))
            out[c0: c0 + cn] = np.asarray(pack(SState(*cols)))[:cn]
        return out

    def _init_index_of(self, s: pyeval.State) -> int:
        """gen_initial index of an initial state (position i is the
        i-th least-significant base-|KeySet|*|ValueSet| digit)."""
        if self.c.model_producer:
            return 0
        idx = 0
        for i, (_mid, k, v) in enumerate(s.messages):
            idx += (k * (self.c.num_values + 1) + v) * (self.kv ** i)
        return idx

    def _lane_of(self, aid: int, child: pyeval.State) -> int:
        """Action id (+ the produced child) -> successor lane index."""
        if aid == 0:  # Producer: lane encodes the appended (key, value)
            _mid, key, val = child.messages[-1]
            return key * (self.c.num_values + 1) + val
        return self.n_producer_lanes + (aid - 1)

    def _apply_lane_py(self, ps: pyeval.State, lane: int) -> pyeval.State:
        c = self.c
        if lane < self.n_producer_lanes:
            key = lane // (c.num_values + 1)
            val = lane % (c.num_values + 1)
            n = len(ps.messages)
            return ps._replace(messages=ps.messages + ((n + 1, key, val),))
        aid = int(self.action_ids[lane])
        for a, t in pyeval.successors(c, ps):
            if a == aid:
                return t
        raise RuntimeError(f"lane {lane} not enabled during replay")

    # ------------------------------------------------------------------
    # host-side conversions to/from the oracle's structural states
    # ------------------------------------------------------------------

    def to_pystate(self, s) -> pyeval.State:
        """SState (host numpy values, single state) -> pyeval.State."""
        g = lambda x: np.asarray(x)
        length = int(g(s.length))
        keys = g(s.keys)
        vals = g(s.vals)
        messages = tuple(
            (i + 1, int(keys[i]), int(vals[i])) for i in range(length)
        )
        ledgers = []
        for cc in range(self.C):
            if int(g(s.led_present)[cc]) == 0:
                ledgers.append(None)
            else:
                words = g(s.led_mask)[cc]
                entries = tuple(
                    messages[j]
                    for j in range(length)
                    if (int(words[j // 32]) >> (j % 32)) & 1
                )
                ledgers.append(entries)
        cursor = (
            (int(g(s.cursor_h)), int(g(s.cursor_c)))
            if int(g(s.cursor_present))
            else None
        )
        if int(g(s.p1_present)):
            rp = int(g(s.p1_readpos))
            latest: dict = {}
            for j in range(1, rp + 1):
                k = int(keys[j - 1])
                if k != 0:
                    latest[k] = j
            p1 = (rp, tuple(sorted(latest.items())))
        else:
            p1 = None
        return pyeval.State(
            messages=messages,
            ledgers=tuple(ledgers),
            cursor=cursor,
            cstate=int(g(s.cstate)),
            p1=p1,
            horizon=int(g(s.horizon)),
            context=int(g(s.context)),
            crash=int(g(s.crash)),
            consume=int(g(s.consume)),
        )

    def from_pystate(self, ps: pyeval.State) -> SState:
        """pyeval.State -> SState (numpy scalars/arrays, single state)."""
        length = len(ps.messages)
        keys = np.zeros((self.M,), np.int32)
        vals = np.zeros((self.M,), np.int32)
        for i, (mid, k, v) in enumerate(ps.messages):
            assert mid == i + 1, "ids must be positional"
            keys[i] = k
            vals[i] = v
        led_present = np.zeros((self.C,), np.int32)
        led_mask = np.zeros((self.C, self.MW), np.uint32)
        for cc, led in enumerate(ps.ledgers):
            if led is None:
                continue
            led_present[cc] = 1
            for mid, k, v in led:
                j = mid - 1
                assert ps.messages[j] == (mid, k, v), "ledger entry must match prefix"
                led_mask[cc, j // 32] |= np.uint32(1 << (j % 32))
        if ps.p1 is not None:
            p1_present, p1_readpos = 1, ps.p1[0]
        else:
            p1_present, p1_readpos = 0, 0
        if ps.cursor is not None:
            cursor_present, cursor_h, cursor_c = 1, ps.cursor[0], ps.cursor[1]
        else:
            cursor_present, cursor_h, cursor_c = 0, 0, 0
        i32 = np.int32
        return SState(
            length=i32(length),
            keys=keys,
            vals=vals,
            led_present=led_present,
            led_mask=led_mask,
            cursor_present=i32(cursor_present),
            cursor_h=i32(cursor_h),
            cursor_c=i32(cursor_c),
            cstate=i32(ps.cstate),
            p1_present=i32(p1_present),
            p1_readpos=i32(p1_readpos),
            horizon=i32(ps.horizon),
            context=i32(ps.context),
            crash=i32(ps.crash),
            consume=i32(ps.consume),
        )
