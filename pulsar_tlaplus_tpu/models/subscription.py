"""TPU-native compiled model of the ``subscription`` spec.

Hand-compiled equivalent of ``specs/subscription.tla`` (Pulsar cursor
ack/redelivery): one vectorizable kernel per action, invariant kernels,
and initial-state generation over a :class:`~..ops.packing.StructLayout`
bit-packed state.  Per-message lifecycle sets (``delivered``/``pending``/
``acked``/``everProcessed``/``duplicated``) are 1-bit lanes over message
ids — set algebra compiles to elementwise boolean ops, and the ``\\E m``
nondeterminism in Deliver/Process/SendAck becomes ``MessageLimit``
enumerated lanes each.

All kernels are pure functions of a single ``SubState``; batch via
``jax.vmap``.  Differentially tested against the generic interpreter on
the same .tla source (tests/test_subscription.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.models import ByConstants
from pulsar_tlaplus_tpu.ops.packing import StructLayout, bitlen
from typing import NamedTuple


class SubState(NamedTuple):
    """One state of subscription.tla (specs/subscription.tla VARIABLES).

    Sets over message ids are 0/1 vectors indexed by id-1."""

    produced: jax.Array  # i32 scalar: 0..M
    delivered: jax.Array  # i32[M] 0/1: in flight, not yet processed
    pending: jax.Array  # i32[M] 0/1: processed, ack not on broker yet
    acked: jax.Array  # i32[M] 0/1: individually acked past markDelete
    mark: jax.Array  # i32 scalar: markDelete position, 0..M
    ever: jax.Array  # i32[M] 0/1: processed at least once (monotone)
    dup: jax.Array  # i32[M] 0/1: processed more than once (monotone)
    crash: jax.Array  # i32 scalar: crashTimes


@dataclass(frozen=True)
class SubscriptionConstants:
    """CONSTANTS of subscription.tla (specs/subscription.tla)."""

    message_limit: int = 3
    max_crash_times: int = 2

    def validate(self) -> None:
        if self.message_limit < 1:
            raise ValueError("MessageLimit >= 1 (subscription.tla ASSUME)")
        if self.max_crash_times < 0:
            raise ValueError("MaxCrashTimes \\in Nat (subscription.tla ASSUME)")


ACTION_NAMES = (
    "Publish",
    "Deliver",
    "Process",
    "SendAck",
    "AdvanceMarkDelete",
    "ConsumerCrash",
)

DEFAULT_INVARIANTS = ("TypeOK", "NoLostMessage", "AckedWasProcessed")


class SubscriptionModel(ByConstants):
    """Compiled ``subscription`` spec for a fixed constants binding."""

    def __init__(self, c: SubscriptionConstants):
        c.validate()
        self.c = c
        self.M = c.message_limit
        m = self.M
        mb = bitlen(m)
        self.layout = StructLayout(
            SubState,
            {
                "produced": ((), mb),
                "delivered": ((m,), 1),
                "pending": ((m,), 1),
                "acked": ((m,), 1),
                "mark": ((), mb),
                "ever": ((m,), 1),
                "dup": ((m,), 1),
                "crash": ((), bitlen(c.max_crash_times)),
            },
        )
        # lanes: Publish | Deliver(m)*M | Process(m)*M | SendAck(m)*M |
        #        AdvanceMarkDelete | ConsumerCrash
        self.action_ids = np.array(
            [0] + [1] * m + [2] * m + [3] * m + [4, 5], dtype=np.int32
        )
        self.A = len(self.action_ids)
        self.action_names = ACTION_NAMES
        self.default_invariants = DEFAULT_INVARIANTS
        self._ids = jnp.arange(1, m + 1, dtype=jnp.int32)  # [M], 1-based

    # ------------------------------------------------------------------
    # initial states (subscription.tla Init)
    # ------------------------------------------------------------------

    @property
    def n_initial(self) -> int:
        return 1

    def gen_initial(self, idx: jax.Array) -> SubState:
        del idx
        z = jnp.int32(0)
        zv = jnp.zeros((self.M,), jnp.int32)
        return SubState(
            produced=z, delivered=zv, pending=zv, acked=zv,
            mark=z, ever=zv, dup=zv, crash=z,
        )

    # ------------------------------------------------------------------
    # actions; each returns (valid, successor)
    # ------------------------------------------------------------------

    def _publish(self, s: SubState) -> Tuple[jax.Array, SubState]:
        valid = s.produced < self.M
        return valid, s._replace(produced=s.produced + 1)

    def _deliver(self, s: SubState, m: int) -> Tuple[jax.Array, SubState]:
        """Deliver id m+1 (0-based lane index m)."""
        mid = m + 1
        valid = (
            (mid <= s.produced)
            & (mid > s.mark)
            & (s.delivered[m] == 0)
            & (s.pending[m] == 0)
            & (s.acked[m] == 0)
        )
        return valid, s._replace(delivered=s.delivered.at[m].set(1))

    def _process(self, s: SubState, m: int) -> Tuple[jax.Array, SubState]:
        valid = s.delivered[m] == 1
        return valid, s._replace(
            delivered=s.delivered.at[m].set(0),
            pending=s.pending.at[m].set(1),
            ever=s.ever.at[m].set(1),
            # duplicated gains m iff m was processed before (IF in Process)
            dup=s.dup.at[m].set(jnp.maximum(s.dup[m], s.ever[m])),
        )

    def _send_ack(self, s: SubState, m: int) -> Tuple[jax.Array, SubState]:
        valid = s.pending[m] == 1
        return valid, s._replace(
            pending=s.pending.at[m].set(0),
            acked=s.acked.at[m].set(1),
        )

    def _advance(self, s: SubState) -> Tuple[jax.Array, SubState]:
        """AdvanceMarkDelete: markDelete+1 \\in acked."""
        nxt = jnp.clip(s.mark, 0, self.M - 1)  # 0-based index of id mark+1
        valid = (s.mark < self.M) & (s.acked[nxt] == 1)
        return valid, s._replace(
            mark=s.mark + 1,
            acked=s.acked.at[nxt].set(0),
        )

    def _crash(self, s: SubState) -> Tuple[jax.Array, SubState]:
        valid = s.crash < self.c.max_crash_times
        zv = jnp.zeros((self.M,), jnp.int32)
        return valid, s._replace(delivered=zv, pending=zv, crash=s.crash + 1)

    def successors(self, s: SubState) -> Tuple[SubState, jax.Array]:
        """All non-stuttering Next lanes: (stacked SubState [A], valid [A])."""
        lanes: List[Tuple[jax.Array, SubState]] = [self._publish(s)]
        for m in range(self.M):
            lanes.append(self._deliver(s, m))
        for m in range(self.M):
            lanes.append(self._process(s, m))
        for m in range(self.M):
            lanes.append(self._send_ack(s, m))
        lanes.append(self._advance(s))
        lanes.append(self._crash(s))
        valid = jnp.stack([v for v, _ in lanes])
        succ = jax.tree.map(lambda *xs: jnp.stack(xs), *[t for _, t in lanes])
        return succ, valid

    def stutter_enabled(self, s: SubState) -> jax.Array:
        """Terminating self-loop (drained end state)."""
        return self.drained(s)

    def drained(self, s: SubState) -> jax.Array:
        """Drained == produced = MessageLimit /\\ markDelete = MessageLimit."""
        return (s.produced == self.M) & (s.mark == self.M)

    # ------------------------------------------------------------------
    # invariants; True = satisfied
    # ------------------------------------------------------------------

    def type_ok(self, s: SubState) -> jax.Array:
        ids = self._ids
        bits_ok = jnp.bool_(True)
        for v in (s.delivered, s.pending, s.acked, s.ever, s.dup):
            bits_ok = bits_ok & jnp.all((v == 0) | (v == 1))
        tracked = (s.delivered | s.pending | s.acked) == 1
        return (
            bits_ok
            & (s.produced >= 0)
            & (s.produced <= self.M)
            & (s.mark >= 0)
            & (s.mark <= s.produced)
            & (s.crash >= 0)
            & (s.crash <= self.c.max_crash_times)
            & jnp.all(s.dup <= s.ever)
            & jnp.all(s.delivered + s.pending + s.acked <= 1)  # disjoint
            & jnp.all(~tracked | ((ids > s.mark) & (ids <= s.produced)))
        )

    def no_lost_message(self, s: SubState) -> jax.Array:
        """Every id <= markDelete was processed at least once."""
        return jnp.all(~(self._ids <= s.mark) | (s.ever == 1))

    def acked_was_processed(self, s: SubState) -> jax.Array:
        return jnp.all(((s.acked | s.pending) == 0) | (s.ever == 1))

    def exactly_once_processing(self, s: SubState) -> jax.Array:
        """VIOLATED whenever MaxCrashTimes >= 1 (at-least-once delivery)."""
        return jnp.all(s.dup == 0)

    @property
    def invariants(self) -> Dict[str, Callable[[SubState], jax.Array]]:
        return {
            "TypeOK": self.type_ok,
            "NoLostMessage": self.no_lost_message,
            "AckedWasProcessed": self.acked_was_processed,
            "ExactlyOnceProcessing": self.exactly_once_processing,
        }

    @property
    def liveness_goals(self) -> Dict[str, Callable[[SubState], jax.Array]]:
        """Termination == <>Drained (subscription.tla)."""
        return {"Termination": self.drained}

    # ------------------------------------------------------------------
    # host-side conversions
    # ------------------------------------------------------------------

    def _sets(self, s):
        g = lambda v: np.asarray(v)
        out = {}
        for name in ("delivered", "pending", "acked", "ever", "dup"):
            bits = g(getattr(s, name))
            out[name] = frozenset(int(i + 1) for i in np.nonzero(bits)[0])
        return out

    def to_interp_state(self, s) -> tuple:
        """SubState -> the generic interpreter's state tuple (VARIABLES
        order in specs/subscription.tla) for exact differential testing."""
        st = self._sets(s)
        return (
            int(np.asarray(s.produced)),
            st["delivered"],
            st["pending"],
            st["acked"],
            int(np.asarray(s.mark)),
            st["ever"],
            st["dup"],
            int(np.asarray(s.crash)),
        )

    def to_pystate(self, s) -> dict:
        """SubState -> rendered {var: value} (utils.render dict protocol)."""
        fmt = lambda fs: "{" + ", ".join(str(i) for i in sorted(fs)) + "}"
        st = self._sets(s)
        return {
            "produced": int(np.asarray(s.produced)),
            "delivered": fmt(st["delivered"]),
            "pending": fmt(st["pending"]),
            "acked": fmt(st["acked"]),
            "markDelete": int(np.asarray(s.mark)),
            "everProcessed": fmt(st["ever"]),
            "duplicated": fmt(st["dup"]),
            "crashTimes": int(np.asarray(s.crash)),
        }

    def from_interp_state(self, t: tuple) -> SubState:
        """Interpreter state tuple -> SubState (numpy host values)."""
        produced, delivered, pending, acked, mark, ever, dup, crash = t

        def mask(fs):
            v = np.zeros((self.M,), np.int32)
            for i in fs:
                v[i - 1] = 1
            return v

        return SubState(
            produced=np.int32(produced),
            delivered=mask(delivered),
            pending=mask(pending),
            acked=mask(acked),
            mark=np.int32(mark),
            ever=mask(ever),
            dup=mask(dup),
            crash=np.int32(crash),
        )
