"""TPU-native compiled model of the ``bookkeeper`` spec.

Hand-compiled equivalent of ``specs/bookkeeper.tla`` (BookKeeper ledger
write-quorum replication): per-(bookie, entry) storage and ack bits over a
:class:`~..ops.packing.StructLayout` packed state, with the round-robin
write sets precomputed as a static mask.  The ``\\E b, e`` nondeterminism
in WriteLand/AckArrive becomes ``E*L`` enumerated lanes; BookieCrash is
``E`` lanes.

Differentially tested against the generic interpreter on the same .tla
source (tests/test_bookkeeper.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.models import ByConstants
from pulsar_tlaplus_tpu.ops.packing import StructLayout, bitlen


class BkState(NamedTuple):
    """One state of bookkeeper.tla (specs/bookkeeper.tla VARIABLES)."""

    added: jax.Array  # i32 scalar: 0..L
    stored: jax.Array  # i32[E, L] 0/1: entry e+1 persisted on bookie b+1
    acked_by: jax.Array  # i32[L, E] 0/1: bookie b+1's ack for e+1 arrived
    lac: jax.Array  # i32 scalar: LastAddConfirmed, 0..L
    crashed: jax.Array  # i32[E] 0/1


@dataclass(frozen=True)
class BookkeeperConstants:
    """CONSTANTS of bookkeeper.tla (specs/bookkeeper.tla)."""

    num_bookies: int = 3
    write_quorum: int = 2
    ack_quorum: int = 2
    entry_limit: int = 2
    max_bookie_crashes: int = 1

    def validate(self) -> None:
        if self.num_bookies < 1:
            raise ValueError("NumBookies >= 1 (bookkeeper.tla ASSUME)")
        if not 1 <= self.write_quorum <= self.num_bookies:
            raise ValueError("WriteQuorum \\in 1..NumBookies")
        if not 1 <= self.ack_quorum <= self.write_quorum:
            raise ValueError("AckQuorum \\in 1..WriteQuorum")
        if self.entry_limit < 1:
            raise ValueError("EntryLimit >= 1")
        if not 0 <= self.max_bookie_crashes <= self.num_bookies:
            raise ValueError("MaxBookieCrashes \\in 0..NumBookies")


ACTION_NAMES = (
    "AddEntry",
    "WriteLand",
    "AckArrive",
    "AdvanceLAC",
    "BookieCrash",
)

DEFAULT_INVARIANTS = (
    "TypeOK",
    "LacIsConfirmed",
    "AckImpliesStoredOrCrashed",
    "ConfirmedEntryReadable",
)


class BookkeeperModel(ByConstants):
    """Compiled ``bookkeeper`` spec for a fixed constants binding."""

    def __init__(self, c: BookkeeperConstants):
        c.validate()
        self.c = c
        self.E = c.num_bookies
        self.L = c.entry_limit
        e, l = self.E, self.L
        self.layout = StructLayout(
            BkState,
            {
                "added": ((), bitlen(l)),
                "stored": ((e, l), 1),
                "acked_by": ((l, e), 1),
                "lac": ((), bitlen(l)),
                "crashed": ((e,), 1),
            },
        )
        # WriteSet(e) == {((e-1+i) % E) + 1 : i \in 0..Qw-1} as [L, E] mask
        ws = np.zeros((l, e), np.int32)
        for ent in range(l):
            for i in range(c.write_quorum):
                ws[ent, (ent + i) % e] = 1
        self._ws = jnp.asarray(ws)  # [L, E]
        # lanes: AddEntry | WriteLand(b,e)*E*L | AckArrive(b,e)*E*L |
        #        AdvanceLAC | BookieCrash(b)*E
        self.action_ids = np.array(
            [0] + [1] * (e * l) + [2] * (e * l) + [3] + [4] * e,
            dtype=np.int32,
        )
        self.A = len(self.action_ids)
        self.action_names = ACTION_NAMES
        self.default_invariants = DEFAULT_INVARIANTS

    # ------------------------------------------------------------------
    # initial states (bookkeeper.tla Init)
    # ------------------------------------------------------------------

    @property
    def n_initial(self) -> int:
        return 1

    def gen_initial(self, idx: jax.Array) -> BkState:
        del idx
        return BkState(
            added=jnp.int32(0),
            stored=jnp.zeros((self.E, self.L), jnp.int32),
            acked_by=jnp.zeros((self.L, self.E), jnp.int32),
            lac=jnp.int32(0),
            crashed=jnp.zeros((self.E,), jnp.int32),
        )

    # ------------------------------------------------------------------
    # actions; each returns (valid, successor)
    # ------------------------------------------------------------------

    def _add_entry(self, s: BkState) -> Tuple[jax.Array, BkState]:
        valid = s.added < self.L
        return valid, s._replace(added=s.added + 1)

    def _write_land(self, s: BkState, b: int, e: int):
        valid = (
            (e + 1 <= s.added)
            & (self._ws[e, b] == 1)
            & (s.crashed[b] == 0)
            & (s.stored[b, e] == 0)
        )
        return valid, s._replace(stored=s.stored.at[b, e].set(1))

    def _ack_arrive(self, s: BkState, b: int, e: int):
        valid = (s.stored[b, e] == 1) & (s.acked_by[e, b] == 0)
        return valid, s._replace(acked_by=s.acked_by.at[e, b].set(1))

    def _advance_lac(self, s: BkState) -> Tuple[jax.Array, BkState]:
        row = jnp.clip(s.lac, 0, self.L - 1)  # 0-based row of entry lac+1
        n_acks = jnp.sum(jnp.take(s.acked_by, row, axis=0))
        valid = (s.lac < s.added) & (n_acks >= self.c.ack_quorum)
        return valid, s._replace(lac=s.lac + 1)

    def _bookie_crash(self, s: BkState, b: int) -> Tuple[jax.Array, BkState]:
        valid = (jnp.sum(s.crashed) < self.c.max_bookie_crashes) & (
            s.crashed[b] == 0
        )
        return valid, s._replace(
            crashed=s.crashed.at[b].set(1),
            stored=s.stored.at[b, :].set(0),
        )

    def successors(self, s: BkState) -> Tuple[BkState, jax.Array]:
        lanes: List[Tuple[jax.Array, BkState]] = [self._add_entry(s)]
        for b in range(self.E):
            for e in range(self.L):
                lanes.append(self._write_land(s, b, e))
        for b in range(self.E):
            for e in range(self.L):
                lanes.append(self._ack_arrive(s, b, e))
        lanes.append(self._advance_lac(s))
        for b in range(self.E):
            lanes.append(self._bookie_crash(s, b))
        valid = jnp.stack([v for v, _ in lanes])
        succ = jax.tree.map(lambda *xs: jnp.stack(xs), *[t for _, t in lanes])
        return succ, valid

    def _wedged(self, s: BkState) -> jax.Array:
        """Wedged: entry lac+1 can never reach an ack quorum."""
        row = jnp.clip(s.lac, 0, self.L - 1)
        acked = jnp.take(s.acked_by, row, axis=0)  # [E]
        live_ws = jnp.take(self._ws, row, axis=0) * (1 - s.crashed)
        reachable = jnp.sum(jnp.maximum(acked, live_ws))
        return (s.lac < s.added) & (reachable < self.c.ack_quorum)

    def done(self, s: BkState) -> jax.Array:
        """Done == added = EntryLimit /\\ (lac = EntryLimit \\/ Wedged)."""
        return (s.added == self.L) & (
            (s.lac == self.L) | self._wedged(s)
        )

    def stutter_enabled(self, s: BkState) -> jax.Array:
        return self.done(s)

    # ------------------------------------------------------------------
    # invariants; True = satisfied
    # ------------------------------------------------------------------

    def type_ok(self, s: BkState) -> jax.Array:
        ents = jnp.arange(1, self.L + 1, dtype=jnp.int32)  # [L]
        bits_ok = jnp.bool_(True)
        for v in (s.stored, s.acked_by, s.crashed):
            bits_ok = bits_ok & jnp.all((v == 0) | (v == 1))
        stored_ok = jnp.all(
            (s.stored == 0)
            | ((ents[None, :] <= s.added) & (self._ws.T == 1))
        )
        acked_ok = jnp.all(
            (s.acked_by == 0)
            | ((ents[:, None] <= s.added) & (self._ws == 1))
        )
        crashed_clean = jnp.all((s.crashed[:, None] == 0) | (s.stored == 0))
        return (
            bits_ok
            & (s.added >= 0)
            & (s.added <= self.L)
            & (s.lac >= 0)
            & (s.lac <= s.added)
            & (jnp.sum(s.crashed) <= self.c.max_bookie_crashes)
            & stored_ok
            & acked_ok
            & crashed_clean
        )

    def lac_is_confirmed(self, s: BkState) -> jax.Array:
        ents = jnp.arange(1, self.L + 1, dtype=jnp.int32)
        n_acks = jnp.sum(s.acked_by, axis=1)  # [L]
        return jnp.all((ents > s.lac) | (n_acks >= self.c.ack_quorum))

    def ack_implies_stored_or_crashed(self, s: BkState) -> jax.Array:
        ok = (s.acked_by.T == 0) | (s.stored == 1) | (s.crashed[:, None] == 1)
        return jnp.all(ok)

    def confirmed_entry_readable(self, s: BkState) -> jax.Array:
        """VIOLATED when MaxBookieCrashes >= AckQuorum (durability bound)."""
        ents = jnp.arange(1, self.L + 1, dtype=jnp.int32)
        somewhere = jnp.any(s.stored == 1, axis=0)  # [L]
        return jnp.all((ents > s.lac) | somewhere)

    @property
    def invariants(self) -> Dict[str, Callable[[BkState], jax.Array]]:
        return {
            "TypeOK": self.type_ok,
            "LacIsConfirmed": self.lac_is_confirmed,
            "AckImpliesStoredOrCrashed": self.ack_implies_stored_or_crashed,
            "ConfirmedEntryReadable": self.confirmed_entry_readable,
        }

    @property
    def liveness_goals(self) -> Dict[str, Callable[[BkState], jax.Array]]:
        """Termination == <>Done (bookkeeper.tla)."""
        return {"Termination": self.done}

    # ------------------------------------------------------------------
    # host-side conversions
    # ------------------------------------------------------------------

    def to_interp_state(self, s) -> tuple:
        """BkState -> interpreter state tuple (VARIABLES order).  Functions
        with domain 1..n normalize to tuples in the interpreter, so
        ``stored``/``ackedBy`` are tuples of frozensets."""
        g = lambda v: np.asarray(v)
        stored = tuple(
            frozenset(int(e + 1) for e in np.nonzero(g(s.stored)[b])[0])
            for b in range(self.E)
        )
        acked = tuple(
            frozenset(int(b + 1) for b in np.nonzero(g(s.acked_by)[e])[0])
            for e in range(self.L)
        )
        crashed = frozenset(
            int(b + 1) for b in np.nonzero(g(s.crashed))[0]
        )
        return (int(g(s.added)), stored, acked, int(g(s.lac)), crashed)

    def from_interp_state(self, t: tuple) -> BkState:
        """Interpreter state tuple -> BkState (numpy host values)."""
        added, stored, acked, lac, crashed = t
        st = np.zeros((self.E, self.L), np.int32)
        for b, es in enumerate(stored):
            for e in es:
                st[b, e - 1] = 1
        ab = np.zeros((self.L, self.E), np.int32)
        for e, bs in enumerate(acked):
            for b in bs:
                ab[e, b - 1] = 1
        cr = np.zeros((self.E,), np.int32)
        for b in crashed:
            cr[b - 1] = 1
        return BkState(
            added=np.int32(added), stored=st, acked_by=ab,
            lac=np.int32(lac), crashed=cr,
        )

    def to_pystate(self, s) -> dict:
        """BkState -> rendered {var: value} (utils.render dict protocol)."""
        added, stored, acked, lac, crashed = self.to_interp_state(s)
        fset = lambda fs: "{" + ", ".join(str(i) for i in sorted(fs)) + "}"
        ftup = lambda t: "<<" + ", ".join(fset(x) for x in t) + ">>"
        return {
            "added": added,
            "stored": ftup(stored),
            "ackedBy": ftup(acked),
            "lac": lac,
            "crashed": fset(crashed),
        }
