"""TPU-native compiled model of the ``georeplication`` spec.

Hand-compiled equivalent of ``specs/georeplication.tla`` (Pulsar
geo-replication over a full cluster mesh): per-(src, dst) replicator
cursors, durable ack positions, and monotone delivery watermarks packed
as small integer matrices, with per-pair duplicated-seqno bitmaps.  The
``\\E src, dst`` nondeterminism becomes ``N*(N-1)`` enumerated lanes per
replicator action; Publish is ``N`` lanes.

Differentially tested against the generic interpreter on the same .tla
source (tests/test_georeplication.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.models import ByConstants
from pulsar_tlaplus_tpu.ops.packing import StructLayout, bitlen


class GeoState(NamedTuple):
    """One state of georeplication.tla (specs/georeplication.tla)."""

    published: jax.Array  # i32[N]: messages originated at cluster c+1
    recv_hwm: jax.Array  # i32[N, N]: [dst, src] delivery high watermark
    rep_cursor: jax.Array  # i32[N, N]: [src, dst] in-memory read position
    rep_acked: jax.Array  # i32[N, N]: [src, dst] durable cursor position
    duplicated: jax.Array  # i32[N, N, P] 0/1: [dst, src, seq-1] dup history
    crash: jax.Array  # i32 scalar: crashTimes


@dataclass(frozen=True)
class GeoConstants:
    """CONSTANTS of georeplication.tla (specs/georeplication.tla)."""

    num_clusters: int = 3
    publish_limit: int = 1
    max_replicator_crashes: int = 1

    def validate(self) -> None:
        if self.num_clusters < 2:
            raise ValueError("NumClusters >= 2 (georeplication.tla ASSUME)")
        if self.publish_limit < 1:
            raise ValueError("PublishLimit >= 1")
        if self.max_replicator_crashes < 0:
            raise ValueError("MaxReplicatorCrashes \\in Nat")


ACTION_NAMES = (
    "Publish",
    "Replicate",
    "PersistCursor",
    "ReplicatorCrash",
)

DEFAULT_INVARIANTS = ("TypeOK", "CursorWithinWatermark", "NoPhantomMessages")


class GeoreplicationModel(ByConstants):
    """Compiled ``georeplication`` spec for a fixed constants binding."""

    def __init__(self, c: GeoConstants):
        c.validate()
        self.c = c
        self.N = c.num_clusters
        self.P = c.publish_limit
        n, p = self.N, self.P
        pb = bitlen(p)
        self.layout = StructLayout(
            GeoState,
            {
                "published": ((n,), pb),
                "recv_hwm": ((n, n), pb),
                "rep_cursor": ((n, n), pb),
                "rep_acked": ((n, n), pb),
                "duplicated": ((n, n, p), 1),
                "crash": ((), bitlen(c.max_replicator_crashes)),
            },
        )
        self.pairs = [
            (s, d) for s in range(n) for d in range(n) if s != d
        ]
        np_ = len(self.pairs)
        # lanes: Publish(c)*N | Replicate(s,d)*N(N-1) |
        #        PersistCursor(s,d)*N(N-1) | ReplicatorCrash(s,d)*N(N-1)
        self.action_ids = np.array(
            [0] * n + [1] * np_ + [2] * np_ + [3] * np_, dtype=np.int32
        )
        self.A = len(self.action_ids)
        self.action_names = ACTION_NAMES
        self.default_invariants = DEFAULT_INVARIANTS

    # ------------------------------------------------------------------
    # initial states
    # ------------------------------------------------------------------

    @property
    def n_initial(self) -> int:
        return 1

    def gen_initial(self, idx: jax.Array) -> GeoState:
        del idx
        n, p = self.N, self.P
        return GeoState(
            published=jnp.zeros((n,), jnp.int32),
            recv_hwm=jnp.zeros((n, n), jnp.int32),
            rep_cursor=jnp.zeros((n, n), jnp.int32),
            rep_acked=jnp.zeros((n, n), jnp.int32),
            duplicated=jnp.zeros((n, n, p), jnp.int32),
            crash=jnp.int32(0),
        )

    # ------------------------------------------------------------------
    # actions; each returns (valid, successor)
    # ------------------------------------------------------------------

    def _publish(self, s: GeoState, c: int) -> Tuple[jax.Array, GeoState]:
        valid = s.published[c] < self.P
        return valid, s._replace(
            published=s.published.at[c].set(s.published[c] + 1)
        )

    def _replicate(self, s: GeoState, src: int, dst: int):
        cur = s.rep_cursor[src, dst]
        valid = cur < s.published[src]
        nxt = cur + 1
        hwm = s.recv_hwm[dst, src]
        is_dup = nxt <= hwm
        seq_idx = jnp.clip(cur, 0, self.P - 1)  # 0-based index of seqno nxt
        dup_bit = jnp.where(is_dup, 1, s.duplicated[dst, src, seq_idx])
        return valid, s._replace(
            rep_cursor=s.rep_cursor.at[src, dst].set(nxt),
            recv_hwm=s.recv_hwm.at[dst, src].set(jnp.maximum(hwm, nxt)),
            duplicated=s.duplicated.at[dst, src, seq_idx].set(dup_bit),
        )

    def _persist(self, s: GeoState, src: int, dst: int):
        valid = s.rep_acked[src, dst] < s.rep_cursor[src, dst]
        return valid, s._replace(
            rep_acked=s.rep_acked.at[src, dst].set(s.rep_cursor[src, dst])
        )

    def _crash(self, s: GeoState, src: int, dst: int):
        valid = (s.crash < self.c.max_replicator_crashes) & (
            s.rep_acked[src, dst] < s.rep_cursor[src, dst]
        )
        return valid, s._replace(
            rep_cursor=s.rep_cursor.at[src, dst].set(s.rep_acked[src, dst]),
            crash=s.crash + 1,
        )

    def successors(self, s: GeoState) -> Tuple[GeoState, jax.Array]:
        lanes: List[Tuple[jax.Array, GeoState]] = []
        for c in range(self.N):
            lanes.append(self._publish(s, c))
        for src, dst in self.pairs:
            lanes.append(self._replicate(s, src, dst))
        for src, dst in self.pairs:
            lanes.append(self._persist(s, src, dst))
        for src, dst in self.pairs:
            lanes.append(self._crash(s, src, dst))
        valid = jnp.stack([v for v, _ in lanes])
        succ = jax.tree.map(lambda *xs: jnp.stack(xs), *[t for _, t in lanes])
        return succ, valid

    def done(self, s: GeoState) -> jax.Array:
        """Done: all published and every replicator fully caught up."""
        off = ~jnp.eye(self.N, dtype=bool)
        return (
            jnp.all(s.published == self.P)
            & jnp.all(jnp.where(off, s.rep_cursor, self.P) == self.P)
            & jnp.all(jnp.where(off, s.rep_acked, self.P) == self.P)
        )

    def stutter_enabled(self, s: GeoState) -> jax.Array:
        return self.done(s)

    # ------------------------------------------------------------------
    # invariants; True = satisfied
    # ------------------------------------------------------------------

    def type_ok(self, s: GeoState) -> jax.Array:
        eye = jnp.eye(self.N, dtype=bool)
        off = ~eye
        diag_zero = (
            jnp.all(jnp.where(eye, s.recv_hwm, 0) == 0)
            & jnp.all(jnp.where(eye, s.rep_cursor, 0) == 0)
            & jnp.all(jnp.where(eye, s.rep_acked, 0) == 0)
            & jnp.all(jnp.where(eye[:, :, None], s.duplicated, 0) == 0)
        )
        seqs = jnp.arange(1, self.P + 1, dtype=jnp.int32)  # [P]
        dup_in_hwm = jnp.all(
            (s.duplicated == 0) | (seqs[None, None, :] <= s.recv_hwm[:, :, None])
        )
        return (
            jnp.all((s.published >= 0) & (s.published <= self.P))
            & diag_zero
            & jnp.all(
                ~off
                | (
                    # rep_cursor/rep_acked are [src, dst]: bound by the
                    # source's published count; recv_hwm is [dst, src]
                    (s.rep_cursor >= 0)
                    & (s.rep_cursor <= s.published[:, None])
                    & (s.rep_acked >= 0)
                    & (s.rep_acked <= s.rep_cursor)
                    & (s.recv_hwm >= 0)
                    & (s.recv_hwm <= s.published[None, :])
                )
            )
            & jnp.all((s.duplicated == 0) | (s.duplicated == 1))
            & dup_in_hwm
            & (s.crash >= 0)
            & (s.crash <= self.c.max_replicator_crashes)
        )

    def cursor_within_watermark(self, s: GeoState) -> jax.Array:
        """repCursor[src][dst] <= recvHwm[dst][src] for all src # dst."""
        off = ~jnp.eye(self.N, dtype=bool)
        return jnp.all(~off | (s.rep_cursor <= s.recv_hwm.T))

    def no_phantom_messages(self, s: GeoState) -> jax.Array:
        """recvHwm[dst][src] <= published[src]."""
        off = ~jnp.eye(self.N, dtype=bool)
        return jnp.all(~off | (s.recv_hwm <= s.published[None, :]))

    def no_duplicate_delivery(self, s: GeoState) -> jax.Array:
        """VIOLATED whenever MaxReplicatorCrashes >= 1 (at-least-once)."""
        return jnp.all(s.duplicated == 0)

    @property
    def invariants(self) -> Dict[str, Callable[[GeoState], jax.Array]]:
        return {
            "TypeOK": self.type_ok,
            "CursorWithinWatermark": self.cursor_within_watermark,
            "NoPhantomMessages": self.no_phantom_messages,
            "NoDuplicateDelivery": self.no_duplicate_delivery,
        }

    @property
    def liveness_goals(self) -> Dict[str, Callable[[GeoState], jax.Array]]:
        """Termination == <>Done (georeplication.tla)."""
        return {"Termination": self.done}

    # ------------------------------------------------------------------
    # host-side conversions
    # ------------------------------------------------------------------

    def to_interp_state(self, s) -> tuple:
        """GeoState -> interpreter state tuple (VARIABLES order:
        published, recvHwm, repCursor, repAcked, duplicated, crashTimes).
        Functions over 1..N normalize to tuples in the interpreter."""
        g = lambda v: np.asarray(v)
        pub = tuple(int(x) for x in g(s.published))
        mat = lambda v: tuple(
            tuple(int(x) for x in row) for row in g(v)
        )
        dup = tuple(
            tuple(
                frozenset(
                    int(k + 1) for k in np.nonzero(g(s.duplicated)[d, sr])[0]
                )
                for sr in range(self.N)
            )
            for d in range(self.N)
        )
        return (
            pub,
            mat(s.recv_hwm),
            mat(s.rep_cursor),
            mat(s.rep_acked),
            dup,
            int(g(s.crash)),
        )

    def from_interp_state(self, t: tuple) -> GeoState:
        """Interpreter state tuple -> GeoState (numpy host values)."""
        pub, hwm, cur, ack, dup, crash = t
        n, p = self.N, self.P
        dmat = np.zeros((n, n, p), np.int32)
        for d in range(n):
            for sr in range(n):
                for k in dup[d][sr]:
                    dmat[d, sr, k - 1] = 1
        return GeoState(
            published=np.asarray(pub, np.int32),
            recv_hwm=np.asarray(hwm, np.int32),
            rep_cursor=np.asarray(cur, np.int32),
            rep_acked=np.asarray(ack, np.int32),
            duplicated=dmat,
            crash=np.int32(crash),
        )

    def to_pystate(self, s) -> dict:
        """GeoState -> rendered {var: value} (utils.render dict protocol)."""
        pub, hwm, cur, ack, dup, crash = self.to_interp_state(s)
        fint = lambda t: "<<" + ", ".join(str(x) for x in t) + ">>"
        fmat = lambda m: "<<" + ", ".join(fint(r) for r in m) + ">>"
        fset = lambda fs: "{" + ", ".join(str(i) for i in sorted(fs)) + "}"
        fdup = lambda m: (
            "<<"
            + ", ".join(
                "<<" + ", ".join(fset(x) for x in r) + ">>" for r in m
            )
            + ">>"
        )
        return {
            "published": fint(pub),
            "recvHwm": fmat(hwm),
            "repCursor": fmat(cur),
            "repAcked": fmat(ack),
            "duplicated": fdup(dup),
            "crashTimes": crash,
        }
