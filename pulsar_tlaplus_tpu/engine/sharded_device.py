"""Device-resident mesh-sharded BFS checker (VERDICT r2 missing #2).

The round-2 ``ShardedChecker`` proved the sharding *semantics* (owner =
``key % n_shards``, identical counts on any mesh) but staged every chunk
through host numpy — a host round trip per chunk, and no basis for a
multi-chip slice.  This engine ports the round-3 single-chip
design (``engine/device_bfs.py``) into ``shard_map``:

- every shard owns HBM-resident visited key columns, a packed row store
  (its states, in local-gid order), parent/lane trace logs, and a
  candidate accumulator — the exact single-chip layout, one per shard;
- each BFS round, every shard expands a window of its own frontier,
  buckets the candidate lanes by key owner (one-hot running-rank, no
  host), and one ``all_to_all`` routes keys + packed rows + parent gid +
  action lane to the owning shards (ICI traffic on a real slice);
- received lanes accumulate locally; the flush (a probe of the
  owner's ``ops.fpset`` table) and append run per shard inside the
  same jitted program;
- the host fetches ONE per-shard stats matrix per group of flushes and
  only orchestrates: rounds, levels, growth, verdicts.

Global state ids encode ``(shard, local gid)`` as
``shard << SB | local`` so parent chains cross shards; counterexamples
replay through the model exactly like the single-chip engine.

Determinism/exactness: counts, levels, and verdict sets are identical
for any shard count (tested on the virtual CPU mesh for n in {1,2,4,8}
and vs the Python oracle).  Routing capacity is ``slack *
lanes/n_shards`` per destination; an overflow cannot corrupt the search
— it sets a sticky flag, and the host auto-recovers by doubling
``route_slack``, re-jitting, and retrying the level (every state the
partial attempt appended dedups to a no-op), never a silent drop.

Round-4 additions (VERDICT r3 #6/#7/#8): checkpoint/resume of the full
per-shard device state at level boundaries (``checkpoint_path``),
2-D multi-slice meshes with hierarchical dcn-then-ici owner routing
inside the jitted round (``n_slices``), and the overflow auto-recovery
above.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.obs import telemetry as obs
from pulsar_tlaplus_tpu.utils import ckpt, faults, recovery
from pulsar_tlaplus_tpu.engine.bfs import CheckerResult
from pulsar_tlaplus_tpu.ops import compact as compact_ops
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL, KeySpec
from pulsar_tlaplus_tpu.ref import pyeval

BIG = jnp.int32(2**31 - 1)

# per-shard zero-sync fpset metrics vector [flushes, probe_rounds,
# failures, valid_lanes_lo, max_probe_rounds, valid_lanes_hi] —
# widened 3 -> 5 in r9 to match the single-chip engine and 5 -> 6 in
# r12 (hi/lo uint32 valid-lane words survive the int32 wrap;
# ops/fpset.py is the shared source)
FPM_N = fpset.FPM_N

# per-shard route state riding the stats fetch: [sticky overflow flag,
# lanes sent through the key exchange as a hi/lo uint32 pair (LO, HI)]
RT_N = 3

AXIS = "shard"
DCN_AXIS = "dcn"  # across slices (multi-slice; data-center network)
ICI_AXIS = "ici"  # within a slice (inter-chip interconnect)


class _RouteOverflow(Exception):
    """Internal: a routing round exceeded per-destination capacity.
    Recovered by the host (double route_slack, re-jit, retry level)."""


def _owner(kcols, n: int):
    """Owning shard of a key: a murmur-style mix of the columns, mod n.
    Exact (non-hashed) keys are raw state words whose low bits can be
    heavily skewed; mixing keeps per-destination counts near lanes/n so
    the dense routing capacity holds."""
    h = kcols[0]
    for c in kcols[1:]:
        h = (h ^ c) * jnp.uint32(0xCC9E2D51)
        h = (h << jnp.uint32(13)) | (h >> jnp.uint32(19))
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    return (h % jnp.uint32(n)).astype(jnp.int32)


@spans.staged("route")
def _route_note(rt, over, q):
    """The route state after one exchange: the overflow flag stays up
    once raised, and the lanes this shard sent (``q >= 0``: those that
    got a slot) add to the hi/lo counter."""
    lo, hi = fpset.add_u32(
        rt[1], rt[2], jnp.sum((q >= 0).astype(jnp.int32))
    )
    return jnp.stack([rt[0] | over.astype(jnp.int32), lo, hi])


@spans.staged("route")
def _route_keys(kcols, ak, acc_off, N: int, CAPO: int):
    """Round-5 producer-local routing (VERDICT r4 #3): bucket candidate
    KEYS by owner (one-hot running rank — no sort, no host), route them
    with one ``all_to_all`` of K planes, and append the received keys
    into the owner-side key accumulator at ``acc_off``.  Packed rows,
    parent gids, and action lanes NEVER travel — they stay on the
    producing shard, which appends them once the owner's dedup flags
    return (see ``_flags_back``).  Routed planes per round drop from
    ``K + 2 + W`` (26 at bench shapes) to ``K`` forward + 1 back.

    Returns ``(ak', q, over)``: ``q[l] = owner * CAPO + rank`` is the
    producer-side return address of lane ``l`` (-1 for invalid/dropped
    lanes), saved in the producer accumulator for the flag gather."""
    K = len(kcols)
    valid = kcols[0] != SENTINEL
    for c in kcols[1:]:
        valid = valid | (c != SENTINEL)
    owner = _owner(kcols, N)
    outs, q, over = _bucket_scatter(
        owner, N, CAPO, valid, list(kcols), [SENTINEL] * K
    )
    stack = jnp.stack(outs).reshape(K, N, CAPO)
    r_stack = lax.all_to_all(
        stack, AXIS, split_axis=1, concat_axis=1, tiled=False
    ).reshape(K, N * CAPO)
    ak = tuple(
        lax.dynamic_update_slice(a, r_stack[i], (acc_off,))
        for i, a in enumerate(ak)
    )
    return ak, q, over


@spans.staged("route")
def _flags_back(flag_owner, FLUSH: int, N: int, CAPO: int):
    """Inverse of ``_route_keys`` for the dedup flags: the owner's
    acc-order flag vector (slot ``r * N*CAPO + p * CAPO + j`` = round
    r's key from producer p at rank j) is regrouped per producer and
    returned with one ``all_to_all`` of a single u32 plane.  Producer p
    receives ``[N, FLUSH * CAPO]`` where block o holds owner o's flags
    for p's lanes; lane l of round r with saved ``q = o * CAPO + j``
    reads flat index ``o * FLUSH*CAPO + r * CAPO + j``."""
    f = flag_owner.reshape(FLUSH, N, CAPO).transpose(1, 0, 2)
    return lax.all_to_all(
        f, AXIS, split_axis=0, concat_axis=0, tiled=False
    ).reshape(N * FLUSH * CAPO)


@spans.staged("route")
def _flag_gather(recv, aq, FLUSH: int, cap: int, NCs: int):
    """Producer-side per-lane flags from the returned flag planes:
    ``aq`` is the saved q per producer lane (acc order, -1 = invalid).
    ``cap`` is the per-destination slot stride the q values were built
    with — CAPO on a 1-D mesh, CAPD for the 2-D stage-1 addresses (a
    2-D ``aq`` holds OWNER-SLICE slots, not owner-chip ones).  Returns
    u32[FLUSH * NCs] new-flags in producer-acc order."""
    lanei = jnp.arange(FLUSH * NCs, dtype=jnp.int32)
    r = lanei // NCs
    o = aq // cap
    j = aq % cap
    idx = o * (FLUSH * cap) + r * cap + j
    ok = aq >= 0
    return jnp.where(
        ok, recv[jnp.where(ok, idx, 0)], jnp.uint32(0)
    )


def _bucket_scatter(dest, ndest: int, cap: int, valid, cols, fills):
    """One-hot running-rank bucketing shared by both routing stages:
    scatter each valid lane to slot ``dest * cap + rank_within_dest``.
    Rank-overflow and invalid lanes target the out-of-bounds index and
    are genuinely dropped (``over`` flags the loss — fail-stop/recover
    upstream, never silent).  Returns ([ndest*cap] planes, q, over)
    where ``q`` is each lane's slot (-1 for dropped/invalid lanes) —
    the producer-side return address for the dedup-flag gather."""
    onehot = (
        dest[:, None] == jnp.arange(ndest, dtype=jnp.int32)[None, :]
    ) & valid[:, None]
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    rank = jnp.take_along_axis(
        ranks, jnp.clip(dest, 0, ndest - 1)[:, None], axis=1
    )[:, 0] - 1
    over = jnp.any(ranks[-1] > cap)
    fit = valid & (rank < cap)
    q = jnp.where(fit, dest * cap + rank, ndest * cap)
    outs = [
        jnp.full((ndest * cap,), fill, col.dtype).at[q].set(
            col, mode="drop", unique_indices=True
        )
        for col, fill in zip(cols, fills)
    ]
    return outs, jnp.where(fit, q, -1), over


@spans.staged("route")
def _route_keys_2d(
    kcols, ak, aq2, acc_off, r,
    D: int, I: int, CAPD: int, CAPO2: int,
):
    """Hierarchical keys-only owner routing over a (dcn, ici) mesh:
    stage 1 buckets lanes by owner SLICE (``owner // I``) and routes
    K+1 planes (keys + owner id) over dcn — all cross-slice traffic for
    a slice pair rides one aggregated transfer; stage 2 buckets the
    received keys by owner CHIP (``owner % I``) and routes K planes
    over ici.  The stage-2 slot map ``q2`` is saved per round in the
    intermediate shard's ``aq2`` so the dedup flags can retrace both
    hops positionally (``_flags_back_2d``).  Returns
    ``(ak', q1, aq2', over)``."""
    K = len(kcols)
    valid = kcols[0] != SENTINEL
    for c in kcols[1:]:
        valid = valid | (c != SENTINEL)
    owner = _owner(kcols, D * I)
    # ---- stage 1: to the owner slice, over DCN ----
    cols1 = list(kcols) + [owner.astype(jnp.uint32)]
    fills1 = [SENTINEL] * K + [jnp.uint32(0)]
    outs1, q1, over1 = _bucket_scatter(
        owner // jnp.int32(I), D, CAPD, valid, cols1, fills1
    )
    stack1 = jnp.stack(outs1).reshape(K + 1, D, CAPD)
    r1 = lax.all_to_all(
        stack1, DCN_AXIS, split_axis=1, concat_axis=1, tiled=False
    ).reshape(K + 1, D * CAPD)
    # ---- stage 2: to the owner chip within the slice, over ICI ----
    k1 = tuple(r1[i] for i in range(K))
    v1 = k1[0] != SENTINEL
    for c in k1[1:]:
        v1 = v1 | (c != SENTINEL)
    own1 = r1[K].astype(jnp.int32)
    outs2, q2, over2 = _bucket_scatter(
        own1 % jnp.int32(I), I, CAPO2, v1, list(k1), [SENTINEL] * K
    )
    stack2 = jnp.stack(outs2).reshape(K, I, CAPO2)
    r2 = lax.all_to_all(
        stack2, ICI_AXIS, split_axis=1, concat_axis=1, tiled=False
    ).reshape(K, I * CAPO2)
    ak = tuple(
        lax.dynamic_update_slice(a, r2[i], (acc_off,))
        for i, a in enumerate(ak)
    )
    aq2 = lax.dynamic_update_slice(aq2, q2, (r * D * CAPD,))
    return ak, q1, aq2, over1 | over2


@spans.staged("route")
def _flags_back_2d(
    flag_owner, aq2, FLUSH: int, D: int, I: int, CAPD: int, CAPO2: int,
):
    """Inverse of ``_route_keys_2d`` for the dedup flags: owner →
    (ici) → intermediate, per-round gather through the saved ``q2``
    back to stage-1 slot order, then (dcn) → producer.  One u32 plane
    per hop.  DCN all_to_all preserves the chip index, so the
    intermediate holder of a producer's stage-1 block is the chip with
    the producer's own chip index in the owner slice — both inversions
    are purely positional."""
    f = flag_owner.reshape(FLUSH, I, CAPO2).transpose(1, 0, 2)
    recv_i = lax.all_to_all(
        f, ICI_AXIS, split_axis=0, concat_axis=0, tiled=False
    ).reshape(I * FLUSH * CAPO2)
    DC = D * CAPD
    j = jnp.arange(FLUSH * DC, dtype=jnp.int32)
    r = j // DC
    ok = aq2 >= 0
    idx = (
        (aq2 // CAPO2) * (FLUSH * CAPO2) + r * CAPO2 + aq2 % CAPO2
    )
    fl1 = jnp.where(
        ok, recv_i[jnp.where(ok, idx, 0)], jnp.uint32(0)
    )
    f1 = fl1.reshape(FLUSH, D, CAPD).transpose(1, 0, 2)
    return lax.all_to_all(
        f1, DCN_AXIS, split_axis=0, concat_axis=0, tiled=False
    ).reshape(D * FLUSH * CAPD)


class ShardedDeviceChecker:
    """Level-synchronous BFS over a 1-D (ici) or 2-D (dcn x ici) device
    mesh, fully device-resident.

    Capacities are PER SHARD; hash ownership keeps shards balanced to
    within sampling noise, so per-shard capacity ~ total / n_shards.
    """

    # local-gid bits in the global id (shard << SB | local); derived
    # per instance: small meshes get the widest possible local stores
    # (round 5: the fixed SB=26 capped an n=1 store at 67M rows, below
    # what the 40M-state bench tier needs with its append windows)

    def __init__(
        self,
        model,
        n_devices: Optional[int] = None,
        invariants: Optional[Tuple[str, ...]] = None,
        check_deadlock: bool = True,
        sub_batch: int = 1024,
        expand_chunk: Optional[int] = None,
        visited_cap: int = 1 << 14,
        max_states: int = 1 << 26,
        time_budget_s: Optional[float] = None,
        progress: bool = False,
        metrics_path: Optional[str] = None,
        group: int = 4,
        flush_factor: int = 1,
        fp_bits: Optional[int] = None,
        route_slack: float = 1.5,
        append_chunk: Optional[int] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 5,
        n_slices: int = 1,
        telemetry=None,
        heartbeat_s: Optional[float] = None,
    ):
        self.model = model
        self.layout = model.layout
        if invariants is None:
            invariants = getattr(
                model, "default_invariants", pyeval.DEFAULT_INVARIANTS
            )
        self.invariant_names = tuple(invariants)
        model_invs = getattr(model, "invariants", None)
        if (
            model_invs is not None
            and "__EvalError__" in model_invs
            and "__EvalError__" not in self.invariant_names
        ):
            self.invariant_names += ("__EvalError__",)
        self.check_deadlock = check_deadlock
        devs = jax.devices()
        self.N = n_devices or len(devs)
        if self.N > len(devs):
            raise ValueError(f"need {self.N} devices, have {len(devs)}")
        # gid = shard << SB | local must stay a positive int32
        self.SB = 30 - max(0, (self.N - 1).bit_length())
        if self.SB < 16:
            raise ValueError("too many shards for the global-gid encoding")
        if n_slices > 1:
            # multi-slice: a (dcn, ici) grid — shard s lives at slice
            # ``s // I``, chip ``s % I``; routing goes owner-slice-
            # then-owner-chip so cross-slice bytes ride DCN once
            if self.N % n_slices:
                raise ValueError(
                    "n_devices must be divisible by n_slices"
                )
            self.D, self.I = n_slices, self.N // n_slices
            self._axes: Tuple[str, ...] = (DCN_AXIS, ICI_AXIS)
            self.mesh = Mesh(
                np.array(devs[: self.N]).reshape(self.D, self.I),
                self._axes,
            )
        else:
            self.D, self.I = 1, self.N
            self._axes = (AXIS,)
            self.mesh = Mesh(np.array(devs[: self.N]), (AXIS,))
        self.A = model.A
        self.W = self.layout.W
        self.G = sub_batch  # states expanded per shard per round
        self.Fi = expand_chunk or min(sub_batch, 8192)
        if self.G % self.Fi:
            raise ValueError("sub_batch must be a multiple of expand_chunk")
        self.NCs = self.G * self.A  # candidate lanes sent per shard/round
        # per-destination route capacity; hash ownership concentrates
        # counts at NCs/N, so slack=1.5 is far beyond sampling noise —
        # and an overflow auto-recovers (double slack, re-jit, retry
        # the level), never corrupts
        self.route_slack = route_slack
        self.FLUSH = flush_factor
        self.SL = append_chunk or (1 << 14)
        self._calc_route()
        self.keys = KeySpec(self.layout.total_bits, self.W, fp_bits)
        self.K = self.keys.ncols
        if fp_bits is None:
            self.keys.warn_if_hashed(max_states)
        # The visited set: per-shard ownership-sharded HBM hash tables
        # (ops/fpset.py) — the routed key planes probe the OWNER's
        # table, so owner-side dedup is O(routed batch), not O(owned
        # keys).  VCAP is "max owned keys per shard before growth"; the
        # table carries TCAP = 2 * VCAP slots so the nk_bound <= VCAP
        # invariant IS the load-factor <= 1/2 contract.
        # fpset probe schedule: the two-step ladder, by value.  Not
        # the single-chip engine's halving ladder (PR 37): this
        # engine's programs are traced and lowered again every check,
        # so every step is paid a check, for a flush of 98,304 lanes
        # that holds some 1,852 valid ones (ROADMAP S5, S9 (a))
        self.fps_dense = fpset.DENSE_ROUNDS
        self.fps_stages = fpset.STAGES_TWO_STEP
        self.VCAP = self._round_cap(visited_cap)
        self.TCAP = 2 * self.VCAP
        self.SCAP = max_states  # global
        self.LCAP = max(
            min(
                self._round_cap(max(visited_cap, self.NCs)),
                max(max_states // self.N, self.NCs) + self.APAD,
            ),
            self.APAD,
        )
        if self.LCAP > 1 << self.SB:
            raise ValueError("per-shard store exceeds local-gid bits")
        if self.ACAP * self.W >= 1 << 31 or self.LCAP * self.W >= 1 << 31:
            raise ValueError("flat buffers exceed int32 addressing")
        self.time_budget_s = time_budget_s
        self.progress = progress
        self.metrics_path = metrics_path
        # mesh-wide HBM-recovery bookkeeping shared with the
        # single-chip engine (utils/recovery.py, r9): armed frames,
        # recovery count, degraded group-ahead + frozen headroom
        self.rec = recovery.RecoveryState(checkpoint_path, group)
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self._ckpt_frames = 0
        self._ckpt_bytes = 0
        self._ckpt_write_s = 0.0
        self._ckpt_retries = 0
        self._bufs_poisoned = False
        self._flush_seq = 0
        self._watcher = None
        self._jits: Dict[tuple, object] = {}
        self.last_stats: Dict[str, float] = {}
        self._last_fpm = None
        # unified telemetry (round 8): stream + heartbeat, both fed
        # from the existing stats fetch — zero extra device syncs
        self._telemetry_arg = telemetry
        self.tel = obs.NULL
        self.heartbeat_s = heartbeat_s
        self._run_id: Optional[str] = None
        self._snap: Dict[str, object] = {}
        self._fetch_n = 0
        self._fpm_prev = np.zeros((fpset.FPM_LOGICAL_N,), np.int64)
        self._compact_n = 0
        self._compact_prev = 0
        self._resume_meta: Dict[str, object] = {}
        # host phases, dispatches and exchanges of the current run
        # (obs/spans.py); outside a run they count into these idle ones
        self._clock = spans.PhaseClock()
        self._dispatch_n = 0
        self._route_rounds: Dict[int, int] = {}
        self._route_overflows = 0
        # keys moved and lanes presented by the run's table doublings
        self._rehash_keys = 0
        self._rehash_lane_rounds = 0

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

    @property
    def _host_wait_s(self) -> float:
        """Seconds this run's host has been blocked on stats fetches:
        the ``fetch`` phase of its clock."""
        return self._clock.seconds_of("fetch")

    def _calc_route(self):
        """Derive every route-capacity-dependent size from the current
        ``route_slack`` (re-run by overflow recovery).

        Round 5 (producer-local rows): two accumulators per shard —
        ``ACAP`` lanes of OWNER-side routed keys (K planes) and
        ``PACAP = NCs * FLUSH`` lanes of PRODUCER-side candidate rows /
        parent / lane / return-address, which never travel.
        ``route_cap`` is the lanes one round's key exchange is compiled
        to carry a shard (both hops of a 2-D mesh together)."""
        if self.N == 1:
            # singleton mesh: no routing at all (the n=1 fast path
            # appends lanes straight into the accumulator), so no
            # slack inflation either — shapes match the single-chip
            # engine exactly
            self.CAPO = self.NCs
            self.RCV = self.NCs
            self.route_cap = 0
        elif len(self._axes) == 1:
            self.CAPO = int(-(-self.NCs * self.route_slack // self.N))
            self.RCV = self.N * self.CAPO
            self.route_cap = self.RCV
        else:
            # expected per-destination fill is NCs/D (stage 1, slices)
            # and NCs/I (stage 2, chips within the slice)
            self.CAPD = int(-(-self.NCs * self.route_slack // self.D))
            self.CAPO2 = int(-(-self.NCs * self.route_slack // self.I))
            self.RCV = self.I * self.CAPO2
            self.route_cap = self.D * self.CAPD + self.RCV
        self.ACAP = self.RCV * self.FLUSH
        self.PACAP = self.NCs * self.FLUSH
        # append chunking runs over the PRODUCER accumulator
        self.SLc = min(self.SL, self.PACAP)
        self.C = -(-self.PACAP // self.SLc)
        self.APAD = self.C * self.SLc

    def _dev_fill(self, shape, fill, dtype):
        """Constant-filled sharded buffer, materialized ON DEVICE.
        ``jnp.zeros(..., device=NamedSharding)`` builds the array on
        the host and ships it across the link — at bench tiers that is
        ~6 GB of zero buffers, silently charged to the first BFS
        levels (scripts/probe_sharded_latency.py measures it)."""
        key = ("fill", shape, jnp.dtype(dtype).name)
        fn = self._jits.get(key)
        if fn is None:
            # shard_map forces one per-device block fill (a plain
            # jitted constant gets folded to a replicated constant that
            # fights the sharding annotation); the fill value rides as
            # a traced argument
            block = (1,) + tuple(shape[1:])
            fn = jax.jit(
                jax.shard_map(
                    lambda v: jnp.broadcast_to(v, block),
                    mesh=self.mesh,
                    in_specs=P(),
                    out_specs=P(self._axes),
                    check_vma=False,
                )
            )
            self._jits[key] = fn
        # the program is a ``shard_map`` of a lambda and has no name of
        # its own: ``fill`` on the clock
        with self._clock.upload("fill", 1):
            fill_d = jnp.asarray(fill, dtype)
        with self._clock.call("fill"):
            return fn(fill_d)

    def _alloc_acc(self, bufs):
        """(Re)allocate the per-shard accumulator buffers (fresh run,
        overflow recovery, restore): owner-side routed keys at ACAP,
        producer-side rows/par/lane/return-address at PACAP."""
        N, K = self.N, self.K
        bufs["ak"] = tuple(
            self._dev_fill((N, self.ACAP), SENTINEL, jnp.uint32)
            for _ in range(K)
        )
        bufs["arows"] = self._dev_fill(
            (N, self.W, self.PACAP), 0, jnp.uint32
        )
        bufs["apar"] = self._dev_fill((N, self.PACAP), 0, jnp.int32)
        bufs["alane"] = self._dev_fill((N, self.PACAP), 0, jnp.int32)
        bufs["aq"] = self._dev_fill((N, self.PACAP), 0, jnp.int32)
        if len(self._axes) == 2:
            # stage-2 slot map per round, saved on the intermediate
            # shard for the positional flag return
            bufs["aq2"] = self._dev_fill(
                (N, self.FLUSH * self.D * self.CAPD), 0, jnp.int32
            )
        else:
            bufs["aq2"] = self._dev_fill((N, 1), 0, jnp.int32)

    def _shard_idx(self):
        """Traced global shard index inside a shard_map body."""
        if len(self._axes) == 1:
            return lax.axis_index(AXIS).astype(jnp.int32)
        return (
            lax.axis_index(DCN_AXIS) * self.I + lax.axis_index(ICI_AXIS)
        ).astype(jnp.int32)

    def _route_acc(self, kcols, ak, aq, aq2, rt, w):
        """Producer-side half of a round: route keys to their owners
        and save the per-lane return address.  Rows/par/lane are NOT
        here — the caller stores them producer-locally.  Returns
        ``(ak', aq', aq2', rt')``, ``rt`` being the shard's route state
        (``_route_note``)."""
        o_off = w * self.RCV
        if self.N == 1:
            # -workers 1 must not be a perf trap (VERDICT r3 #4): the
            # one-hot bucketing + all_to_all cost ~2 s/round in plane
            # scatters on a singleton mesh where every lane is already
            # home — and the dedup flags are consumed in place, so no
            # return address is needed either
            ak = tuple(
                lax.dynamic_update_slice(a, c, (o_off,))
                for a, c in zip(ak, kcols)
            )
            return ak, aq, aq2, rt
        if len(self._axes) == 1:
            ak, q, over = _route_keys(
                kcols, ak, o_off, self.N, self.CAPO
            )
        else:
            ak, q, aq2, over = _route_keys_2d(
                kcols, ak, aq2, o_off, w,
                self.D, self.I, self.CAPD, self.CAPO2,
            )
        aq = lax.dynamic_update_slice(aq, q, (w * self.NCs,))
        return ak, aq, aq2, _route_note(rt, over, q)

    def _round_cap(self, c: int) -> int:
        n = 1 << 10
        while n < c:
            n <<= 1
        return n

    def _vk_width(self) -> int:
        """Per-shard width of a visited column: TCAP slots + the trash
        row."""
        return self.TCAP + 1

    def _log(self, msg: str):
        if self.progress:
            import sys

            print(f"  {msg}", file=sys.stderr, flush=True)

    def _shard(self, spec=None):
        return NamedSharding(
            self.mesh, P(self._axes) if spec is None else spec
        )

    def _program(self, fn, donate=(), exchange=False):
        """``jax.jit(fn)`` whose calls count as dispatches of the run
        and, for a program that holds a key exchange, as a round at
        the route capacity it was compiled with.  ``fn`` is named
        ``ptt_*``: a program's name is part of its compile-cache key
        and its scopes are not (docs/observability.md)."""
        jitted = jax.jit(fn, donate_argnums=donate)
        cap = self.route_cap if exchange else 0

        def call(*args):
            self._dispatch_n += 1
            if cap:
                self._route_rounds[cap] = (
                    self._route_rounds.get(cap, 0) + 1
                )
            return jitted(*args)

        return call

    def _smap(self, body, in_specs, out_specs, donate=(), exchange=False):
        fn = jax.shard_map(
            body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return self._program(fn, donate, exchange)

    # ------------------------------------------------------ device code

    def _round_jit(self):
        """One BFS round: expand a per-shard frontier window, store the
        candidate rows/par/lane PRODUCER-LOCALLY, and route only the
        keys to their owners (VERDICT r4 #3).

        (ak cols, arows, apar, alane, aq, aq2, rows, lb, nf, dead,
        rt, r, w) -> (ak', arows', apar', alane', aq', aq2', dead',
        rt')
        """
        key = ("round", self.LCAP)
        if key in self._jits:
            return self._jits[key]
        m, layout, keyspec = self.model, self.layout, self.keys
        K, W, A, N = self.K, self.W, self.A, self.N
        G, Fi, NCs = self.G, self.Fi, self.NCs

        @spans.staged("expand")
        def ptt_shard_round(ak, arows, apar, alane, aq, aq2, rows, lb,
                            nf, dead, rt, r, w):
            # local blocks arrive with a leading length-1 shard axis
            ak = tuple(a[0] for a in ak)
            arows, apar, alane = arows[0], apar[0], alane[0]
            aq, aq2 = aq[0], aq2[0]
            rows, lb, nf, dead, rt = (
                rows[0], lb[0], nf[0], dead[0], rt[0]
            )
            shard = self._shard_idx()
            f_off = r * G
            window = lax.dynamic_slice(
                rows, ((lb + f_off) * W,), (G * W,)
            )

            def chunk(i):
                rws = lax.dynamic_slice(
                    window, (i * Fi * W,), (Fi * W,)
                ).reshape(Fi, W)
                pos = f_off + i * Fi + jnp.arange(Fi, dtype=jnp.int32)
                live = pos < nf
                states = jax.vmap(layout.unpack)(rws)
                succ, valid = jax.vmap(m.successors)(states)
                valid = valid & live[:, None]
                packed = jax.vmap(jax.vmap(layout.pack))(succ)
                fa = Fi * A
                packedf = packed.reshape(fa, W)
                kcols = keyspec.make(packedf)
                vflat = valid.reshape(fa)
                kcols = tuple(
                    jnp.where(vflat, c, SENTINEL) for c in kcols
                )
                par = (shard << self.SB) | (
                    lb + pos[:, None] + jnp.zeros((1, A), jnp.int32)
                )
                lane = jnp.zeros((Fi, 1), jnp.int32) + jnp.arange(
                    A, dtype=jnp.int32
                )
                if self.check_deadlock:
                    stut = jax.vmap(m.stutter_enabled)(states)
                    dead_rows = live & ~jnp.any(valid, axis=1) & ~stut
                    didx = jnp.min(
                        jnp.where(
                            dead_rows,
                            (shard << self.SB) | (lb + pos), BIG,
                        )
                    )
                else:
                    didx = BIG
                return (
                    kcols, packedf, par.reshape(fa), lane.reshape(fa),
                    didx,
                )

            def scan_body(dead, i):
                kcols, p, par, lane, didx = chunk(i)
                return jnp.minimum(dead, didx), (kcols, p, par, lane)

            dead, (kcols, packed, par, lane) = lax.scan(
                scan_body, dead, jnp.arange(G // Fi, dtype=jnp.int32)
            )
            kcols = tuple(c.reshape(NCs) for c in kcols)
            packed = packed.reshape(NCs, W)
            par = par.reshape(NCs)
            lane = lane.reshape(NCs)

            # producer-local candidate store (never routed)
            p_off = w * NCs
            arows = lax.dynamic_update_slice(
                arows, packed.T, (0, p_off)
            )
            apar = lax.dynamic_update_slice(apar, par, (p_off,))
            alane = lax.dynamic_update_slice(alane, lane, (p_off,))
            ak, aq, aq2, rt = self._route_acc(
                kcols, ak, aq, aq2, rt, w
            )
            return (
                tuple(a[None] for a in ak), arows[None], apar[None],
                alane[None], aq[None], aq2[None], dead[None],
                rt[None],
            )

        sh = P(self._axes)
        in_specs = (
            (sh,) * self.K, sh, sh, sh, sh, sh, sh, sh, sh, sh, sh,
            P(), P(),
        )
        out_specs = ((sh,) * self.K, sh, sh, sh, sh, sh, sh, sh)
        fn = self._smap(
            ptt_shard_round, in_specs, out_specs,
            donate=(0, 1, 2, 3, 4, 5), exchange=True,
        )
        self._jits[key] = fn
        return fn

    def _init_round_jit(self):
        """Initial-state round: shard s generates init indices
        ``base + s, base + s + N, ...`` (stride N — round 5: with
        producer-local rows a CONTIGUOUS split handed every init state
        of a small Init set to shard 0, and since discovery stays on
        the producing shard the whole mesh degenerated to one working
        shard; striping balances the roots and therefore the whole
        search) — same contract as an expand round (par = -1 -
        init_idx)."""
        key = ("initround",)
        if key in self._jits:
            return self._jits[key]
        m, layout, keyspec = self.model, self.layout, self.keys
        K, W, N = self.K, self.W, self.N
        NCs = self.NCs
        n_init = min(m.n_initial, (1 << 31) - 1)

        Fi = self.Fi

        N = self.N

        def chunk(start, i):
            # Fi lanes per scan step (an unchunked vmap over all NCs
            # lanes materializes the full unpacked state structs —
            # gigabytes at bench widths)
            idx = start + (
                i * Fi + jnp.arange(Fi, dtype=jnp.int32)
            ) * N
            states = jax.vmap(m.gen_initial)(
                jnp.where(idx < n_init, idx, 0)
            )
            packed = jax.vmap(layout.pack)(states)
            valid = idx < n_init
            kcols = keyspec.make(packed)
            return (
                tuple(jnp.where(valid, c, SENTINEL) for c in kcols),
                packed,
            )

        @spans.staged("init")
        def ptt_shard_init(ak, arows, apar, alane, aq, aq2, rt, base, w):
            ak = tuple(a[0] for a in ak)
            arows, apar, alane, rt = arows[0], apar[0], alane[0], rt[0]
            aq, aq2 = aq[0], aq2[0]
            start = base + self._shard_idx()
            idx = start + jnp.arange(NCs, dtype=jnp.int32) * N
            _, (kcols, packed) = lax.scan(
                lambda c, i: (c, chunk(start, i)),
                0,
                jnp.arange(NCs // Fi, dtype=jnp.int32),
            )
            kcols = tuple(c.reshape(NCs) for c in kcols)
            packed = packed.reshape(NCs, W)
            par = -1 - idx
            lane = jnp.zeros((NCs,), jnp.int32)

            p_off = w * NCs
            arows = lax.dynamic_update_slice(
                arows, packed.T, (0, p_off)
            )
            apar = lax.dynamic_update_slice(apar, par, (p_off,))
            alane = lax.dynamic_update_slice(alane, lane, (p_off,))
            ak, aq, aq2, rt = self._route_acc(
                kcols, ak, aq, aq2, rt, w
            )
            return (
                tuple(a[None] for a in ak), arows[None], apar[None],
                alane[None], aq[None], aq2[None], rt[None],
            )

        sh = P(self._axes)
        in_specs = ((sh,) * self.K, sh, sh, sh, sh, sh, sh, P(), P())
        out_specs = ((sh,) * self.K, sh, sh, sh, sh, sh, sh)
        fn = self._smap(
            ptt_shard_init, in_specs, out_specs,
            donate=(0, 1, 2, 3, 4, 5), exchange=True,
        )
        self._jits[key] = fn
        return fn

    def _flush_jit(self):
        """Owner-side dedup of the routed key accumulator into the
        visited set, then the positional flag return: owner-order
        new-flags travel back through the inverse all_to_all(s) and
        land as PRODUCER-acc-order flags via the saved return
        addresses — one u32 plane per hop instead of the round-4
        design's K+2+W routed planes per round.

        The routed key planes PROBE the owner's HBM hash table
        (``fpset.lookup_or_insert``): the probe's is_new IS the
        owner-acc-order flag vector, and per-shard probe metrics
        accumulate in ``fpm``."""
        key = ("flush", self.VCAP, self.fps_dense, self.fps_stages)
        if key in self._jits:
            return self._jits[key]
        K, ACAP, PACAP = self.K, self.ACAP, self.PACAP

        @spans.staged("probe")
        def ptt_shard_flush(vk, ak, aq, aq2, n_keys, fpm, n_acc):
            vk = tuple(v[0] for v in vk)
            ak = tuple(a[0] for a in ak)
            aq, aq2, n_keys, fpm = aq[0], aq2[0], n_keys[0], fpm[0]
            lanei = jnp.arange(ACAP, dtype=jnp.int32)
            amask = lanei < n_acc
            valid = amask & ~fpset.all_sentinel(ak)
            is_new, vk2, n_failed, rounds, lane_rounds, _, _ = (
                fpset.lookup_or_insert(
                    vk, ak, valid,
                    dense_rounds=self.fps_dense,
                    stages=self.fps_stages,
                )
            )
            n_new_owner = jnp.sum(is_new.astype(jnp.int32))
            flag_own = is_new.astype(jnp.uint32)
            # zero-sync metrics (r9, = device_bfs.FPM_N):
            # valid_lanes is the routed-candidate count after
            # masking (duplicate-rate denominator; hi/lo uint32
            # words since r12); col 4 is the worst flush's probe
            # depth (running max, not a sum)
            fpm = fpset.fpm_update(
                fpm, rounds, n_failed,
                jnp.sum(valid.astype(jnp.int32)), lane_rounds,
            )
            if self.N == 1:
                flag_local = flag_own  # PACAP == ACAP, same order
            elif len(self._axes) == 1:
                recv = _flags_back(
                    flag_own, self.FLUSH, self.N, self.CAPO
                )
                flag_local = _flag_gather(
                    recv, aq, self.FLUSH, self.CAPO, self.NCs
                )
            else:
                recv = _flags_back_2d(
                    flag_own, aq2, self.FLUSH, self.D, self.I,
                    self.CAPD, self.CAPO2,
                )
                flag_local = _flag_gather(
                    recv, aq, self.FLUSH, self.CAPD, self.NCs
                )
            n_new_local = jnp.sum(flag_local.astype(jnp.int32))
            return (
                tuple(v[None] for v in vk2),
                (n_keys + n_new_owner)[None],
                n_new_local[None], flag_local[None], fpm[None],
            )

        sh = P(self._axes)
        fn = self._smap(
            ptt_shard_flush,
            ((sh,) * self.K, (sh,) * self.K, sh, sh, sh, sh, P()),
            ((sh,) * self.K, sh, sh, sh, sh),
            donate=(0,),
        )
        self._jits[key] = fn
        return fn

    def _compact_jit(self):
        """Per-shard compaction stage, split out of the append as its
        own dispatch (round 10): the producer-acc-order new-flag
        compacts the W word columns + routed parent/lane to the front
        in arrival order — ``(arows, apar, alane, flag_acc) -> (crows,
        cpar, clane)``, all producer-local (``ops/compact.py``).  The
        producer accumulator triple is DONATED and the compacted
        triple recycled as the next fill's buffers (same contract as
        the single-chip engine's split), so the extra dispatch adds no
        resident HBM."""
        key = ("compact",)
        if key in self._jits:
            return self._jits[key]
        W = self.W

        @spans.staged("compact")
        def ptt_shard_compact(arows, apar, alane, flag_acc):
            arows, apar, alane = arows[0], apar[0], alane[0]
            flag_acc = flag_acc[0]
            drop = flag_acc ^ jnp.uint32(1)
            cols = tuple(arows[j] for j in range(W)) + (
                lax.bitcast_convert_type(apar, jnp.uint32),
                lax.bitcast_convert_type(alane, jnp.uint32),
            )
            out, _idx = compact_ops.compact_by_flag(
                drop, cols, need_idx=False
            )
            crows = jnp.stack(out[:W])
            cpar = lax.bitcast_convert_type(out[W], jnp.int32)
            clane = lax.bitcast_convert_type(out[W + 1], jnp.int32)
            return crows[None], cpar[None], clane[None]

        sh = P(self._axes)
        fn = self._smap(
            ptt_shard_compact, (sh, sh, sh, sh), (sh, sh, sh),
            donate=(0, 1, 2),
        )
        self._jits[key] = fn
        return fn

    def _append_jit(self):
        """Per-shard append of the flush's new states (already
        compacted to the front in arrival order by ``_compact_jit``):
        invariants evaluate on exactly the new states in SL-sized
        chunks; one DUS lands rows + logs in the local store."""
        key = ("append", self.LCAP)
        if key in self._jits:
            return self._jits[key]
        W, PACAP = self.W, self.PACAP
        SL, C = self.SLc, self.C
        layout = self.layout
        inv_fns = [self.model.invariants[n] for n in self.invariant_names]
        n_inv = len(self.invariant_names)

        @spans.staged("append")
        def ptt_shard_append(rows, parent_log, lane_log, crows, cpar,
                             clane, n_new, n_visited, viol):
            rows, parent_log, lane_log = rows[0], parent_log[0], lane_log[0]
            crows, cpar, clane = crows[0], cpar[0], clane[0]
            n_new = n_new[0]
            n_visited, viol = n_visited[0], viol[0]
            shard = self._shard_idx()
            ccols = tuple(crows[j] for j in range(W))
            par = cpar
            lane = clane
            lanei = jnp.arange(PACAP, dtype=jnp.int32)
            live = lanei < n_new
            par = jnp.where(live, par, 0)
            lane = jnp.where(live, lane, 0)
            pad = C * SL - PACAP
            ecols = (
                tuple(
                    jnp.concatenate(
                        [c, jnp.zeros((pad,), jnp.uint32)]
                    )
                    for c in ccols
                )
                if pad
                else ccols
            )

            # one SL-chunked scan does BOTH invariant evaluation and
            # the row-store append (same shape as device_bfs: a
            # monolithic [ACAP, W] stack takes the 128-padded tiled
            # layout — 6.4x memory — and OOMs the XLA planner at
            # bench-tier accumulators)
            def chunk(carry, c):
                viol, store = carry
                off = c * SL
                rws = jnp.stack(
                    [
                        lax.dynamic_slice(col, (off,), (SL,))
                        for col in ecols
                    ],
                    axis=1,
                )
                if n_inv:
                    gids = (shard << self.SB) | (
                        n_visited + off
                        + jnp.arange(SL, dtype=jnp.int32)
                    )
                    livec = (
                        off + jnp.arange(SL, dtype=jnp.int32) < n_new
                    )
                    states = jax.vmap(layout.unpack)(rws)
                    vnew = []
                    for fn in inv_fns:
                        ok = jax.vmap(fn)(states)
                        bad = livec & ~ok
                        vnew.append(jnp.min(jnp.where(bad, gids, BIG)))
                    viol = jnp.minimum(viol, jnp.stack(vnew))
                store = lax.dynamic_update_slice(
                    store, rws.reshape(SL * W),
                    ((n_visited + off) * W,),
                )
                return (viol, store)

            # dynamic trip count (round 5): a flush yielding few new
            # states must not unpack/DUS the full APAD window
            n_chunks = jnp.minimum((n_new + SL - 1) // SL, C)
            viol, rows = lax.fori_loop(
                0, n_chunks, lambda c, carry: chunk(carry, c),
                (viol, rows),
            )
            parent_log = lax.dynamic_update_slice(
                parent_log, par, (n_visited,)
            )
            lane_log = lax.dynamic_update_slice(
                lane_log, lane, (n_visited,)
            )
            return (
                rows[None], parent_log[None], lane_log[None],
                (n_visited + n_new)[None], viol[None],
            )

        sh = P(self._axes)
        fn = self._smap(
            ptt_shard_append, (sh,) * 9, (sh,) * 5, donate=(0, 1, 2),
        )
        self._jits[key] = fn
        return fn

    # ----------------------------------------------- host-seeded starts

    SEED_CHUNK = 1 << 15

    def _seed_chunk(self) -> int:
        return min(ShardedDeviceChecker.SEED_CHUNK, self.APAD, self.NCs)

    def _seed_write_jit(self):
        """Write one SEED_CHUNK of host-enumerated states into the
        local stores (rows/parent/lane at fixed-shape DUS windows) and
        evaluate invariants on the chunk — fixed shapes so the warmup
        can precompile it once for any seed size."""
        key = ("seedwrite", self.LCAP)
        if key in self._jits:
            return self._jits[key]
        W = self.W
        SC = self._seed_chunk()
        layout = self.layout
        inv_fns = [self.model.invariants[n] for n in self.invariant_names]
        n_inv = len(self.invariant_names)

        @spans.staged("seed")
        def ptt_shard_seed_write(rows, parent_log, lane_log, viol,
                                 seed_rows, seed_par, seed_lane, n_local,
                                 off):
            rows, parent_log, lane_log = (
                rows[0], parent_log[0], lane_log[0],
            )
            viol, n_local = viol[0], n_local[0]
            srows = lax.dynamic_slice(
                seed_rows[0], (off * W,), (SC * W,)
            )
            spar = lax.dynamic_slice(seed_par[0], (off,), (SC,))
            slane = lax.dynamic_slice(seed_lane[0], (off,), (SC,))
            shard = self._shard_idx()
            rows = lax.dynamic_update_slice(rows, srows, (off * W,))
            parent_log = lax.dynamic_update_slice(
                parent_log, spar, (off,)
            )
            lane_log = lax.dynamic_update_slice(lane_log, slane, (off,))
            if n_inv:
                idx = off + jnp.arange(SC, dtype=jnp.int32)
                live = idx < n_local
                states = jax.vmap(layout.unpack)(srows.reshape(SC, W))
                gids = (shard << self.SB) | idx
                vnew = []
                for fn in inv_fns:
                    ok = jax.vmap(fn)(states)
                    bad = live & ~ok
                    vnew.append(jnp.min(jnp.where(bad, gids, BIG)))
                viol = jnp.minimum(viol, jnp.stack(vnew))
            return (
                rows[None], parent_log[None], lane_log[None],
                viol[None],
            )

        sh = P(self._axes)
        fn = self._smap(
            ptt_shard_seed_write, (sh, sh, sh, sh, sh, sh, sh, sh, P()),
            (sh, sh, sh, sh), donate=(0, 1, 2),
        )
        self._jits[key] = fn
        return fn

    def _seed_src(self, n_states: int) -> tuple:
        """(SRC, Mp) for a seed of ``n_states``: the seed-round chunk
        size and the padded per-shard store length.  SRC scales with
        the seed, never past one expand round — padding the seed
        arrays to a full NCs window would ship 680 MB across the link
        for a 51 MB seed."""
        SC = self._seed_chunk()
        M = -(-n_states // self.N)
        msc = max(SC, -(-M // SC) * SC)
        src = max(SC, min((self.NCs // SC) * SC, msc))
        return src, -(-msc // src) * src

    def _seed_round_jit(self, SRC: int):
        """Route one SRC-chunk of local seed-state KEYS to their owner
        shards (the regular flush then inserts them; the append is
        skipped — rows were written by ``_seed_write_jit``).  On a
        singleton mesh the keys pack contiguously at ``w * SRC`` (a
        partial RCV window would leave stale slots inside n_acc)."""
        key = ("seedround", SRC)
        if key in self._jits:
            return self._jits[key]
        W = self.W
        keyspec = self.keys

        @spans.staged("seed")
        def ptt_shard_seed_round(ak, aq, aq2, rt, rows_flat, n_local,
                                 off, w):
            ak = tuple(a[0] for a in ak)
            aq, aq2, rt = aq[0], aq2[0], rt[0]
            rows_flat, n_local = rows_flat[0], n_local[0]
            chunk = lax.dynamic_slice(
                rows_flat, (off * W,), (SRC * W,)
            ).reshape(SRC, W)
            kcols = keyspec.make(chunk)
            valid = off + jnp.arange(SRC, dtype=jnp.int32) < n_local
            kcols = tuple(
                jnp.where(valid, c, SENTINEL) for c in kcols
            )
            if self.N == 1:
                ak = tuple(
                    lax.dynamic_update_slice(a, c, (w * SRC,))
                    for a, c in zip(ak, kcols)
                )
            else:
                ak, aq, aq2, rt = self._route_acc(
                    kcols, ak, aq, aq2, rt, w
                )
            return (
                tuple(a[None] for a in ak), aq[None], aq2[None],
                rt[None],
            )

        sh = P(self._axes)
        fn = self._smap(
            ptt_shard_seed_round,
            ((sh,) * self.K, sh, sh, sh, sh, sh, P(), P()),
            ((sh,) * self.K, sh, sh, sh), donate=(0, 1, 2),
            exchange=True,
        )
        self._jits[key] = fn
        return fn

    @spans.in_phase("seed_load")
    def _load_seed(self, bufs, st, seed):
        """Bulk-load a host-enumerated BFS prefix (same contract as
        ``device_bfs._load_seed``): states in BFS order with parent
        gids (roots ``-1 - init_idx``) and action lanes, plus
        per-level sizes.  Producer assignment is round-robin by BFS
        index (state i -> shard ``i % N``, local ``i // N``), which
        keeps levels contiguous in every local store; parent gids are
        remapped to the sharded ``shard << SB | local`` numbering.
        Returns ``(level_sizes, lb, nf)``."""
        rows, parents, lanes, lsizes = seed
        rows = np.ascontiguousarray(rows, np.uint32)
        n = len(rows)
        N, W = self.N, self.W
        if sum(lsizes) != n:
            raise ValueError("seed level sizes do not sum to the count")
        if n > self.SCAP:
            raise ValueError(f"seed too large ({n} states)")
        par = np.asarray(parents, np.int64)
        mask = par >= 0
        par_new = par.copy()
        par_new[mask] = ((par[mask] % N) << self.SB) | (par[mask] // N)
        SC = self._seed_chunk()
        SRC, Mp = self._seed_src(n)
        npad = N * Mp

        def to_shards(a, dtype, width=None):
            a = np.ascontiguousarray(a, dtype)
            shape = (npad,) + a.shape[1:]
            p = np.zeros(shape, dtype)
            p[:n] = a
            p = p.reshape(Mp, N, -1).transpose(1, 0, 2)
            return p.reshape(N, -1) if width else p.reshape(N, Mp)

        rows_sh = to_shards(rows, np.uint32, width=W)
        par_sh = to_shards(par_new.astype(np.int32), np.int32)
        lane_sh = to_shards(
            np.asarray(lanes, np.int32), np.int32
        )
        counts = np.array(
            [(n + N - 1 - s) // N for s in range(N)], np.int64
        )
        pre = n - lsizes[-1]
        lb = np.array(
            [(pre + N - 1 - s) // N for s in range(N)], np.int64
        )
        nf = counts - lb
        self._grow_visited(bufs, n + self.ACAP)
        self._grow_store(bufs, Mp + self.APAD)
        sh = self._shard()
        rows_d = jax.device_put(rows_sh, sh)
        par_d = jax.device_put(par_sh, sh)
        lane_d = jax.device_put(lane_sh, sh)
        nloc_d = jax.device_put(counts.astype(np.int32), sh)
        jax.block_until_ready(rows_d)
        write = self._seed_write_jit()
        clock = self._clock
        for off in range(0, Mp, SC):
            with clock.upload("ptt_shard_seed_write", 1):
                off_d = jnp.int32(off)
            with clock.call("ptt_shard_seed_write"):
                (
                    bufs["rows"], bufs["parent"], bufs["lane"],
                    st["viol"],
                ) = write(
                    bufs["rows"], bufs["parent"], bufs["lane"],
                    st["viol"], rows_d, par_d, lane_d, nloc_d, off_d,
                )
        jax.block_until_ready(bufs["rows"])
        st["n_visited"] = jax.device_put(counts.astype(np.int32), sh)
        # key insertion through the regular routed flush (append
        # skipped — rows are already in place); retried wholesale on a
        # routing overflow, which dedups to a no-op
        while True:
            try:
                seed_round = self._seed_round_jit(SRC)
                w = 0
                for off in range(0, Mp, SRC):
                    with clock.upload("ptt_shard_seed_round", 2):
                        off_d, w_d = jnp.int32(off), jnp.int32(w)
                    with clock.call("ptt_shard_seed_round"):
                        out = seed_round(
                            bufs["ak"], bufs["aq"], bufs["aq2"],
                            st["rt"], rows_d, nloc_d, off_d, w_d,
                        )
                    bufs["ak"] = tuple(out[0])
                    bufs["aq"], bufs["aq2"], st["rt"] = out[1:]
                    w += 1
                    if w == self.FLUSH or off + SRC >= Mp:
                        # singleton meshes pack contiguously (w * SRC
                        # keys); routed meshes rebuild full RCV windows
                        n_acc = w * (SRC if N == 1 else self.RCV)
                        with clock.upload("ptt_shard_flush", 1):
                            n_acc_d = jnp.int32(n_acc)
                        with clock.call("ptt_shard_flush"):
                            fout = self._flush_jit()(
                                bufs["vk"], bufs["ak"], bufs["aq"],
                                bufs["aq2"], st["n_keys"], st["fpm"],
                                n_acc_d,
                            )
                        bufs["vk"] = tuple(fout[0])
                        st["n_keys"] = fout[1]
                        st["fpm"] = fout[4]
                        w = 0
                # the fetch surfaces routing overflows (``rt``'s sticky
                # flag)
                # so the except below can actually engage — without it
                # dropped seed keys would masquerade as duplicates
                stats = self._fetch(st)
                nk = int(stats[:, 1].sum())
                break
            except _RouteOverflow:
                self._grow_route(bufs, st)
        if nk != n:
            raise ValueError(
                f"seed states are not all distinct ({nk} of {n} unique)"
            )
        return [int(x) for x in lsizes], lb, nf

    def _stats_jit(self):
        key = ("stats",)
        if key in self._jits:
            return self._jits[key]

        @spans.staged("levelctl")
        def ptt_shard_stats(n_visited, n_keys, dead, viol, rt, fpm):
            return jnp.concatenate(
                [
                    n_visited[:, None], n_keys[:, None], dead[:, None],
                    viol, rt, fpm,
                ],
                axis=1,
            )

        fn = self._program(ptt_shard_stats)
        self._jits[key] = fn
        return fn

    # ------------------------------------------------------------ growth

    def _rehash_jit(self):
        """fpset growth: every shard rehashes its own table into a
        double-capacity one inside the same shard_map dispatch —
        (vk cols) -> (vk' cols, per-shard rehash vector:
        ``fpset.rhm_logical``)."""
        key = ("rehash", self.TCAP)
        if key in self._jits:
            return self._jits[key]
        K, TCAP = self.K, self.TCAP

        @spans.staged("rehash")
        def ptt_shard_rehash(vk):
            vk = tuple(v[0] for v in vk)
            new, rhm = fpset.rehash_cols(
                vk, fpset.empty_cols(2 * TCAP, K)
            )
            return tuple(v[None] for v in new), rhm[None]

        sh = P(self._axes)
        fn = self._smap(ptt_shard_rehash, ((sh,) * K,), ((sh,) * K, sh))
        self._jits[key] = fn
        return fn

    @spans.in_phase("grow")
    def _grow_visited(self, bufs, need: int):
        while self.VCAP < need:
            with self._clock.call("ptt_shard_rehash"):
                out = self._rehash_jit()(bufs["vk"])
            bufs["vk"] = tuple(out[0])
            # one fetch, as before: every shard's fail-stop count and
            # its two rehash counters
            failed, keys, lane_rounds = fpset.rhm_logical(out[1])
            if failed:
                raise RuntimeError(
                    "fpset rehash overflow — table corrupted its "
                    "load-factor contract (bug)"
                )
            self._rehash_keys += keys
            self._rehash_lane_rounds += lane_rounds
            self.TCAP *= 2
            self.VCAP = self.TCAP // 2

    @spans.in_phase("grow")
    def _grow_store(self, bufs, need: int):
        cap = max(
            self.SCAP // self.N + self.APAD, self.NCs + self.APAD
        )
        while self.LCAP < need:
            pad = min(self.LCAP, max(cap - self.LCAP, need - self.LCAP))
            if self.rec.headroom_frozen:
                # reduced per-shard row budget after an HBM recovery:
                # grow to EXACTLY the capacity the pending flushes
                # need, never the doubling overshoot (per-shard rows
                # grow toward SCAP/N; the overshoot is what exhausted
                # the mesh).  The blind-DUS bound still holds — only
                # the speculative headroom is gone; if even this
                # minimal growth re-exhausts, the unarmed recovery
                # state truncates honestly (stop_reason="hbm").
                pad = need - self.LCAP
            bufs["rows"] = jnp.concatenate(
                [
                    bufs["rows"],
                    self._dev_fill(
                        (self.N, pad * self.W), 0, jnp.uint32
                    ),
                ],
                axis=1,
            )
            for k in ("parent", "lane"):
                bufs[k] = jnp.concatenate(
                    [
                        bufs[k],
                        self._dev_fill((self.N, pad), 0, jnp.int32),
                    ],
                    axis=1,
                )
            self.LCAP += pad
            if self.LCAP > 1 << self.SB:
                raise ValueError(
                    "per-shard store exceeds local-gid bits"
                )

    # ------------------------------------------------- checkpoint/resume

    def _config_sig(self) -> str:
        return repr(
            (
                ckpt.model_sig(self.model),
                self.invariant_names,
                self.check_deadlock,
                self.layout.total_bits,
                self.keys.ncols,
                self.keys.exact,
                self.N,
                self._axes,
                # SB fixes the gid encoding (shard << SB | local); a
                # frame written under a different split must not resume
                self.SB,
                # r5: producer-local rows changed the gid numbering and
                # the checkpoint fields — r4 frames must not resume.
                # r6: frames store hash-table columns, not the sorted
                # prefixes of r5
                "sharded_device_r6_fpset",
            )
        )

    @spans.in_phase("ckpt")
    def _save_checkpoint(self, bufs, st, level_sizes, lb, nf, t0):
        """Level-boundary snapshot of the full per-shard device state
        (SURVEY.md §2.2-E8 on the device-resident sharded engine:
        VERDICT r3 #6): visited keys, packed row store, parent/lane
        trace logs, per-shard counts, and the level frame
        ``(level_sizes, lb, nf)`` meaning "about to expand the
        contiguous frontier [lb, lb+nf) of each shard".  The atomic
        frame writer is shared with the single-chip engine
        (utils/ckpt.py); fpset visited sets use the compacted-occupancy
        codec — only occupied slots (keys + slot index) are stored, so
        frame size scales with the state count, not the table tier."""
        if self._bufs_poisoned:
            # device buffers hold donated/poisoned storage after an
            # unrecovered exhaustion — keep the previous (older but
            # valid) frame rather than overwrite it with garbage
            return
        t_stall = time.perf_counter()
        nvis = np.asarray(st["n_visited"]).astype(np.int64)
        nkeys = np.asarray(st["n_keys"]).astype(np.int64)
        mx = int(nvis.max())
        W = self.W
        vk_arrays = ckpt.pack_fpset(
            [np.asarray(col) for col in bufs["vk"]]
        )
        nbytes, write_s, retries = ckpt.save_frame(
            self.checkpoint_path,
            self._config_sig(),
            dict(
                vk_arrays,
                rows=np.asarray(bufs["rows"][:, : mx * W]),
                parent=np.asarray(bufs["parent"][:, :mx]),
                lane=np.asarray(bufs["lane"][:, :mx]),
                n_visited=nvis,
                n_keys=nkeys,
                level_sizes=np.asarray(level_sizes, np.int64),
                lb=np.asarray(lb, np.int64),
                nf=np.asarray(nf, np.int64),
                hbm_recovered=np.int64(self._hbm_recovered),
            ),
            wall_s=time.time() - t0,
            meta={
                "run_id": self._run_id,
                "frame_seq": self._ckpt_frames + 1,
                "level": len(level_sizes),
                "engine": "sharded_device",
            },
        )
        stall_s = time.perf_counter() - t_stall
        self._ckpt_frames += 1
        self._ckpt_bytes += nbytes
        self._ckpt_write_s += stall_s
        self._ckpt_retries += retries
        # a fresh frame re-arms mesh-wide OOM recovery (consumed by
        # the next rebuild; see utils/recovery.py)
        self.rec.arm()
        self.last_stats.update(
            ckpt_frames=self._ckpt_frames,
            ckpt_bytes=self._ckpt_bytes,
            ckpt_write_s=round(self._ckpt_write_s, 3),
            ckpt_retries=self._ckpt_retries,
        )
        self.tel.emit(
            "ckpt_frame",
            frame_seq=self._ckpt_frames,
            bytes=nbytes,
            write_s=round(write_s, 3),
            stall_s=round(stall_s, 3),
            retries=retries,
            level=len(level_sizes),
            distinct_states=int(nvis.sum()),
        )
        self._log(
            f"checkpoint: level {len(level_sizes)}, "
            f"{int(nvis.sum())} states ({nbytes >> 10} KiB, "
            f"{stall_s:.2f}s stall) -> {self.checkpoint_path}"
        )

    def load_checkpoint(self):
        # a file that isn't a checkpoint frame (round-3 host-staged
        # checkpoints, arbitrary files) fails with one clean message,
        # not a raw KeyError/zipfile error; r4-r6 full-column frames
        # predate the format-version field and still load (ADVICE r4)
        return ckpt.load_frame(self.checkpoint_path, self._config_sig())

    def _restore(self, d):
        """Rebuild sharded device buffers from a checkpoint dict;
        returns (bufs, st, level_sizes, lb, nf, saved_wall_s)."""
        N, W, K = self.N, self.W, self.K
        nvis = d["n_visited"].astype(np.int64)
        nkeys = d["n_keys"].astype(np.int64)
        mx = int(nvis.max())
        mk = int(nkeys.max())
        # capacity planning BEFORE allocating: the next flush may add a
        # full accumulator per shard, and the store must admit one
        # append window past the restored high-water mark
        # the snapshot fixes the table tier; growth (if the resumed
        # run needs it) goes through the regular rehash below.
        # v2 frames use the compacted-occupancy codec ("fp_tcap");
        # v1 frames snapshotted the full columns ("vk0") — both load
        fp_cols = (
            ckpt.unpack_fpset(d, K) if "fp_tcap" in d else None
        )
        self.TCAP = (
            fp_cols[0].shape[1] - 1
            if fp_cols is not None
            else int(d["vk0"].shape[1]) - 1
        )
        self.VCAP = self.TCAP // 2
        need_l = max(mx + self.APAD, self.NCs + self.APAD)
        while self.LCAP < need_l:
            self.LCAP = min(self.LCAP * 2, need_l)
        if self.LCAP > 1 << self.SB:
            raise ValueError("per-shard store exceeds local-gid bits")
        sh = self._shard()

        # only the REAL data crosses the link; the (much larger)
        # capacity padding is a device-side fill concatenated on device
        def pad_to(name, width, fill, dtype):
            a = np.ascontiguousarray(d[name], dtype)
            return jnp.concatenate(
                [
                    jax.device_put(a, sh),
                    self._dev_fill(
                        (N, width - a.shape[1]), fill, dtype
                    ),
                ],
                axis=1,
            )

        bufs = {
            "vk": tuple(
                jax.device_put(np.ascontiguousarray(c), sh)
                for c in fp_cols
            )
            if fp_cols is not None
            else tuple(
                jax.device_put(
                    np.ascontiguousarray(d[f"vk{i}"], np.uint32),
                    sh,
                )
                for i in range(K)
            ),
        }
        self._alloc_acc(bufs)
        bufs["rows"] = pad_to("rows", self.LCAP * W, 0, jnp.uint32)
        bufs["parent"] = pad_to("parent", self.LCAP, 0, jnp.int32)
        bufs["lane"] = pad_to("lane", self.LCAP, 0, jnp.int32)
        n_inv = len(self.invariant_names)
        st = {
            "n_visited": jax.device_put(
                nvis.astype(np.int32), sh
            ),
            "n_keys": jax.device_put(nkeys.astype(np.int32), sh),
            "dead": self._dev_fill((N,), int(BIG), jnp.int32),
            "viol": self._dev_fill((N, n_inv), int(BIG), jnp.int32),
            "rt": self._dev_fill((N, RT_N), 0, jnp.int32),
            "fpm": self._dev_fill((N, FPM_N), 0, jnp.int32),
        }
        # the next flush may add a full accumulator of owned keys
        # per shard; grow (rehash) now if the snapshot tier cannot
        # absorb that at load <= 1/2
        self._grow_visited(bufs, mk + self.ACAP)
        if "hbm_recovered" in d:
            # pre-r9 frames predate the field and restore at 0
            self.rec.hbm_recovered = max(
                self.rec.hbm_recovered, int(d["hbm_recovered"])
            )
        # the device fpm counters restart at zero after a restore;
        # flush-telemetry deltas must restart with them or every
        # record until the old totals are re-exceeded is suppressed
        self._fpm_prev = np.zeros((fpset.FPM_LOGICAL_N,), np.int64)
        return (
            bufs, st, [int(x) for x in d["level_sizes"]],
            d["lb"].astype(np.int64), d["nf"].astype(np.int64),
            float(d["wall_s"]),
        )

    # --------------------------------------------------------------- run

    def _prewarm_tiers(self):
        """Pre-compile the capacity tiers reachable under
        ``max_states`` (VERDICT r5 #8, sharded half).  The visited
        tiers are exact (the fpset rehash doubles); the per-shard
        row-store tiers follow the balanced doubling schedule toward
        ``SCAP/N`` — producer skew can push a
        shard past that (the growth formula then grows to exact need),
        so the store prewarm is best-effort: it covers the schedule
        every balanced run takes."""
        drain = jax.block_until_ready
        N, K = self.N, self.K
        save = (self.TCAP, self.VCAP, self.LCAP)
        cap_k = self.SCAP // self.N + (self.group + 1) * self.ACAP
        while self.VCAP < cap_k:
            out = self._rehash_jit()(
                tuple(
                    self._dev_fill(
                        (N, self._vk_width()), SENTINEL, jnp.uint32
                    )
                    for _ in range(K)
                )
            )
            drain(out)
            del out
            self.TCAP *= 2
            self.VCAP = self.TCAP // 2
            self._compile_flush_tier()
        cap_l = max(
            self.SCAP // self.N + self.APAD, self.NCs + self.APAD
        )
        cap_l = min(cap_l, 1 << self.SB)
        while self.LCAP < cap_l:
            self.LCAP += min(self.LCAP, cap_l - self.LCAP)
            self._compile_store_tier()
        self.TCAP, self.VCAP, self.LCAP = save

    def _compile_flush_tier(self):
        """Compile the flush program at the current VCAP tier on
        dummies (one tier's worth of transient HBM)."""
        N, K = self.N, self.K
        vk = tuple(
            self._dev_fill((N, self._vk_width()), SENTINEL, jnp.uint32)
            for _ in range(K)
        )
        ak = tuple(
            self._dev_fill((N, self.ACAP), SENTINEL, jnp.uint32)
            for _ in range(K)
        )
        aq = self._dev_fill((N, self.PACAP), 0, jnp.int32)
        aq2 = self._dev_fill(
            (N, self.FLUSH * self.D * self.CAPD)
            if len(self._axes) == 2
            else (N, 1),
            0, jnp.int32,
        )
        zk = self._dev_fill((N,), 0, jnp.int32)
        fpm = self._dev_fill((N, FPM_N), 0, jnp.int32)
        out = self._flush_jit()(vk, ak, aq, aq2, zk, fpm, jnp.int32(0))
        jax.block_until_ready(out)
        del vk, ak, aq, aq2, zk, fpm, out

    def _compile_store_tier(self):
        """Compile the LCAP-keyed programs (round + append) at the
        current store tier on dummies."""
        N, K = self.N, self.K
        n_inv = len(self.invariant_names)
        bufs = {}
        self._alloc_acc(bufs)
        rows = self._dev_fill((N, self.LCAP * self.W), 0, jnp.uint32)
        zq = self._dev_fill((N,), 0, jnp.int32)
        dead = self._dev_fill((N,), int(BIG), jnp.int32)
        rt = self._dev_fill((N, RT_N), 0, jnp.int32)
        out = self._round_jit()(
            bufs["ak"], bufs["arows"], bufs["apar"], bufs["alane"],
            bufs["aq"], bufs["aq2"], rows, zq, zq, dead, rt,
            jnp.int32(0), jnp.int32(0),
        )
        jax.block_until_ready(out)
        parent = self._dev_fill((N, self.LCAP), 0, jnp.int32)
        lane = self._dev_fill((N, self.LCAP), 0, jnp.int32)
        viol = self._dev_fill((N, n_inv), int(BIG), jnp.int32)
        app = self._append_jit()(
            rows, parent, lane,
            out[1], self._dev_fill((N, self.PACAP), 0, jnp.int32),
            self._dev_fill((N, self.PACAP), 0, jnp.int32),
            zq, zq, viol,
        )
        jax.block_until_ready(app)
        del bufs, rows, parent, lane, viol, out, app

    def warmup(
        self, seed_states: int = 0, tiers: bool = True
    ) -> float:
        """Compile every hot-path program on dummy data, outside any
        timed budget; returns compile wall time, per-stage times in
        ``last_stats``.  ``seed_states`` (the upcoming host seed's
        state count) also precompiles the seed-loader programs at the
        matching shape; ``tiers=True`` (default) additionally walks the
        capacity-growth schedule so no tier crossing pays a mid-window
        lazy compile (VERDICT r5 #8 — see ``_prewarm_tiers``).
        Without this the lazy compiles (~6-8 min at
        bench tiers) eat the run's time budget — the round-4 n=1 bench
        found the capped "warm run" truncating on its own budget before
        the ROUND program ever compiled, leaving a 2-minute compile
        stall inside the measured run."""
        t0 = time.time()
        self.last_stats = {}
        tlast = [t0]

        def mark(stage):
            now = time.time()
            self.last_stats[f"compile_{stage}_s"] = round(
                now - tlast[0], 1
            )
            tlast[0] = now

        drain = jax.block_until_ready

        N, K = self.N, self.K
        n_inv = len(self.invariant_names)
        bufs = {}
        self._alloc_acc(bufs)
        bufs["vk"] = tuple(
            self._dev_fill((N, self._vk_width()), SENTINEL, jnp.uint32)
            for _ in range(K)
        )
        bufs["rows"] = self._dev_fill(
            (N, self.LCAP * self.W), 0, jnp.uint32
        )
        bufs["parent"] = self._dev_fill((N, self.LCAP), 0, jnp.int32)
        bufs["lane"] = self._dev_fill((N, self.LCAP), 0, jnp.int32)
        rt = self._dev_fill((N, RT_N), 0, jnp.int32)
        dead = self._dev_fill((N,), int(BIG), jnp.int32)
        viol = self._dev_fill((N, n_inv), int(BIG), jnp.int32)
        nvis = self._dev_fill((N,), 0, jnp.int32)
        nkeys = self._dev_fill((N,), 0, jnp.int32)
        fpm = self._dev_fill((N, FPM_N), 0, jnp.int32)
        mark("alloc")
        out = self._init_round_jit()(
            bufs["ak"], bufs["arows"], bufs["apar"], bufs["alane"],
            bufs["aq"], bufs["aq2"], rt, jnp.int32(0), jnp.int32(0),
        )
        drain(out)
        bufs["ak"] = tuple(out[0])
        (
            bufs["arows"], bufs["apar"], bufs["alane"], bufs["aq"],
            bufs["aq2"], rt,
        ) = out[1:]
        mark("initround")
        zq = jax.device_put(
            np.zeros((N,), np.int32), self._shard()
        )
        out = self._round_jit()(
            bufs["ak"], bufs["arows"], bufs["apar"], bufs["alane"],
            bufs["aq"], bufs["aq2"], bufs["rows"], zq, zq, dead, rt,
            jnp.int32(0), jnp.int32(0),
        )
        drain(out)
        bufs["ak"] = tuple(out[0])
        (
            bufs["arows"], bufs["apar"], bufs["alane"], bufs["aq"],
            bufs["aq2"], dead, rt,
        ) = out[1:]
        mark("round")
        out = self._flush_jit()(
            bufs["vk"], bufs["ak"], bufs["aq"], bufs["aq2"], nkeys,
            fpm, jnp.int32(0),
        )
        drain(out)
        bufs["vk"] = tuple(out[0])
        mark("flush")
        comp = self._compact_jit()(
            bufs["arows"], bufs["apar"], bufs["alane"], out[3]
        )
        drain(comp)
        crows, cpar, clane = comp
        bufs["arows"], bufs["apar"], bufs["alane"] = crows, cpar, clane
        mark("compact")
        app = self._append_jit()(
            bufs["rows"], bufs["parent"], bufs["lane"],
            crows, cpar, clane, out[2], nvis, viol,
        )
        drain(app)
        mark("append")
        drain(self._stats_jit()(nvis, nkeys, dead, viol, rt, fpm))
        mark("misc")
        if seed_states:
            # precompile the host-seed loader's programs at the shape
            # this seed size will use (the caller knows it — the seed
            # is built before warmup), so run(seed=...) pays no compile
            # inside the timed budget.  The append's outputs are reused
            # as the store dummies: a second LCAP-sized row store here
            # OOMed the 24M-state n=1 bench tier.
            SRC, Mp = self._seed_src(seed_states)
            rows2, par2, lane2 = app[0], app[1], app[2]
            del app
            srows = self._dev_fill((N, Mp * self.W), 0, jnp.uint32)
            spar = self._dev_fill((N, Mp), 0, jnp.int32)
            slane = self._dev_fill((N, Mp), 0, jnp.int32)
            nloc = self._dev_fill((N,), 0, jnp.int32)
            drain(
                self._seed_write_jit()(
                    rows2, par2, lane2, viol, srows, spar, slane,
                    nloc, jnp.int32(0),
                )
            )
            del spar, slane
            out = self._seed_round_jit(SRC)(
                bufs["ak"], bufs["aq"], bufs["aq2"], rt, srows,
                nloc, jnp.int32(0), jnp.int32(0),
            )
            drain(out)
            del out, srows
            mark("seed")
        del bufs
        if tiers:
            self._prewarm_tiers()
            mark("tiers")
        return time.time() - t0

    def run(self, resume: bool = False, seed=None) -> CheckerResult:
        """``seed``: optional host-enumerated BFS prefix
        ``(packed_rows, parent_gids, action_lanes, level_sizes)`` —
        the warm start that removed half the single-chip engine's wall
        clock (VERDICT r4 #4 asked for it on this engine too)."""
        # this run's exclusive host phases, and the compile meter's
        # reading before it (obs/spans.py)
        clock = self._clock = spans.PhaseClock(obs.new_run_id())
        self._jit0 = spans.compile_meter().snapshot()
        with spans.span("run", run_id=clock.run_id):
            return self._run_spanned(resume, seed)

    def _run_spanned(self, resume: bool, seed) -> CheckerResult:
        rid = self._clock.run_id
        self.tel = obs.as_telemetry(self._telemetry_arg, run_id=rid)
        self._run_id = self._clock.run_id = self.tel.run_id or rid
        self._snap = {"distinct_states": 0}
        self._fetch_n = 0
        self._dispatch_n = 0
        self._route_rounds = {}
        self._route_overflows = 0
        self._rehash_keys = self._rehash_lane_rounds = 0
        # per-run recovery/frame state: a fresh run() must not inherit
        # a previous run's degraded capacity or frame counts
        self.rec.reset()
        self._ckpt_frames = 0
        self._ckpt_bytes = 0
        self._ckpt_write_s = 0.0
        self._ckpt_retries = 0
        self._bufs_poisoned = False
        self._flush_seq = 0
        self._fpm_prev = np.zeros((fpset.FPM_LOGICAL_N,), np.int64)
        self._compact_n = 0
        self._compact_prev = 0
        self._resume_meta = {}
        # a crash mid-frame-write can leave a dead multi-GB tmp behind
        ckpt.cleanup_stale_tmp(self.checkpoint_path)
        # crash breadcrumbs: installed FIRST — before the heartbeat or
        # any warmup-adjacent dispatch — so even a level-1/flush-1
        # drill leaves its breadcrumb (the null sink makes this a
        # no-op when telemetry is off)
        faults.set_observer(
            lambda kind, site, count: self.tel.emit(
                "fault", kind=kind, site=site, count=count
            )
        )
        hb = None
        if self.heartbeat_s:
            hb = obs.Heartbeat(
                self.heartbeat_s, self._snap, telemetry=self.tel,
                capacity=self.SCAP,
            )
        # preemption-safe shutdown: SIGTERM/SIGINT request a checkpoint
        # at the next level boundary (armed only with a frame path)
        watcher = ckpt.PreemptionWatcher(
            enabled=bool(self.checkpoint_path), log=self._log
        )
        self._watcher = watcher
        try:
            with watcher:
                if hb is not None:
                    hb.start()
                return self._run(resume, seed)
        except BaseException as e:
            self.tel.emit("error", error=repr(e)[:300])
            raise
        finally:
            if hb is not None:
                hb.stop()
            faults.set_observer(None)
            self._watcher = None
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
            engine="sharded_device",
            device=dev,
            n_devices=self.N,
            n_slices=self.D,
            **obs.IMPL_FIELDS,
            config_sig=self._config_sig(),
            # REQUIRED since schema v8, a constant null
            profile_sig=None,
            hbm_budget=None,
            # v10: tenant identity (None outside the daemon)
            tenant=getattr(self, "tenant", None),
            warm=getattr(self, "warm", None),
            # v15: distributed-trace identity (None outside the daemon)
            trace_id=getattr(self, "trace_id", None),
            # v11: workload class (exhaustive BFS)
            mode="check",
            wall_unix=round(time.time(), 3),
            max_states=self.SCAP,
            sub_batch=self.G,
            flush_factor=self.FLUSH,
            key_cols=self.K,
            key_exact=bool(self.keys.exact),
            invariants=list(self.invariant_names),
            resume=resume,
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

    def _run(self, resume: bool, seed) -> CheckerResult:
        with self._clock.phase("init"):
            frame = self._start(resume, seed)
        # everything of the level loop that is no phase of its own
        # (telemetry emits, the log line, fault polls, the host's
        # bounds arithmetic) is ``account``
        with self._clock.phase("account"):
            return self._run_levels(*frame)

    def _start(self, resume: bool, seed):
        """Fresh, seeded or restored device state up to the first level
        boundary: the arguments of :meth:`_run_levels`."""
        t0 = time.time()
        # the time budget always gets a fresh clock on resume (t0 is
        # rewound below so wall_s stays cumulative; without a separate
        # budget clock a resumed run would be instantly over budget)
        self._budget_t0 = t0
        m = self.model
        N, K, n_inv = self.N, self.K, len(self.invariant_names)
        if resume:
            if not self.checkpoint_path:
                raise ValueError("resume requires checkpoint_path")
            d = self.load_checkpoint()
            self._resume_meta = ckpt.frame_meta(d)
            (
                bufs, st, level_sizes, lb, nf, saved_wall,
            ) = self._restore(d)
            t0 = time.time() - saved_wall
            self.rec.arm()  # the on-disk frame is valid
            self._emit_header(resume=True)
            return t0, bufs, st, level_sizes, lb, nf
        bufs = {
            "vk": tuple(
                self._dev_fill(
                    (N, self._vk_width()), SENTINEL, jnp.uint32
                )
                for _ in range(K)
            ),
            "rows": self._dev_fill(
                (N, self.LCAP * self.W), 0, jnp.uint32
            ),
            "parent": self._dev_fill((N, self.LCAP), 0, jnp.int32),
            "lane": self._dev_fill((N, self.LCAP), 0, jnp.int32),
        }
        self._alloc_acc(bufs)
        st = {
            "n_visited": self._dev_fill((N,), 0, jnp.int32),
            "n_keys": self._dev_fill((N,), 0, jnp.int32),
            "dead": self._dev_fill((N,), int(BIG), jnp.int32),
            "viol": self._dev_fill((N, n_inv), int(BIG), jnp.int32),
            "rt": self._dev_fill((N, RT_N), 0, jnp.int32),
            "fpm": self._dev_fill((N, FPM_N), 0, jnp.int32),
        }
        self._emit_header(resume=False)

        if seed is not None:
            level_sizes, lb, nf = self._load_seed(bufs, st, seed)
            stats = self._fetch(st)
            fv = self._first_viol(stats)
            if fv is not None:
                # violation inside the seeded prefix: diameter = the
                # violating state's level (gid -> BFS index -> level)
                gid = fv[1]
                i = (
                    (gid & ((1 << self.SB) - 1)) * self.N
                    + (gid >> self.SB)
                )
                cum = 0
                for li, cnt in enumerate(level_sizes):
                    cum += cnt
                    if i < cum:
                        level_sizes = level_sizes[: li + 1]
                        break
            return t0, bufs, st, level_sizes, lb, nf, stats

        # ---- level 1: initial states (keys to owners, rows local) ----
        # level-1 fault site: the level loop's poll counts start at 2,
        # so without this a kill@level:1 drill would never fire (the
        # breadcrumb observer is already installed above)
        kinds = faults.poll("level", 1)
        if "oom" in kinds:
            raise faults.oom_error("level", 1)
        n_init = m.n_initial
        if n_init > self.SCAP:
            raise ValueError("initial-state set exceeds max_states")
        while True:
            try:
                per_round = N * self.NCs
                w = 0
                for base in range(0, n_init, per_round):
                    with self._clock.phase("dispatch", level=1):
                        with self._clock.upload("ptt_shard_init", 2):
                            base_d, w_d = jnp.int32(base), jnp.int32(w)
                        with self._clock.call("ptt_shard_init"):
                            out = self._init_round_jit()(
                                bufs["ak"], bufs["arows"], bufs["apar"],
                                bufs["alane"], bufs["aq"], bufs["aq2"],
                                st["rt"], base_d, w_d,
                            )
                    bufs["ak"] = tuple(out[0])
                    (
                        bufs["arows"], bufs["apar"], bufs["alane"],
                        bufs["aq"], bufs["aq2"], st["rt"],
                    ) = out[1:]
                    w += 1
                    if w == self.FLUSH or base + per_round >= n_init:
                        # capacity for the worst case of this flush:
                        # visited keys grow with the OWNER count, the
                        # local store with the PRODUCER count
                        self._grow_visited(
                            bufs,
                            int(np.asarray(st["n_keys"]).max())
                            + self.ACAP,
                        )
                        self._grow_store(
                            bufs,
                            int(np.asarray(st["n_visited"]).max())
                            + self.APAD,
                        )
                        self._flush(bufs, st, w * self.RCV)
                        w = 0
                stats = self._fetch(st)
                break
            except _RouteOverflow:
                # re-route the whole init set at doubled capacity —
                # states already inserted dedup to no-ops, so the retry
                # is exact (ADVICE/VERDICT r3 #8)
                self._grow_route(bufs, st)
        nv = stats[:, 0].copy()
        level_sizes = [int(nv.sum())]
        lb = np.zeros((N,), np.int64)
        nf = nv.copy()
        # per-shard level-1 counts: LivenessChecker's dense gid remap
        # needs to place exactly the initial states first
        self.last_level1_counts = nv.copy()
        return t0, bufs, st, level_sizes, lb, nf, stats

    def _fetch(self, st):
        """Stats matrix columns: 0 = per-shard producer-local state
        count, 1 = per-shard owned-key count, 2 = deadlock gid, 3.. =
        per-invariant violation gids, then the route state (the
        routing-overflow flag, the lanes sent as LO and HI words) and
        the per-shard fpset metrics [flushes, probe rounds, failures,
        valid lanes, max probe rounds]."""
        with self._clock.phase("fetch"):
            with self._clock.call("ptt_shard_stats"):
                dev = self._stats_jit()(
                    st["n_visited"], st["n_keys"], st["dead"],
                    st["viol"], st["rt"], st["fpm"],
                )
            out = np.asarray(dev)
        self._fetch_n += 1
        n_inv = len(self.invariant_names)
        nv = int(out[:, 0].sum())
        self._snap["distinct_states"] = nv
        if out[:, 3 + n_inv].any():
            raise _RouteOverflow
        f0 = 3 + n_inv + RT_N
        self._last_fpm = out[:, f0: f0 + FPM_N]
        self._snap["occupancy"] = float(out[:, 1].max()) / max(
            self.TCAP, 1
        )
        if self._last_fpm.shape[1] >= 4:
            # TLC's "states generated": routed lanes examined
            # (per-shard 64-bit reassembly before the mesh sum)
            self._snap["generated"] = int(
                sum(
                    fpset.fpm_logical(row)[3]
                    for row in self._last_fpm
                )
            )
        self._emit_flush_event(nv, out)
        self._emit_compact_event()
        if self._last_fpm[:, 2].any():
            # probe overflow: some owner table dropped routed keys in a
            # flush that already appended — counts can no longer be
            # trusted, so abort hard (never a silent drop)
            raise RuntimeError(
                "fpset probe overflow on "
                f"{int((self._last_fpm[:, 2] > 0).sum())} shard(s) — "
                + fpset.OVERFLOW_HINT
            )
        return out

    def _emit_flush_event(self, nv: int, stats):
        """One telemetry record per stats fetch, covering the flushes
        since the last one (mesh-summed deltas of the per-shard
        device counters; max_probe_rounds is a mesh MAX, not a sum) —
        per-flush visibility, zero extra syncs."""
        if not self.tel.enabled or self._last_fpm is None:
            return
        # per-shard 64-bit reassembly FIRST (hi/lo valid-lane words,
        # r12), THEN the mesh sum — summing lo words across shards
        # would drop every shard-local carry
        per = np.stack(
            [fpset.fpm_logical(row) for row in self._last_fpm]
        )
        cur = np.concatenate(
            [
                per[:, :4].sum(axis=0),
                [per[:, 4].max(), per[:, 5].sum()],
            ]
        )
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
            occupancy=round(
                float(stats[:, 1].max()) / max(self.TCAP, 1), 4
            ),
            distinct_states=nv,
        )

    def _emit_compact_event(self):
        """One ``compact`` record per stats fetch covering the compact
        dispatches since the previous fetch — free host counters, zero
        extra device syncs (mirrors the single-chip engine's event)."""
        if not self.tel.enabled:
            return
        d = self._compact_n - self._compact_prev
        if d <= 0:
            return
        self._compact_prev = self._compact_n
        self.tel.emit(
            "compact", dispatches=d,
            impl=obs.IMPL_FIELDS["compact_impl"],
        )

    @spans.in_phase("dispatch")
    def _flush(self, bufs, st, n_acc: int):
        # deterministic fault site (utils/faults.py): oom@flush:N hits
        # the sharded fpset flush — raised BEFORE the dispatch mutates
        # any device buffer, so a recovery retry of the level is exact;
        # fpset_fail@flush:N accounts one synthetic dropped lane in the
        # device metrics and the next stats fetch fail-stops exactly
        # like a real probe overflow would
        self._flush_seq += 1
        kinds = faults.poll("flush", self._flush_seq)
        if "oom" in kinds:
            raise faults.oom_error("flush", self._flush_seq)
        if "fpset_fail" in kinds:
            # one synthetic dropped lane on ONE shard (shard 0) — a
            # full-mesh broadcast would misstate the drill's blast
            # radius in the failure telemetry and the abort message
            bump = np.zeros((self.N, FPM_N), np.int32)
            bump[0, 2] = 1
            st["fpm"] = st["fpm"] + jnp.asarray(bump)
        clock = self._clock
        with clock.upload("ptt_shard_flush", 1):
            n_acc_d = jnp.int32(n_acc)
        with clock.call("ptt_shard_flush"):
            out = self._flush_jit()(
                bufs["vk"], bufs["ak"], bufs["aq"], bufs["aq2"],
                st["n_keys"], st["fpm"], n_acc_d,
            )
        bufs["vk"] = tuple(out[0])
        st["n_keys"], n_new, flag_local = out[1], out[2], out[3]
        st["fpm"] = out[4]
        # compact in its own dispatch (round 10): the donated producer
        # accumulator comes back compacted and is recycled as the next
        # fill's buffers (stale content is overwritten by the next
        # round's DUS windows and masked by n_acc at the next flush)
        with clock.call("ptt_shard_compact"):
            crows, cpar, clane = self._compact_jit()(
                bufs["arows"], bufs["apar"], bufs["alane"], flag_local
            )
        bufs["arows"], bufs["apar"], bufs["alane"] = crows, cpar, clane
        self._compact_n += 1
        self.last_stats["stage_compact_n"] = self._compact_n
        with clock.call("ptt_shard_append"):
            (
                bufs["rows"], bufs["parent"], bufs["lane"],
                st["n_visited"], st["viol"],
            ) = self._append_jit()(
                bufs["rows"], bufs["parent"], bufs["lane"],
                crows, cpar, clane,
                n_new, st["n_visited"], st["viol"],
            )

    @spans.in_phase("grow")
    def _grow_route(self, bufs, st):
        """Auto-recover from a routing overflow (VERDICT r3 #8): double
        ``route_slack``, re-derive every route-capacity-dependent size,
        drop the jit cache (CAPO/ACAP are baked into the compiled
        programs), reallocate the accumulator, and clear the sticky
        flag.  The caller then simply retries the current level — every
        state appended by the partial attempt deduplicates to a no-op,
        so counts stay exact (the overflow itself only ever DROPPED
        candidates, never corrupted the visited set)."""
        self._route_overflows += 1
        self.route_slack *= 2.0
        self._calc_route()
        if self.ACAP * self.W >= 1 << 31:
            raise RuntimeError(
                "routing overflow recovery exceeded int32 flat "
                "addressing; reduce sub_batch"
            )
        self._jits.clear()
        self._alloc_acc(bufs)
        st["rt"] = st["rt"].at[:, 0].set(0)
        self._log(
            f"routing overflow: retrying with route_slack="
            f"{self.route_slack} (ACAP={self.ACAP})"
        )

    def _run_levels(self, t0, bufs, st, level_sizes, lb, nf, stats=None):
        """The BFS level loop under the mesh-wide HBM-exhaustion
        recovery contract (r9): a ``RESOURCE_EXHAUSTED`` anywhere in a
        level — dispatch, fetch, or the injected ``oom@level/flush``
        drills — with a valid checkpoint frame on disk frees every
        per-shard buffer, rebuilds the sharded FPSet + frontier from
        the frame at degraded capacity (halved group-ahead, frozen
        growth headroom, reduced per-shard row budget — see
        ``_grow_store``), and resumes the level.  Every state the
        partial attempt appended dedups to a no-op, so counts and gids
        stay exact — the same contract as the single-chip engine, on
        the mesh as the unit of failure.  Only when recovery itself
        exhausts memory (or no fresh frame was written since the last
        recovery) does the run truncate with ``stop_reason="hbm"``."""
        while True:
            try:
                return self._level_loop(
                    t0, bufs, st, level_sizes, lb, nf, stats
                )
            except recovery.HbmExhausted as hx:
                last = (hx.nv, hx.level_sizes, hx.msg)
                # the rebuild happens OUTSIDE this except block: the
                # traceback pins _level_loop's frame locals (per-shard
                # accumulators) plus the chained XLA error — restoring
                # under it would re-OOM exactly when memory is tightest
            self.rec.degrade()
            self.tel.emit(
                "hbm_recovery",
                recovery_n=self._hbm_recovered,
                group=self.group,
                distinct_states=last[0],
                error=last[2][:200],
            )
            self._log(
                "HBM exhausted on the mesh: recovering from the last "
                f"checkpoint frame (recovery #{self._hbm_recovered}"
                f", group={self.group}) — {last[2][:120]}"
            )
            # drop every per-shard buffer reference BEFORE the restore
            # allocates: the poisoned/donated storage must be freed
            # first or the rebuild would OOM on top of it
            bufs.clear()
            st.clear()
            try:
                d = self.load_checkpoint()
                nbufs, nst, level_sizes, lb, nf, _w = self._restore(d)
                bufs.update(nbufs)
                st.update(nst)
                # the post-rebuild fetch happens HERE, inside the
                # recovery handler: it is the first dispatch after the
                # rebuild and the likeliest to re-OOM — it must take
                # the honest-truncate path, not crash the run
                stats = self._fetch(st)
            except Exception as e:  # noqa: BLE001
                if not recovery.is_resource_exhausted(e):
                    raise
                # recovery itself exhausted memory: report what the
                # interrupted run had verified, honestly
                self._bufs_poisoned = True
                return self._hbm_result(t0, last[0], last[1])

    def _hbm_result(self, t0, nv: int, level_sizes) -> CheckerResult:
        """Truncated stop_reason="hbm" result from the last known
        totals — the per-shard stats matrix is gone (poisoned or never
        fetched), so a minimal one carries the mesh total."""
        n_inv = len(self.invariant_names)
        stats = np.zeros((self.N, 3 + n_inv + RT_N + FPM_N), np.int64)
        stats[:, 2] = int(BIG)
        stats[:, 3: 3 + n_inv] = int(BIG)
        stats[0, 0] = nv
        return self._result(
            t0, stats, level_sizes, {}, truncated=True,
            stop_reason="hbm",
        )

    def _level_loop(self, t0, bufs, st, level_sizes, lb, nf, stats=None):
        """One pass of BFS levels over a restored-or-fresh level frame
        (re-entered by ``_run_levels`` after an HBM recovery)."""
        if stats is None:
            # resume entry: the first fetch after a restore gets the
            # same recovery contract as any in-level exhaustion (the
            # frame on disk is armed, so a rebuild retry is legal; the
            # pre-fetch state count is unknown — report level_sizes)
            try:
                stats = self._fetch(st)
            except Exception as e:  # noqa: BLE001
                if not recovery.is_resource_exhausted(e):
                    raise
                if self.rec.can_recover():
                    raise recovery.HbmExhausted(
                        0, list(level_sizes), repr(e)
                    )
                self._bufs_poisoned = True
                return self._hbm_result(t0, 0, list(level_sizes))
        nv = stats[:, 0].copy()
        while True:
            reason = self._stop_reason(stats, t0)
            if reason is not None and not (
                reason.get("truncated") and nf.sum() == 0
            ):
                if reason.get("truncated") and self.checkpoint_path:
                    self._save_checkpoint(
                        bufs, st, level_sizes, lb, nf, t0
                    )
                return self._result(t0, stats, level_sizes, bufs, **reason)
            if nf.sum() == 0:
                return self._result(t0, stats, level_sizes, bufs)
            if self._watcher is not None and self._watcher.requested:
                # preemption-safe shutdown: write a resumable frame at
                # this level boundary and exit truncated
                if self.checkpoint_path:
                    self._save_checkpoint(
                        bufs, st, level_sizes, lb, nf, t0
                    )
                return self._result(
                    t0, stats, level_sizes, bufs, truncated=True,
                    stop_reason="preempted",
                )
            try:
                # deterministic fault sites (utils/faults.py): kill/
                # sigterm fire inside poll; an injected oom raises the
                # same RESOURCE_EXHAUSTED path a real allocator
                # failure takes — recovered mesh-wide below (r9)
                kinds = faults.poll("level", len(level_sizes) + 1)
                if "oom" in kinds:
                    raise faults.oom_error(
                        "level", len(level_sizes) + 1
                    )
                stats, nv2, stop = self._run_one_level(
                    t0, bufs, st, stats, nv, lb, nf,
                    len(level_sizes) + 1,
                )
            except _RouteOverflow:
                self._grow_route(bufs, st)
                stats = self._fetch(st)
                nv = stats[:, 0].copy()
                continue  # retry the same level at doubled capacity
            except Exception as e:  # noqa: BLE001
                if not recovery.is_resource_exhausted(e):
                    raise
                if self.rec.can_recover():
                    raise recovery.HbmExhausted(
                        int(nv.sum()), list(level_sizes), repr(e)
                    )
                # HBM exhausted with no frame to rebuild from: report
                # what was checked so far (truncated).  The per-shard
                # buffers may hold donated/poisoned storage — only
                # host-side totals are reported from here on.
                self._log(
                    f"HBM exhausted mid-level: truncating ({e!r:.120})"
                )
                self._bufs_poisoned = True
                return self._hbm_result(
                    t0, int(nv.sum()), list(level_sizes)
                )
            level_count = (nv2 - (lb + nf)).sum()
            if level_count or stop:
                level_sizes.append(int(max(level_count, 0)))
                wall = time.time() - t0
                total = int(nv2.sum())
                self._emit_metrics(t0, len(level_sizes), level_count,
                                   total, frontier=int(nf.sum()))
                self._log(
                    f"level {len(level_sizes)}: +{level_count} "
                    f"(total {total}, {total/max(wall,1e-9):.0f} st/s)"
                )
                self._clock.level_boundary(len(level_sizes))
            if stop:
                reason = self._stop_reason(stats, t0) or {
                    "truncated": True
                }
                if reason.get("truncated") and self.checkpoint_path:
                    # a mid-level stop: the just-appended entry is
                    # partial, so the snapshot rewinds to the level
                    # boundary (the retried level dedups exactly)
                    self._save_checkpoint(
                        bufs, st, level_sizes[:-1], lb, nf, t0
                    )
                return self._result(
                    t0, stats, level_sizes, bufs, **reason
                )
            lb = lb + nf
            nf = nv2 - lb
            nv = nv2
            if nf.sum() == 0 and level_count == 0:
                return self._result(t0, stats, level_sizes, bufs)
            if self.checkpoint_path and (
                len(level_sizes) % self.checkpoint_every == 0
            ):
                self._save_checkpoint(bufs, st, level_sizes, lb, nf, t0)

    def _run_one_level(self, t0, bufs, st, stats, nv, lb, nf, level):
        """Expand level ``level``; returns (stats, nv2, stop)."""
        self._grow_store(bufs, int((lb + nf).max()) + self.G)
        # a level's two bounds, made once for all its rounds
        with self._clock.upload("ptt_shard_round", 2):
            lb_dev = jax.device_put(
                np.asarray(lb, np.int32), self._shard()
            )
            nf_dev = jax.device_put(
                np.asarray(nf, np.int32), self._shard()
            )
        rounds = int(-(-nf.max() // self.G))
        stop = False
        pending = 0
        w = 0
        # worst-case per-shard bounds under in-flight flushes: the
        # local store grows by <= PACAP states per flush (producer
        # side), the visited keys by <= ACAP (owner side)
        nv_bound = nv.max()
        nk_bound = stats[:, 1].max()
        for r in range(rounds):
            last = r + 1 >= rounds
            with self._clock.phase("dispatch", level=level):
                with self._clock.upload("ptt_shard_round", 2):
                    r_d, w_d = jnp.int32(r), jnp.int32(w)
                with self._clock.call("ptt_shard_round"):
                    out = self._round_jit()(
                        bufs["ak"], bufs["arows"], bufs["apar"],
                        bufs["alane"], bufs["aq"], bufs["aq2"],
                        bufs["rows"], lb_dev, nf_dev, st["dead"],
                        st["rt"], r_d, w_d,
                    )
            bufs["ak"] = tuple(out[0])
            (
                bufs["arows"], bufs["apar"], bufs["alane"],
                bufs["aq"], bufs["aq2"], st["dead"], st["rt"],
            ) = out[1:]
            w += 1
            if w < self.FLUSH and not last:
                continue
            nv_bound = nv_bound + self.PACAP
            nk_bound = nk_bound + self.ACAP
            need_sync = (
                nk_bound + self.ACAP > self.VCAP
                or nv_bound + self.APAD > self.LCAP
                # near the state cap, sync on the OPTIMISTIC bound: at
                # bench shapes one flush can append a PACAP (~27M) of
                # states, so letting group flushes fly past SCAP forced
                # multi-GB row-store growth for states the run would
                # discard (OOMed the 24M n=1 tier)
                or nv_bound * self.N >= self.SCAP
                or pending >= self.group
            )
            if need_sync:
                stats = self._fetch(st)
                nv = stats[:, 0].copy()
                nv_bound = nv.max()
                nk_bound = stats[:, 1].max()
                pending = 0
                if self._stop_reason(stats, t0) is not None:
                    stop = True
                    break
                # growth headroom for a full group of in-flight
                # flushes — except after an HBM recovery, where it is
                # FROZEN at one accumulator (degraded capacity so the
                # retry fits where the full-headroom run did not)
                head_k = (
                    self.ACAP
                    if self.rec.headroom_frozen
                    else (self.group + 1) * self.ACAP
                )
                head_p = (
                    self.PACAP
                    if self.rec.headroom_frozen
                    else (self.group + 1) * self.PACAP
                )
                if nk_bound + head_k > self.VCAP:
                    self._grow_visited(bufs, int(nk_bound) + head_k)
                if nv_bound + head_p + self.APAD > self.LCAP:
                    # headroom for a full group of in-flight flushes,
                    # but never beyond what the state cap (plus one
                    # overshooting flush) can actually use.  The cap is
                    # the GLOBAL SCAP, not SCAP/N: producer-local
                    # placement can be skewed (a small Init set lands
                    # on few shards), and an under-grown store means a
                    # clamped blind DUS — silent row corruption, not an
                    # error (bitten in round 5's resume testing).
                    self._grow_store(
                        bufs,
                        min(
                            int(nv_bound) + head_p,
                            self.SCAP + self.PACAP,
                        )
                        + self.APAD,
                    )
            self._flush(bufs, st, w * self.RCV)
            pending += 1
            w = 0
        stats = self._fetch(st)
        return stats, stats[:, 0].copy(), stop

    # ----------------------------------------------------------- control

    def _over_time(self, t0) -> bool:
        # the budget runs on its own clock: ``t0`` is rewound on resume
        # so wall_s stays cumulative, but a resumed run always gets
        # ``time_budget_s`` of fresh runway
        return (
            self.time_budget_s is not None
            and time.time() - getattr(self, "_budget_t0", t0)
            > self.time_budget_s
        )

    def _stop_reason(self, stats, t0) -> Optional[dict]:
        fv = self._first_viol(stats)
        if fv is not None:
            return {"viol": fv}
        dead = stats[:, 2]
        if (dead < int(BIG)).any():
            return {"dead_gid": int(dead.min())}
        if stats[:, 0].sum() >= self.SCAP:
            return {"truncated": True, "stop_reason": "max_states"}
        if self._over_time(t0):
            return {"truncated": True, "stop_reason": "time_budget"}
        return None

    def _first_viol(self, stats) -> Optional[Tuple[str, int]]:
        """Lowest-global-gid violation across shards.  Global gids are
        ``shard << SB | local``, so among violations discovered in the
        same level the minimum is biased toward low shard indices rather
        than strict discovery order — the reported counterexample can be
        a *different* (equally minimal-depth, equally valid) trace than
        the single-chip engine picks for the same spec (ADVICE r3)."""
        best = None
        for i, name in enumerate(self.invariant_names):
            g = int(stats[:, 3 + i].min())
            if g < int(BIG) and (best is None or g < best[1]):
                best = (name, g)
        return best

    def _emit_metrics(self, t0, level, level_count, total, frontier=None):
        wall = time.time() - t0
        self._snap.update(level=level, distinct_states=int(total))
        if frontier is not None:
            self._snap["frontier"] = int(frontier)
        self.tel.emit(
            "level",
            level=level,
            new_states=int(level_count),
            distinct_states=int(total),
            frontier=int(frontier) if frontier is not None else 0,
            wall_s=round(wall, 3),
            states_per_sec=round(total / max(wall, 1e-9), 1),
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
                        "new_states": int(level_count),
                        "distinct_states": total,
                        "wall_s": round(wall, 3),
                        "host_wait_s": round(self._host_wait_s, 3),
                        "states_per_sec": round(
                            total / max(wall, 1e-9), 1
                        ),
                        "n_shards": self.N,
                    }
                )
                + "\n"
            )

    # ------------------------------------------------------------- trace

    @spans.in_phase("trace_walk")
    def _trace(self, bufs, gid: int, max_depth: int):
        """Walk the cross-shard parent chain on the host (per-hop fetch
        of two scalars; traces are rare and shallow), then replay lanes
        through the model."""
        par_log = bufs["parent"]
        lane_log = bufs["lane"]
        chain = []
        g = gid
        for _ in range(max_depth):
            if g < 0:
                break
            s, idx = g >> self.SB, g & ((1 << self.SB) - 1)
            lane = int(np.asarray(lane_log[s, idx]))
            chain.append((g, lane))
            g = int(np.asarray(par_log[s, idx]))
        if g >= 0:
            # a corrupted chain must never fall through to a nonsense
            # init_idx replay (and asserts vanish under python -O)
            raise RuntimeError(
                "parent chain did not terminate at an initial state "
                f"(depth {max_depth}, last gid {g}) — trace log corrupt"
            )
        init_idx = -1 - g
        chain.reverse()
        return self.model.replay_trace(
            init_idx, [lane for _gid, lane in chain[1:]]
        )

    # ------------------------------------------------------------ result

    @spans.in_phase("result")
    def _result(
        self, t0, stats, level_sizes, bufs,
        viol: Optional[Tuple[str, int]] = None,
        dead_gid: Optional[int] = None,
        truncated: bool = False,
        stop_reason: Optional[str] = None,
    ) -> CheckerResult:
        self.last_bufs = bufs
        self.last_stats_matrix = stats
        wall = time.time() - t0
        nv = int(stats[:, 0].sum())
        if self._last_fpm is not None:
            fl = int(self._last_fpm[:, 0].sum())
            rd = int(self._last_fpm[:, 1].sum())
            self.last_stats.update(
                fpset_flushes=fl,
                fpset_probe_rounds=rd,
                fpset_avg_probe_rounds=round(rd / max(fl, 1), 2),
                fpset_failures=int(self._last_fpm[:, 2].sum()),
                fpset_table_cap=self.TCAP,
                fpset_max_occupancy=round(
                    float(stats[:, 1].max()) / max(self.TCAP, 1), 4
                ),
            )
            if self._last_fpm.shape[1] >= 5:
                # zero-sync device counters (r9, = device_bfs): routed
                # lanes after validity masking (duplicate-rate
                # denominator; per-shard hi/lo reassembly since r12)
                # and the worst single flush's probe depth anywhere on
                # the mesh; lanes presented to the tables over all
                # probe rounds (PR 28), against the valid ones
                per = np.stack(
                    [fpset.fpm_logical(row) for row in self._last_fpm]
                )
                vl, lr = int(per[:, 3].sum()), int(per[:, 5].sum())
                self.last_stats.update(
                    fpset_valid_lanes=vl,
                    fpset_max_probe_rounds=int(
                        self._last_fpm[:, 4].max()
                    ),
                    fpset_duplicate_ratio=round(
                        max(1.0 - nv / vl, 0.0), 4
                    ) if vl else None,
                    fpset_lane_rounds=lr,
                    fpset_lanes_presented_per_valid=round(
                        lr / vl, 4
                    ) if vl else None,
                )
        # what exists only across shards: the key exchange and the
        # owner map's skew.  Lanes are counted on the device and ride
        # the stats matrix; rounds, capacity and retries are the
        # host's own (it dispatches every exchange)
        r0 = 3 + len(self.invariant_names) + 1  # past the overflow flag
        sent = stats[:, r0: r0 + 2].astype(np.int64)

        def imbalance(col):
            a = stats[:, col].astype(np.float64)
            return round(
                100.0 * (a.max() / a.mean() - 1.0), 4
            ) if a.sum() else 0.0

        self.last_stats.update(
            route_lanes=int(sum(fpset.u64(lo, hi) for lo, hi in sent)),
            route_rounds=sum(self._route_rounds.values()),
            route_capacity_lanes=self.route_cap,
            route_rounds_by_capacity={
                str(cap): n for cap, n in self._route_rounds.items()
            },
            route_overflows=self._route_overflows,
            # keys owned (the owner map) and states stored, which are
            # the states a shard expands: discovery stays on the
            # producing shard, so one initial state leaves one producer
            shard_imbalance_pct=imbalance(1),
            producer_imbalance_pct=imbalance(0),
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
            if self._bufs_poisoned:
                # after an unrecovered RESOURCE_EXHAUSTED the per-shard
                # trace logs may hold donated/poisoned storage —
                # walking them could crash or fabricate a trace; report
                # the verdict without one
                res.trace = None
                res.trace_actions = None
                res.truncated = True
            else:
                res.trace, res.trace_actions = self._trace(
                    bufs, gid, len(level_sizes) + 2
                )
        # host phases and the compile meter (obs/spans.py), taken at the
        # emit, the last thing a run does: host_<phase>_s sum with
        # host_unaccounted_s to the wall of run(); host_wait_s keeps its
        # key and is the fetch phase; jit_* is an orthogonal cut
        phases = self._clock.stats()
        self.last_stats.update(
            phases,
            **spans.compile_meter().since(self._jit0),
            **obs.IMPL_FIELDS,
            hbm_recovered=self._hbm_recovered,
            ckpt_frames=self._ckpt_frames,
            ckpt_bytes=self._ckpt_bytes,
            ckpt_write_s=round(self._ckpt_write_s, 3),
            ckpt_retries=self._ckpt_retries,
            host_wait_s=phases["host_fetch_s"],
            stats_fetches=self._fetch_n,
            dispatches_per_level=round(
                self._dispatch_n / max(len(level_sizes), 1), 2
            ),
            grow_rehash_keys=self._rehash_keys,
            grow_rehash_lane_rounds=self._rehash_lane_rounds,
            **obs.model_stats(self.model, self.keys),
        )
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
