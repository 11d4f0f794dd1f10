"""Dense-tile kernel layer (round 23 tentpole): blocked / Pallas
formulations of the three hottest device kernels, selected per shape by
the autotuner — never hardcoded.

The r14 work counters say probe lanes and expand rows dominate cost at
every calibrated shape, and the r13 megakernel fused the *dispatches*
without touching the *kernel shapes*: the fpset probe is a per-round
triangular-probe gather chain, the expand sweep a `lax.scan` of chunked
vmaps, the sieve's extract an order-preserving compaction feeding a
sort.  BLEST (arXiv:2512.21967) and Graph Traversal on Tensor Cores
(arXiv:2606.05081) recast exactly these shapes as dense tile ops picked
by a cost model; this module is that layer for our three kernels.  Each
kernel ships two variants behind one constructor knob:

- ``tile`` — a pure-XLA blocked formulation (reshaped ``(TILE_R,
  TILE_L)`` planes that vectorize on the CPU mesh and lower to
  MXU/VPU tiles on TPU);
- ``pallas`` — the same blocking as an explicit
  ``jax.experimental.pallas`` kernel, for the expand key plane and the
  sieve (``interpret=True`` on the CPU backend, native lowering on the
  chip — ``chip_smoke.py``'s ``kernels`` phase compiles both there and
  compares them bit for bit).  The probe has none: its random gather
  from the table does not lower through Mosaic.

**(1) Tiled probe** (``probe_impl``).  The legacy flush interleaves
membership resolution and insertion: every dense round gathers K slot
columns, scatter-min-bids for empty slots, scatter-writes winners, and
re-gathers — O(nq + cap) scatter traffic per round whether a lane is a
duplicate or not.  The tile probe splits the two concerns:

- a **blocked membership prefilter**: ``TILE_R`` probe rounds of the
  triangular sequence evaluated at once as a ``(TILE_R, TILE_L)`` key
  plane x slot tile comparison — gather-only, no claims buffer, no
  scatter.  Membership is EXACT for every resolved lane: a key present
  in a triangular-probed table is always found before the first empty
  slot of its probe sequence (inserts claim the then-first empty slot
  and the flush path never holes the table mid-run), so "saw my key
  before an empty slot" = member, "saw an empty slot first" =
  definitely new.
- a **width-proportional insert tail**: the surviving lanes (new keys
  + the rare unresolved tail) compact order-preservingly — original
  lane ids ride along — into ``ceil(npend / CW)`` chunks of width
  ``CW = max(nq/4, MIN_STAGE)`` that run the UNCHANGED legacy
  ``probe_insert`` loop sequentially.  Chunk order is lane order and
  the bidding uses original lane ids, so equal-key resolution is
  min-lane-wins exactly as the legacy flush: a later chunk's equal key
  finds the earlier chunk's insert as a member.  ``is_new`` is
  therefore bit-identical to the legacy path — discovery order is a
  function of (pre-flush table membership, batch keys, min-lane-wins),
  never of slot placement or probe scheduling.

The dynamic chunk count makes the insert cost proportional to the
actual new-key count (duplicate-heavy steady-state flushes run ONE
narrow chunk) while an all-new ramp flush degrades gracefully to
legacy-equivalent width.  Probe-round metrics (``fpm``) count the
prefilter block plus the chunk rounds — the schedule differs from the
legacy path by design and is NOT part of the pinned parity surface
(the work counters are: lanes presented per flush are identical).

**(2) Tiled expand** (``expand_impl``).  The engine's legacy expand is
a ``lax.scan`` over ``G/Fi`` chunks of vmapped successor evaluation.
The tile variant evaluates the whole ``(G, A)`` successor matrix as
one batched tile op and forms the key plane on the full ``(G*A, W)``
matrix in one shot (:func:`key_plane`) — per-lane math is identical
elementwise, so gids, rows, and logs are bit-identical; what changes
is the compiled structure (no scan carry, one fused key-plane
materialization).  The ``pallas`` variant moves the key-plane kernel
(fmix/murmur mixing + validity masking) into an explicit Pallas tile
kernel; the successor functions themselves are arbitrary traced JAX
from the model and stay in XLA — that boundary is the honest one, and
it is the key plane the r14 counters bill per lane anyway.  (The
successor-sweep blocking itself lives in
``engine/device_bfs._expand_body`` where the model closure is; this
module owns the engine-independent tile kernels.)

**(3) Tiled sieve** (``sieve_impl``).  The legacy
``store/sieve.extract_cold`` compacts the cold keys densely, masks the
tail, and sorts.  The tile variant observes the compaction is
redundant work before a sort: masking non-cold lanes to SENTINEL *in
place* (one elementwise tile pass over the table planes) feeds the
same ``lax.sort`` the identical multiset — cold table keys are
distinct and SENTINEL padding sorts last, so the sorted output is
ARRAY-identical while the gather-heavy compact disappears.  The
``pallas`` variant runs the masking plane (cold select, table holing,
generation clear) as one elementwise Pallas kernel over slot tiles.

Every impl preserves discovery order state-for-state (pinned by
``tests/test_tiles.py``: randomized-shape parity properties, the
producer_on rows/parent/lane differentials, and both published bug
oracles under every ``*_impl``).  The winner per shape is arbitrated
by ``cli.py tune`` — the knobs register in ``tune/space.py`` and are
priced by ``tune/predict.py`` at calibrated per-impl lane costs.
Measured CPU-mesh verdicts per kernel: BASELINE.md Round 23.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pulsar_tlaplus_tpu.ops import compact as compact_ops
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL

# SENTINEL as a numpy scalar for use INSIDE Pallas kernel bodies —
# the jnp scalar would be captured by the kernel trace (which
# pallas_call rejects) and a bare Python int overflows the weak-int32
# promotion; a numpy scalar embeds as a plain jaxpr literal
_SENT = np.uint32(0xFFFFFFFF)

# probe rounds resolved per blocked membership pass (the prefilter's
# key-plane height; >= the default dense schedule so steady-state
# flushes resolve in one block)
TILE_R = 8
# lane-tile width for the blocked membership pass — bounds the
# (TILE_R, TILE_L) intermediate planes so a bench-width accumulator
# never materializes an (R, 26M) gather (the r5 relayout lesson)
TILE_L = 1 << 16
# lane-tile width of the sieve's Pallas kernel (one grid program per
# 1-D tile of every table plane)
PALLAS_TILE = 4096
# block of the key-plane Pallas kernel: the lane axis is viewed as
# ``(rows, PALLAS_LANES)`` and one grid program takes ``PALLAS_ROWS``
# of them for every word of the state — whole (8, 128) u32 register
# tiles per word, 2.6 MB of VMEM per buffer at 20 words (a row-major
# ``(4096, 20)`` block pads its minor axis to 128 lanes and overflows
# the 16 MB scoped VMEM; measured on the v5e, PR 23)
PALLAS_LANES = 512
PALLAS_ROWS = 64
PALLAS_BLOCK = PALLAS_ROWS * PALLAS_LANES

# selectable values per knob.  The probe has no ``pallas`` variant:
# its membership pass is a random gather from the table planes, which
# Mosaic does not lower ("Cannot do int indexing on TPU"; v5e, PR 23)
IMPLS = {
    "probe_impl": ("legacy", "tile"),
    "expand_impl": ("legacy", "tile", "pallas"),
    "sieve_impl": ("legacy", "tile", "pallas"),
}


def validate_impl(knob: str, impl: Optional[str]) -> str:
    """Normalize/validate one ``*_impl`` knob value (``None`` = the
    engine default ``legacy``)."""
    impl = impl or "legacy"
    if impl not in IMPLS[knob]:
        raise ValueError(
            f"{knob} must be one of {'|'.join(IMPLS[knob])}: {impl}"
        )
    return impl


def interpret() -> bool:
    """Pallas interpret mode follows the backend and nothing else: the
    CPU backend has no Mosaic lowering, so the kernels run interpreted
    there (the tier-1 parity tests); on an accelerator they compile
    natively, and a kernel that does not lower raises — it never runs
    interpreted on the chip unnoticed."""
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------- probe


def _triangular_offsets(rounds: int) -> jax.Array:
    # weak Python literals only: this also traces inside Pallas
    # kernels, where jnp scalar constants would be captured
    r = jnp.arange(rounds, dtype=jnp.uint32)
    return (r * (r + 1)) >> 1


def _member_plane(tcols, kcols, h, rounds: int):
    """The (rounds, n) blocked membership plane for one lane tile:
    gather the triangular probe sequence of every lane AT ONCE and
    reduce first-match vs first-empty.  Returns ``(member,
    resolved)`` bool[n] — both exact where ``resolved``."""
    cap = tcols[0].shape[0] - 1
    capm = jnp.uint32(cap - 1)
    off = _triangular_offsets(rounds)  # (R,)
    slots = ((h[None, :] + off[:, None]) & capm).astype(jnp.int32)
    sv = tuple(c[slots] for c in tcols)  # K gathers of (R, n)
    empty = sv[0] == SENTINEL
    for c in sv[1:]:
        empty = empty & (c == SENTINEL)
    eq = sv[0] == kcols[0][None, :]
    for cv, ck in zip(sv[1:], kcols[1:]):
        eq = eq & (cv == ck[None, :])
    match = eq & ~empty
    ri = jnp.arange(rounds, dtype=jnp.int32)[:, None]
    big = jnp.int32(rounds)
    first_match = jnp.min(jnp.where(match, ri, big), axis=0)
    first_empty = jnp.min(jnp.where(empty, ri, big), axis=0)
    member = first_match < first_empty
    resolved = member | (first_empty < big)
    return member, resolved


def member_block(
    tcols: Tuple[jax.Array, ...],
    kcols: Tuple[jax.Array, ...],
    valid: jax.Array,
    rounds: int = TILE_R,
):
    """Pure-XLA blocked membership prefilter over the whole batch,
    lane-tiled at :data:`TILE_L` so the (rounds, tile) intermediates
    stay small.  Returns ``(member, resolved)`` bool[nq], both masked
    by ``valid`` (invalid lanes read as resolved non-members)."""
    nq = kcols[0].shape[0]
    h = fpset.slot_hash(kcols)
    if nq <= TILE_L:
        member, resolved = _member_plane(tcols, kcols, h, rounds)
        return member & valid, resolved | ~valid
    lt = TILE_L
    ntiles = -(-nq // lt)
    pad = ntiles * lt - nq
    if pad:
        h = jnp.pad(h, (0, pad))
        kcols = tuple(
            jnp.pad(c, (0, pad), constant_values=SENTINEL)
            for c in kcols
        )

    def body(i, st):
        member, resolved = st
        base = i * lt
        kk = tuple(
            lax.dynamic_slice(c, (base,), (lt,)) for c in kcols
        )
        hh = lax.dynamic_slice(h, (base,), (lt,))
        m, r = _member_plane(tcols, kk, hh, rounds)
        member = lax.dynamic_update_slice(member, m, (base,))
        resolved = lax.dynamic_update_slice(resolved, r, (base,))
        return member, resolved

    member, resolved = lax.fori_loop(
        0, ntiles,
        body,
        (
            jnp.zeros((ntiles * lt,), jnp.bool_),
            jnp.zeros((ntiles * lt,), jnp.bool_),
        ),
    )
    member, resolved = member[:nq], resolved[:nq]
    return member & valid, resolved | ~valid


def flush_acc_tiles(
    tcols: Tuple[jax.Array, ...],
    kcols: Tuple[jax.Array, ...],
    n_acc,
    fpm: jax.Array,
    dense_rounds: Optional[int] = None,
    stages=None,
    compact_impl: str = "logshift",
):
    """The tiled accumulator flush — drop-in for
    :func:`ops.fpset.flush_acc` with IDENTICAL ``(tcols', n_new,
    flag_acc, fpm')`` semantics and bit-identical ``is_new`` (see the
    module docstring's exactness argument)."""
    nq = kcols[0].shape[0]
    K = len(kcols)
    dense_rounds, stages = fpset.resolve_schedule(dense_rounds, stages)
    rounds_blk = max(TILE_R, int(dense_rounds))
    # the insert tail inherits the legacy schedule's total budget
    max_probes = max(
        [int(dense_rounds)] + [int(lim) for _, lim in stages]
    )
    lanei = jnp.arange(nq, dtype=jnp.int32)
    amask = lanei < n_acc
    valid = amask & ~fpset.all_sentinel(kcols)
    member, _resolved = member_block(tcols, kcols, valid, rounds_blk)
    survivors = valid & ~member
    # order-preserving compaction of survivors + ORIGINAL lane ids —
    # chunk order is lane order, so cross-chunk equal-key resolution
    # stays min-lane-wins
    drop = (~survivors).astype(jnp.uint32)
    ccols, _ = compact_ops.compact_by_flag(
        drop, tuple(kcols) + (lanei.astype(jnp.uint32),),
        impl=compact_impl, need_idx=False,
    )
    npend = jnp.sum(survivors.astype(jnp.int32))
    cw = max(nq // 4, min(nq, fpset.MIN_STAGE))
    nchunks_cap = -(-nq // cw)
    padn = nchunks_cap * cw - nq
    ckeys = tuple(c for c in ccols[:K])
    cids = ccols[K].astype(jnp.int32)
    if padn:
        ckeys = tuple(
            jnp.pad(c, (0, padn), constant_values=SENTINEL)
            for c in ckeys
        )
        cids = jnp.pad(cids, (0, padn), constant_values=nq)
    nchunks = jnp.minimum(
        (npend + cw - 1) // cw, jnp.int32(nchunks_cap)
    )

    def chunk(i, carry):
        tc, is_new, nf, rounds = carry
        base = i * cw
        kk = tuple(
            lax.dynamic_slice(c, (base,), (cw,)) for c in ckeys
        )
        lid = lax.dynamic_slice(cids, (base,), (cw,))
        pend = base + jnp.arange(cw, dtype=jnp.int32) < npend
        new2, tc, _, pending, r = fpset.probe_insert(
            tc, kk, pend, max_probes=max_probes, lane_ids=lid
        )
        tgt = jnp.where(new2, lid, jnp.int32(nq))
        is_new = is_new.at[tgt].set(True, mode="drop")
        nf = nf + jnp.sum(pending.astype(jnp.int32))
        return (tc, is_new, nf, rounds + r)

    tcols2, is_new, n_failed, rounds = lax.fori_loop(
        0, nchunks, chunk,
        (
            tuple(tcols),
            jnp.zeros((nq,), jnp.bool_),
            jnp.int32(0),
            jnp.int32(rounds_blk),
        ),
    )
    n_new = jnp.sum(is_new.astype(jnp.int32))
    # lanes presented: the membership block reads all nq lanes for its
    # rounds, every chunk round after it presents one chunk's width
    lane_rounds = jnp.uint32(nq * rounds_blk) + jnp.uint32(cw) * (
        rounds - rounds_blk
    ).astype(jnp.uint32)
    fpm2 = fpset.fpm_update(
        fpm, rounds, n_failed, jnp.sum(valid.astype(jnp.int32)),
        lane_rounds,
    )
    return tcols2, n_new, is_new.astype(jnp.uint32), fpm2


# ------------------------------------------------------------ expand


def _rotl_k(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _fmix_k(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _murmur3_planes_k(planes, seed: int):
    """murmur3 over a state's words, one u32 plane per word."""
    w = len(planes)
    h = jnp.full(planes[0].shape, np.uint32(seed), jnp.uint32)
    for p in planes:
        k = p * np.uint32(0xCC9E2D51)
        k = _rotl_k(k, 15) * np.uint32(0x1B873593)
        h = h ^ k
        h = _rotl_k(h, 13) * np.uint32(5) + np.uint32(0xE6546B64)
    return _fmix_k(h ^ np.uint32(4 * w))


def _key_cols_kernel(keyspec, planes):
    """``KeySpec.make`` re-expressed over word planes (``planes[i]`` is
    word ``i`` of every lane) with kernel-safe numpy-literal constants
    (the dedup originals are jnp scalars, which a Pallas kernel trace
    would capture).  Bit-identical to ``keyspec.make`` — pinned by the
    ``key_plane`` parity properties in ``tests/test_tiles.py``."""
    if keyspec.exact:
        cols = list(planes)
        while len(cols) < keyspec.ncols:
            cols.append(jnp.zeros(planes[0].shape, jnp.uint32))
        return tuple(cols)
    h = [
        _murmur3_planes_k(planes, seed)
        for seed in (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35)[
            : keyspec.ncols
        ]
    ]
    all_sent = h[0] == _SENT
    for c in h[1:]:
        all_sent = all_sent & (c == _SENT)
    h[-1] = jnp.where(all_sent, h[-1] ^ np.uint32(1), h[-1])
    return tuple(h)


def key_plane(keyspec, packedf: jax.Array, vflat: jax.Array,
              impl: str = "tile"):
    """Key-column formation for one expand window's flattened
    successor matrix: ``packed u32[nc, W] -> K masked u32[nc]``
    columns (invalid lanes SENTINEL).  ``tile`` runs the mixing chain
    as one full-matrix XLA op; ``pallas`` runs it as an explicit
    kernel over word-major ``(W, PALLAS_ROWS, PALLAS_LANES)`` blocks
    (each word of the state is one lane-dense register plane, so the
    mixing chain is plain elementwise VPU work).  Both are elementwise
    per lane — bit-identical to the legacy per-chunk path."""
    if impl != "pallas":
        kcols = keyspec.make(packedf)
        return tuple(
            jnp.where(vflat, c, SENTINEL) for c in kcols
        )
    from jax.experimental import pallas as pl

    nc, w = packedf.shape
    K = keyspec.ncols
    pad = -nc % PALLAS_BLOCK
    words = packedf.T  # word-major, as the accumulator stores rows
    vmask = vflat.astype(jnp.uint32)
    if pad:
        words = jnp.pad(words, ((0, 0), (0, pad)))
        vmask = jnp.pad(vmask, (0, pad))
    words = words.reshape(w, -1, PALLAS_LANES)
    vmask = vmask.reshape(-1, PALLAS_LANES)
    rows = vmask.shape[0]

    def kernel(p_ref, v_ref, *orefs):
        cols = _key_cols_kernel(keyspec, [p_ref[i] for i in range(w)])
        v = v_ref[...] != 0
        for o, c in zip(orefs, cols):
            o[...] = jnp.where(v, c, _SENT)

    plane = pl.BlockSpec((PALLAS_ROWS, PALLAS_LANES), lambda i: (i, 0))
    cols = pl.pallas_call(
        kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct((rows, PALLAS_LANES), jnp.uint32)
            for _ in range(K)
        ),
        grid=(rows // PALLAS_ROWS,),
        in_specs=(
            pl.BlockSpec(
                (w, PALLAS_ROWS, PALLAS_LANES), lambda i: (0, i, 0)
            ),
            plane,
        ),
        out_specs=tuple(plane for _ in range(K)),
        interpret=interpret(),
    )(words, vmask)
    return tuple(c.reshape(-1)[:nc] for c in cols)


# ------------------------------------------------------------- sieve


def sieve_mask_planes(
    tcols: Tuple[jax.Array, ...],
    gen: jax.Array,
    cold: jax.Array,
    impl: str = "tile",
):
    """The sieve's masking plane as a tile op: ``(masked_ev cols,
    holed cols, gen_cleared)`` from the cold mask — elementwise over
    the table planes (``tile`` = one fused XLA pass; ``pallas`` = an
    explicit elementwise kernel over slot tiles)."""
    if impl != "pallas":
        masked = tuple(
            jnp.where(cold, c, SENTINEL) for c in tcols
        )
        holed = tuple(
            jnp.where(cold, SENTINEL, c) for c in tcols
        )
        gen2 = jnp.where(cold, jnp.int32(0), gen)
        return masked, holed, gen2
    from jax.experimental import pallas as pl

    K = len(tcols)
    cap1 = tcols[0].shape[0]
    lt = min(PALLAS_TILE, cap1)
    ntiles = -(-cap1 // lt)
    pad = ntiles * lt - cap1
    cols = tcols
    if pad:
        cols = tuple(
            jnp.pad(c, (0, pad), constant_values=SENTINEL)
            for c in tcols
        )
        gen = jnp.pad(gen, (0, pad))
        cold = jnp.pad(cold, (0, pad))

    def kernel(*refs):
        trefs = refs[:K]
        cold_ref, gen_ref = refs[K], refs[K + 1]
        m_refs = refs[K + 2: 2 * K + 2]
        h_refs = refs[2 * K + 2: 3 * K + 2]
        g_ref = refs[3 * K + 2]
        cm = cold_ref[...]
        for m, hr, t in zip(m_refs, h_refs, trefs):
            v = t[...]
            m[...] = jnp.where(cm, v, _SENT)
            hr[...] = jnp.where(cm, _SENT, v)
        g_ref[...] = jnp.where(cm, 0, gen_ref[...])

    spec = pl.BlockSpec((lt,), lambda i: (i,))
    out = pl.pallas_call(
        kernel,
        out_shape=(
            tuple(
                jax.ShapeDtypeStruct((ntiles * lt,), jnp.uint32)
                for _ in range(2 * K)
            )
            + (jax.ShapeDtypeStruct((ntiles * lt,), jnp.int32),)
        ),
        grid=(ntiles,),
        in_specs=[spec] * (K + 2),
        out_specs=tuple([spec] * (2 * K + 1)),
        interpret=interpret(),
    )(*cols, cold, gen)
    masked = tuple(c[:cap1] for c in out[:K])
    holed = tuple(c[:cap1] for c in out[K: 2 * K])
    gen2 = out[2 * K][:cap1]
    return masked, holed, gen2


def extract_cold_tiles(
    tcols: Tuple[jax.Array, ...],
    gen: jax.Array,
    cutoff,
    sieve_impl: str = "tile",
):
    """The tiled ``extract_cold``: identical contract and ARRAY-
    identical outputs to :func:`store.sieve.extract_cold`, with the
    pre-sort compaction dropped — the sort receives the same multiset
    (cold keys are distinct table entries; SENTINEL padding sorts
    last), so sorting the masked planes directly yields the same
    sorted columns while skipping the gather-heavy compact pass."""
    cap = tcols[0].shape[0] - 1
    lane = jnp.arange(cap + 1, dtype=jnp.int32)
    occ = ~fpset.all_sentinel(tcols) & (lane < cap)
    cold = occ & (gen >= 1) & (gen <= jnp.int32(cutoff))
    n_ev = jnp.sum(cold.astype(jnp.int32))
    masked, holed, gen2 = sieve_mask_planes(
        tcols, gen, cold, impl=sieve_impl
    )
    ev_sorted = lax.sort(
        masked, num_keys=len(masked), is_stable=False
    )
    return holed, gen2, ev_sorted, n_ev
