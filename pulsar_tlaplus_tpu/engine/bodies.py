"""The single-chip engine's programs, as units.

Every public function here is a unit (``engine/units.py``) and a whole
PROGRAM of ``engine/device_bfs.py``: a module-level function that closes
over nothing, wrapped once, at import, in ``jax.jit`` and dispatched from
the host under its own name (``jit_ptt_expand``, ``jit_ptt_level2``,
...; the three that trace the probe carry a ``2`` since its part scopes
came, PR 38: the cache-key rule of docs/observability.md).  What a
program used to read of its
``DeviceChecker`` is an explicit argument — arrays traced, everything
else static and hashed by value (the model by class and constants,
``models.ByConstants``; the key spec by its layout,
``ops.dedup.KeySpec``; the compaction's materialization, which the
environment can change) — so the program's identity outlives the checker
and its key is everything it reads.  A second check of one binding
presents JAX with equal arguments and is handed the executable the
first one built: no trace, no lowering, no load from the compile cache.
A first check traces each program once, and it lowers to the HLO it
lowered to when it was a closure of its checker
(``scripts/parent_cache_check.py``).

The bodies the programs share (``_expand_window``, ``_init_window``,
``_append_new``, and the flush and the compaction of ``ops/``) are plain
functions under their stage scopes, traced in place: a body inlined from
a cache of its own builds its equations twice on a miss, which doubled a
first check on the chip's host (PERF.md §6, PR 33).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pulsar_tlaplus_tpu.engine.units import unit
from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ops import compact as compact_ops
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL

BIG = jnp.int32(2**31 - 1)
# the fused kernel's log base: its trace logs are never windowed.  A
# device scalar made at import, not inside a trace, where it would be
# a literal: the program keeps the constant operand it has always had
_LOG_BASE_0 = jnp.int32(0)


def _first_violations(rows, gids, live, model, invariant_names):
    """Per invariant, the least gid among the ``live`` rows that
    violate it (``BIG`` where none does): i32[len(invariant_names)].
    ``rows`` are packed u32[N, W]; the names index
    ``model.invariants``."""
    inv_fns = [model.invariants[n] for n in invariant_names]
    states = jax.vmap(model.layout.unpack)(rows)
    vnew = []
    for fn in inv_fns:
        ok = jax.vmap(fn)(states)
        bad = live & ~ok
        vnew.append(jnp.min(jnp.where(bad, gids, BIG)))
    return jnp.stack(vnew)


# ------------------------------------------------ the stages, as written

# ops/ bodies carry no scope of their own: the engine names its stages
_probe_flush_acc = spans.staged("probe")(fpset.flush_acc)
_compact_rows = spans.staged("compact")(compact_ops.compact_rows)


@spans.staged("expand")
def _expand_window(
    ak, arows, window, f_off, n_live, dead_gid, gid_base, acc_off, *,
    model, keys, Fi, G, check_deadlock,
):
    """Expand one G-state window into ``G * A`` candidate lanes and
    append their key columns + packed rows into the accumulator at
    ``acc_off`` (shared by the stage chain's ``ptt_expand`` and the
    fused level kernel).  ``window`` is the flat [G*W] slice of the
    row store, ``f_off`` its first row index within the current level
    (for liveness masking and deadlock gids).  Returns ``(ak',
    arows', dead_gid')``."""
    layout = model.layout
    A, W = model.A, layout.W

    def chunk(i):
        rows = lax.dynamic_slice(
            window, (i * Fi * W,), (Fi * W,)
        ).reshape(Fi, W)
        pos = f_off + i * Fi + jnp.arange(Fi, dtype=jnp.int32)
        live = pos < n_live
        states = jax.vmap(layout.unpack)(rows)
        succ, valid = jax.vmap(model.successors)(states)  # [Fi, A]
        valid = valid & live[:, None]
        packed = jax.vmap(jax.vmap(layout.pack))(succ)  # [Fi, A, W]
        fa = Fi * A
        packedf = packed.reshape(fa, W)
        kcols = keys.make(packedf)
        vflat = valid.reshape(fa)
        kcols = tuple(jnp.where(vflat, c, SENTINEL) for c in kcols)
        if check_deadlock:
            stut = jax.vmap(model.stutter_enabled)(states)
            dead_rows = live & ~jnp.any(valid, axis=1) & ~stut
            didx = jnp.min(jnp.where(dead_rows, pos, BIG))
        else:
            didx = BIG
        return kcols, packedf, didx

    def body(dead, i):
        kcols, p, didx = chunk(i)
        dead = jnp.minimum(
            dead, jnp.where(didx < BIG, gid_base + didx, BIG)
        )
        return dead, (kcols, p)

    dead, (kcols, packed) = lax.scan(
        body, dead_gid, jnp.arange(G // Fi, dtype=jnp.int32)
    )
    nc = G * A
    ak = tuple(
        lax.dynamic_update_slice(akc, kc.reshape(nc), (acc_off,))
        for akc, kc in zip(ak, kcols)
    )
    arows = lax.dynamic_update_slice(
        arows, packed.reshape(nc, W).T, (0, acc_off)
    )
    return ak, arows, dead


@spans.staged("init")
def _init_window(ak, arows, f_off, acc_off, *, model, keys, NCs, Fi):
    """Generate ``NCs`` initial-state candidates (indices
    ``f_off..f_off+NCs``) into the accumulator at ``acc_off`` — the
    mixed-radix counting kernel shape from SURVEY.md §3.2.  Returns
    ``(ak', arows')``."""
    W = model.layout.W
    n_init = min(model.n_initial, (1 << 31) - 1)

    def chunk(i):
        # Fi lanes per scan step: an unchunked vmap over all NCs
        # lanes materializes the full unpacked state structs —
        # gigabytes at bench widths (this OOMed the first bench run)
        idx = f_off + i * Fi + jnp.arange(Fi, dtype=jnp.int32)
        states = jax.vmap(model.gen_initial)(idx)
        packed = jax.vmap(model.layout.pack)(states)
        valid = idx < n_init
        kcols = keys.make(packed)
        return (
            tuple(jnp.where(valid, c, SENTINEL) for c in kcols),
            packed,
        )

    _, (kcols, packed) = lax.scan(
        lambda c, i: (c, chunk(i)),
        0,
        jnp.arange(NCs // Fi, dtype=jnp.int32),
    )
    kcols = tuple(c.reshape(NCs) for c in kcols)
    ak = tuple(
        lax.dynamic_update_slice(akc, kc, (acc_off,))
        for akc, kc in zip(ak, kcols)
    )
    arows = lax.dynamic_update_slice(
        arows, packed.reshape(NCs, W).T, (0, acc_off)
    )
    return ak, arows


@spans.staged("append")
def _append_new(
    rows_store, parent_log, lane_log, crows, idx, n_new, n_visited, viol,
    acc_base, is_init, row_base, rows_ok, log_base, *,
    model, invariant_names, SL, C, LCAP,
):
    """Land a flush's new states (compacted to the front of
    ``crows[W, ACAP]`` in discovery order, ``idx`` their original
    accumulator slots) in the row store and the trace logs, evaluating
    the invariants on exactly the new states in ``C`` chunks of ``SL``
    rows — the contract of ``DeviceChecker._append_jit``, which
    dispatches this body, as the fused level kernel chains it.
    ``LCAP`` is the row store's capacity in rows (its flat length
    carries padding, so the shape alone does not say).  Returns
    ``(rows_store', parent_log', lane_log', n_visited + n_new,
    viol')``."""
    A = model.A
    W, ACAP = crows.shape
    n_inv = len(invariant_names)
    ccols = tuple(crows[j] for j in range(W))
    lanei = jnp.arange(ACAP, dtype=jnp.int32)
    live = lanei < n_new
    par = jnp.where(
        is_init, -1 - (acc_base + idx), acc_base + idx // A
    )
    lane = jnp.where(is_init, 0, idx % A)
    par = jnp.where(live, par, 0)
    lane = jnp.where(live, lane, 0)
    # pad so the chunks can never clamp mid-window
    pad = C * SL - ACAP
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
    woff = jnp.where(
        rows_ok, n_visited - row_base, jnp.int32(LCAP - C * SL)
    )

    # the SL-chunked loop does BOTH invariant evaluation and
    # the row-store append: each chunk interleaves its [SL, W]
    # rows (needed for the unpack anyway) and lands them with a
    # blind DUS at [woff + off, ...).  Writing the store
    # chunk-wise keeps every intermediate SL-sized — a
    # monolithic [ACAP, W] stack takes the 128-padded T(8,128)
    # tiled layout on TPU (6.4x memory = 9.1 GB at the ff=2
    # bench tier; it OOMed the XLA memory planner).  The run
    # loop guarantees ``woff + APAD <= LCAP`` before
    # dispatching, so no DUS can clamp.
    def chunk(c, carry):
        viol, store = carry
        off = c * SL
        rows = jnp.stack(
            [
                lax.dynamic_slice(col, (off,), (SL,))
                for col in ecols
            ],
            axis=1,
        )
        if n_inv:
            gids = n_visited + off + jnp.arange(
                SL, dtype=jnp.int32
            )
            livec = (
                off + jnp.arange(SL, dtype=jnp.int32) < n_new
            )
            viol = jnp.minimum(
                viol,
                _first_violations(
                    rows, gids, livec, model, invariant_names
                ),
            )
        store = lax.dynamic_update_slice(
            store, rows.reshape(SL * W),
            ((woff + off) * W,),
        )
        return (viol, store)

    n_chunks = jnp.minimum((n_new + SL - 1) // SL, C)
    viol, rows_store = lax.fori_loop(
        0, n_chunks, chunk, (viol, rows_store)
    )
    parent_log = lax.dynamic_update_slice(
        parent_log, par, (n_visited - log_base,)
    )
    lane_log = lax.dynamic_update_slice(
        lane_log, lane, (n_visited - log_base,)
    )
    return (
        rows_store, parent_log, lane_log, n_visited + n_new,
        viol,
    )


# ---------------------------------------------- the units: the programs
#
# Each is a whole program of the engine, dispatched from the host under
# the name it has always had (``jit_ptt_expand`` ...): an ordinary
# ``jax.jit`` at module level, so a later checker of the same binding,
# sizes and tier is handed the executable an earlier one built.  Key
# columns travel as tuples (``ak``, ``tc``, ``vk``) so that the donated
# positions do not depend on the key's width; results stay flat, as the
# lowered module names them.

@unit(
    static=("model", "keys", "Fi", "G", "check_deadlock"), donate=(0, 1)
)
def ptt_expand(
    ak, arows, window, f_off, n_live, dead_gid, gid_base, acc_off, *,
    model, keys, Fi, G, check_deadlock,
):
    """The stage chain's expand dispatch: ``(*ak', arows',
    dead_gid')``."""
    ak, arows, dead = _expand_window(
        ak, arows, window, f_off, n_live, dead_gid, gid_base, acc_off,
        model=model, keys=keys, Fi=Fi, G=G, check_deadlock=check_deadlock,
    )
    return (*ak, arows, dead)


@unit(static=("model", "keys", "NCs", "Fi"), donate=(0, 1))
def ptt_init(ak, arows, f_off, acc_off, *, model, keys, NCs, Fi):
    """Initial-state generation into the accumulator: ``(*ak',
    arows')``."""
    ak, arows = _init_window(
        ak, arows, f_off, acc_off, model=model, keys=keys, NCs=NCs, Fi=Fi
    )
    return (*ak, arows)


@unit(static=("dense_rounds", "stages", "materialize"), donate=(0,))
def ptt_fpflush2(tc, ak, n_acc, fpm, *, dense_rounds, stages, materialize):
    """The flush dispatch — the body lives in ops/fpset.py so that the
    level kernel chains the IDENTICAL trace: ``(*tc', n_new, flag_acc,
    fpm')``."""
    tc2, n_new, flag, fpm = _probe_flush_acc(
        tc, ak, n_acc, fpm, dense_rounds=dense_rounds, stages=stages,
        materialize=materialize,
    )
    return (*tc2, n_new, flag, fpm)


@unit("rehash", static=("materialize",))
def ptt_rehash2(old, *, materialize):
    """Table growth: the old table's columns -> double-capacity
    columns and the rehash vector (``fpset.rhm_logical``: failures,
    keys moved, lanes presented), ``(*new, rhm)``.  No donation: the
    inputs are half the output's shape, so XLA could never reuse them
    (donating only produces warnings)."""
    new, rhm = fpset.rehash_cols(
        old, fpset.empty_cols(2 * (old[0].shape[0] - 1), len(old)),
        materialize=materialize,
    )
    return (*new, rhm)


@unit("grow", static=("pad",))
def ptt_grow(buf, *, pad):
    """Store growth: ``buf`` (the flat row store or a trace log) with
    ``pad`` zeros after it — one program under the ``ptt.grow`` scope
    where an eager fill and an eager concatenate ran unnamed.  No
    donation: the output is larger than the input."""
    return jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])


@unit("ckpt_fetch", static=("size",))
def ptt_ckpt_fetch(buf, start, *, size):
    """``buf[start: start + size]``: what a checkpoint frame needs of a
    row store or a trace log that is under half full, sliced on the
    device to a bucketed ``size`` (``start`` is traced), so that a
    process meets a handful of these and not one a frame."""
    return lax.dynamic_slice(buf, (start,), (size,))


@unit("restore", static=("length",))
def ptt_restore_pad(data, *, length):
    """A restored row store or trace log: the frame's ``data`` (uploaded
    at a bucketed length) with zeros after it up to the buffer's
    ``length`` — one program a ``(bucket, length)`` where an eager fill
    and an eager concatenate ran at the frame's own length."""
    return jnp.concatenate(
        [data, jnp.zeros((length - data.shape[0],), data.dtype)]
    )


@unit(static=("materialize",), donate=(0,))
def ptt_compact(arows, flag_acc, *, materialize):
    """The compaction dispatch: ``(crows, idx)``."""
    return _compact_rows(arows, flag_acc, materialize=materialize)


@unit(
    static=("model", "invariant_names", "SL", "C", "LCAP"),
    donate=(0, 1, 2),
)
def ptt_append(
    rows_store, parent_log, lane_log, crows, idx, n_new, n_visited, viol,
    acc_base, is_init, row_base, rows_ok, log_base, *,
    model, invariant_names, SL, C, LCAP,
):
    """The append dispatch (``DeviceChecker._append_jit`` holds the
    contract)."""
    return _append_new(
        rows_store, parent_log, lane_log, crows, idx, n_new, n_visited,
        viol, acc_base, is_init, row_base, rows_ok, log_base, model=model,
        invariant_names=invariant_names, SL=SL, C=C, LCAP=LCAP,
    )


LEVEL_STATIC = (
    "model", "keys", "invariant_names", "Fi", "G", "FLUSH",
    "check_deadlock", "dense_rounds", "stages", "materialize", "SL", "C",
    "VCAP", "LCAP", "PCAP", "SCAP", "RMAX", "frontier_mode",
)


# the whole kernel traces under ptt.levelctl; the four stages nest
# inside it, and an operation belongs to its innermost scope — so the
# loop's own control flow, the boundary bookkeeping and the stats
# vector are what levelctl keeps
@unit("levelctl", static=LEVEL_STATIC, donate=(0, 1, 2, 3, 4, 5))
def ptt_level2(
    vk, ak, arows, rows, parent, lane, n_visited, dead, viol, fpm, wkm,
    level_base, nf, w_off, levels_left, groups_left, row_base, rows_ok, *,
    model, keys, invariant_names, Fi, G, FLUSH, check_deadlock,
    dense_rounds, stages, materialize, SL, C, VCAP, LCAP, PCAP, SCAP,
    RMAX, frontier_mode,
):
    """The level megakernel (``DeviceChecker._fused_jit`` holds its
    contract): ONE dispatch walks flush groups — and, on the ramp,
    whole level boundaries — of the BFS inside a ``lax.while_loop``,
    each iteration expanding ``FLUSH`` windows of the frontier into
    the accumulator, flushing it into the table, compacting the new
    rows to the front and appending them.

    This unit is a whole PROGRAM, not a body inlined into one: called
    from the host it is an ordinary ``jax.jit`` whose cache JAX keys on
    the arguments below, so the second check of a binding finds the
    executable itself — no trace, no lowering, no load from the
    compile cache — and a first check traces it once, as it always
    did.  (As a body inlined into a per-checker program, a miss built
    the jaxpr twice: my chip runs, PR 33, a first check of the 253k
    binding 7.3 -> 11.8 s.)  ``vk`` and ``ak`` are the tuples of the
    table's and the accumulator's key columns; they, the accumulator
    rows, the row store and both logs are donated."""
    A = model.A
    W = model.layout.W
    NCs = G * A
    ACAP = NCs * FLUSH
    APAD = C * SL
    # device scalars made outside the trace, as they were when a
    # checker built this program: a scalar made under the trace would
    # be a literal, and the loop would lose the operands it has
    # (``device_put`` of a numpy scalar: a transfer, no program)
    with jax.ensure_compile_time_eval():
        scalar = lambda v: jax.device_put(np.int32(v))
        ramp_t = scalar(G)  # new-level batch threshold: one window
        # write-capacity limits, trace-time constants per tier: the
        # append's blind APAD window and the ACAP-wide log DUS must
        # never clamp (reads are clamp-safe — masked by n_live)
        plimit = scalar(PCAP - APAD)
        llimit = None if frontier_mode else scalar(LCAP - APAD)

    def viol_found(viol, dead):
        return jnp.any(viol < BIG) | (dead < BIG)

    def cond(st):
        (vk, ak, arows, rows, parent, lane, nv, dead, viol,
         fpm, wkm, lb, nf, w_off, lv_left, g_left, rows_ok,
         lsizes, n_lv) = st
        live = nf - w_off  # frontier rows not yet expanded
        gnew = jnp.where(
            live > ACAP // A, jnp.int32(ACAP),
            live * A,
        )
        fits = (
            (nv + gnew <= VCAP)
            & (nv <= plimit)
            & (nv < SCAP)
        )
        if llimit is not None:
            fits = fits & (nv <= llimit)
        mid = (w_off > 0) & (w_off < nf)
        fresh = (
            (w_off == 0)
            & (nf > 0)
            & (lv_left > 0)
            & ~viol_found(viol, dead)
            # ramp early-exit: only the dispatch's FIRST level
            # may exceed one expand window
            & ((n_lv == 0) | (nf <= ramp_t))
        )
        return (g_left > 0) & fits & (mid | fresh)

    def body(st):
        (vk, ak, arows, rows, parent, lane, nv, dead, viol,
         fpm, wkm, lb, nf, w_off, lv_left, g_left, rows_ok,
         lsizes, n_lv) = st
        # expand FLUSH windows into the accumulator (windows
        # past the frontier end produce SENTINEL lanes — the
        # same masking the stage chain's partial fills rely on)
        for w in range(FLUSH):
            f_off = w_off + jnp.int32(w * G)
            with spans.stage("expand"):
                window = lax.dynamic_slice(
                    rows, ((lb - row_base + f_off) * W,),
                    (G * W,),
                )
            ak, arows, dead = _expand_window(
                ak, arows, window, f_off, nf, dead, lb,
                jnp.int32(w * NCs), model=model, keys=keys, Fi=Fi,
                G=G, check_deadlock=check_deadlock,
            )
        vk, n_new, flag, fpm = _probe_flush_acc(
            vk, ak, jnp.int32(ACAP), fpm, dense_rounds=dense_rounds,
            stages=stages, materialize=materialize,
        )
        crows, idx = _compact_rows(arows, flag, materialize=materialize)
        if frontier_mode:
            # per-group actual-occupancy check — exactly the
            # predicate the stage loop evaluates at its forced
            # pre-overflow fetch (monotone: once lost, lost)
            rows_ok = rows_ok & (
                nv - row_base + APAD <= LCAP
            )
        rows, parent, lane, nv2, viol = _append_new(
            rows, parent, lane, crows, idx, n_new, nv, viol,
            lb + w_off, jnp.bool_(False), row_base, rows_ok,
            _LOG_BASE_0, model=model,
            invariant_names=invariant_names, SL=SL, C=C, LCAP=LCAP,
        )
        arows = crows  # recycled as the next group's buffer
        # in-kernel work units (r14): the group's LIVE frontier
        # rows (level totals then equal the stage chain's
        # per-dispatch sums exactly), the full accumulator
        # width presented to flush + compact (their dense cost
        # driver), the deduped rows appended, and this
        # iteration — all riding the stats vector below
        wkm = fpset.wkm_update(
            wkm,
            jnp.clip(nf - w_off, 0, FLUSH * G),
            jnp.int32(ACAP), jnp.int32(ACAP),
            n_new, jnp.int32(1),
        )
        w_off2 = w_off + jnp.int32(FLUSH * G)
        g_left = g_left - 1
        # level boundary?
        done = w_off2 >= nf
        size = nv2 - (lb + nf)
        lsizes = jnp.where(
            done,
            lsizes.at[jnp.minimum(n_lv, RMAX - 1)].set(size),
            lsizes,
        )
        di = done.astype(jnp.int32)
        n_lv = n_lv + di
        lv_left = lv_left - di
        lb = jnp.where(done, lb + nf, lb)
        nf = jnp.where(done, size, nf)
        w_off = jnp.where(done, jnp.int32(0), w_off2)
        return (
            vk, ak, arows, rows, parent, lane, nv2, dead,
            viol, fpm, wkm, lb, nf, w_off, lv_left, g_left,
            rows_ok, lsizes, n_lv,
        )

    st = (
        tuple(vk), tuple(ak), arows, rows, parent, lane,
        n_visited, dead, viol, fpm, wkm, level_base, nf, w_off,
        levels_left, groups_left, rows_ok,
        jnp.zeros((RMAX,), jnp.int32), jnp.int32(0),
    )
    (vk, ak, arows, rows, parent, lane, nv, dead, viol, fpm,
     wkm, lb, nf, w_off, lv_left, g_left, rows_ok, lsizes,
     n_lv) = lax.while_loop(cond, body, st)
    statsvec = jnp.concatenate(
        [
            jnp.stack([nv, dead]), viol, fpm, wkm,
            jnp.stack(
                [
                    lb, nf, w_off, n_lv,
                    rows_ok.astype(jnp.int32), g_left,
                ]
            ),
            lsizes,
        ]
    )
    # flat, as the program has always returned them: the result's
    # tree is named in the lowered module (``jax.result_info``)
    return (
        *vk, *ak, arows, rows, parent, lane, nv, dead, viol,
        fpm, wkm, statsvec,
    )
