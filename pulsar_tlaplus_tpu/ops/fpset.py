"""Device-resident growable FPSet — the hash-table visited set.

Why a table.  A sort-merge flush (three full-width sorts of up to 203M
keys per 26.7M-candidate accumulator, ~50% of stage time in
BASELINE.md's round-5 split) is a per-candidate cost that GROWS with
the visited set.  An HBM-resident open-addressing
table makes dedup O(batch * E[probes]) independent of how many states
have been visited — the frontier-expansion shape tensor-core BFS work
(BLEST, arxiv 2512.21967; Graph Traversal on Tensor Cores, arxiv
2606.05081) gets its throughput from.  BASELINE.md's own crossover
estimate ("wins once the visited set is >= 2x the 78M-key tier") is the
sizing argument; this module is the `ops/hashtable.py` triangular-
probing design generalised to the device hot path:

- **K key columns** (2 or 3 uint32 words, straight from
  :class:`~pulsar_tlaplus_tpu.ops.dedup.KeySpec`) instead of the fixed
  3+occupancy layout — the all-SENTINEL tuple is the empty marker
  (KeySpec reserves it), so no occupancy column and one fewer scatter
  per insert round.
- **Staged pending compaction, width following the pending count.**
  A probe round costs by the lane PRESENTED, parked or pending: 2K+1
  gathers and K+1 scatters over the whole buffer (on a v5e a gather
  costs about 13 ns for a pending lane and 31 ns for a parked one,
  which reads the one trash row; my chip runs, PR 28).
  The expected MAX probe count over millions of lanes is ~log2(nq) /
  log2(1/load) rounds, so a single monolithic loop pays ~10-20 dense
  rounds for a tail that involves a few thousand lanes (this is what
  kept the table off the hot path in rounds 3-5).  ``lookup_or_insert``
  therefore walks a ladder of narrowing buffers — all ``nq`` lanes,
  then 1/div of them per stage — compacting the surviving pending
  lanes (the `compact_by_flag` idiom) at each step, and a step ends
  as soon as what is pending fits the next one (PR 28).  The default
  ladder narrows by HALVES between 1/4 and 1/64 of the batch (PR 37),
  so a round is presented no more than about twice the lanes that
  were pending when its step began, whatever share of the batch is
  valid: a ``cli check`` flush holds one valid lane in ten, which the
  two-step ladder before (1/4, then 1/64: a 16x gap) presented 6.10
  times over and the halves 3.06 (``STAGES`` below, with the chip's
  readings; ``STAGES_TWO_STEP`` is kept for the sharded engine and
  the rehash).  A step is one order-preserving compaction of what is
  pending, one probe loop and one scatter of its winners' flags: its
  price is a few bandwidth-bound passes over the step before it, and
  a probe loop more to compile in every program that holds a flush,
  which is why a buffer of over 2^20 lanes narrows by quarters
  (``QUARTER_ABOVE``; :func:`ladder_steps` is the ladder of a width).
  The round counts of the schedule are CEILINGS: ``dense_rounds`` and
  each stage's limit bound how long a step may wait for its survivors
  to fit.  At load <= 1/2 the expected pending fraction after r
  rounds is ~2^-r, so at a ceiling the static stage capacities carry
  2-8x safety margins (the dense step's 4 rounds against 1/4; every
  later step waits to round 16 or more for a half or a quarter of
  itself); a lane that overflows a stage there is counted in
  ``n_failed`` and the engine fails LOUDLY (the same fail-stop
  contract as `ops/hashtable.py`), never a silent drop.  Every
  pending lane probes slot (h + r(r+1)/2) at the same global round r
  with its original lane id whichever buffer holds it, so winners,
  table and round count are those of the single loop, bit for bit.
- **Deterministic discovery order.**  Equal-key lanes resolve to the
  minimum lane id (the least ``lane_ids`` among the bidders of a slot
  wins it; compaction is order-preserving and stages bid with original
  lane ids): "lowest accumulator slot wins", so gids follow lane order
  whatever the table's layout.
- **Two arbitrations, one result** (PR 40).  Which bidder of an empty
  slot wins is found in one of two ways, chosen from the static shapes
  of the round alone (:func:`arbitrates_among_lanes`, a function of
  the buffer's lanes ``nq`` and the table's slots ``cap``):
  :func:`win_by_claims` refills a ``claims`` array of ``cap + 1`` lane
  ids, scatter-mins the bidders' ids into it and reads it back — two
  passes over the table's size a round, and by the lane scattered, so
  it is the WIDE round's (a flagship flush scatters 26.7M lanes into
  2^27 slots in 0.23 s); :func:`win_among_lanes` compares the ``nq``
  lanes with each other — a lane wins unless another bidder has its
  slot and a smaller lane id — which touches nothing of the table's
  size and costs ``nq * nq`` compares, so it is the NARROW round's
  (a ``cli check`` of 9.4M states runs 17,857 of its 23,133 rounds at
  1,024 lanes on tables of up to 2^25 slots, where the ``claims``
  passes were 5.3 s of a 23.9 s check: ``LANE_ARB_*`` below, with the
  chip's readings).  Min-lane-wins holds on both, same-key losers
  resolve in the round's reread and a loser with another key moves
  on, so ``(is_new, table, pending, rounds)`` are the same bit for bit
  and the choice is invisible to every caller.
- **Two writes, one table** (PR 42).  The winners' keys go to the
  table by a scatter a key column, and on a table past some 2^22 slots
  a scatter costs by the LANE HANDED to it, parked or winning (0.1 us
  on a v5e while it is handed fewer than a lane to 1,024 slots; a
  tenth of that from there on).  A wide round hands the scatters every
  lane of its buffer, the others parked on the trash row, and most of
  a wide round's lanes win.  A round NARROW against its table
  (:func:`writes_winners`, the same two static shapes) presents the
  tail of a flush, of which one lane in five wins (one in twenty at
  1,024 lanes): :func:`write_winners` packs the winners' slots and key
  words to the front of the round's own buffer (a prefix sum and K + 1
  scatters into ``nq + 1`` words) and hands the table chunks of
  ``WRITE_CHUNK`` lanes, as many as hold the winners and none for a
  round nobody won (a ``cli check`` of 9.4M states handed the table
  52.7M lanes a column where 9.4M carried a key: ``NARROW_*`` and
  ``WRITE_*`` below, with the chip's readings).  The same words land
  in the same slots, the trash row alone differs (write-only: no
  checkpoint, digest or rehash reads it), and ``write_saved`` counts
  what the table was spared (``fpset_write_lanes``).
- **On-device growth**: :func:`rehash_cols` re-inserts every occupied
  slot of the old table into the new one, fully on device: a
  `fori_loop` over chunks of ``REHASH_CHUNK`` old slots; a chunk's
  occupied slots are packed to the front (one ``compact_by_flag``;
  the buffer is 5/8 of the chunk, the load contract's 1/2 and a
  margin) and go through ``lookup_or_insert``'s two-step ladder
  (``STAGES_TWO_STEP``) — one dispatch, no host staging, and the
  transient is old + new table + a few columns of one chunk.  A
  rehash so costs by the key it moves: an empty slot is read once and
  never presented to the new table (a parked lane costs MORE than a
  pending one, above), and the tail of a chunk's probes runs in the
  1/4 and 1/64 buffers.  Before PR 35 a chunk was 65,536 slots in ONE
  ``probe_insert`` loop, at full width until its last lane resolved:
  17.76 lanes presented a key over the eight doublings of the
  9,445,152-state binding (2^17 -> 2^25 slots, old tables 23-50%
  full), 29.4 of a 52.3 s check; now 2.09, and 4.3 of 28.3 s (PERF.md
  §6 "PR 35", which also has what the ladder alone read, with the
  empty slots dropped by its first hand-over).  It hands back the
  vector ``[failures, keys, lane_rounds lo, hi]``
  (:func:`rhm_logical`), which the engines fetch in the one host sync
  a doubling always had (``grow_rehash_keys``,
  ``grow_rehash_lane_rounds``: docs/observability.md).

Load factor is the caller's contract: engines grow before the table
exceeds 1/2 (`ops/hashtable.py`'s regime), which bounds expected probes
per lane at ~2 and makes stage overflow astronomically unlikely.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from pulsar_tlaplus_tpu.obs import spans
from pulsar_tlaplus_tpu.ops import compact as compact_ops
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL, _fmix

# Width of the zero-sync device metrics vector engines accumulate next
# to the table and ride on their ONE hot-path stats fetch: [flushes,
# probe_rounds, failures, valid_lanes_lo, max_probe_rounds,
# valid_lanes_hi, lane_rounds_lo, lane_rounds_hi].  valid_lanes is the
# candidate count after validity masking (the duplicate-rate
# denominator the host cannot know without a sync); it genuinely
# outgrows int32 — a 1B-state run examines far more than 2.1G
# candidate lanes — so it is carried as hi/lo uint32 WORDS (r12; lo at
# the historical index 3, the hi carry word appended at index 5 so
# every older index keeps its meaning and pre-widening checkpoint
# frames restore zero-padded, the same pattern as the r8/r9
# widenings).  lane_rounds (PR 28, appended at 6/7 the same way) is
# the lanes PRESENTED to the table summed over probe rounds — each
# stage's width times the rounds run at it, the quantity a round's
# gathers and scatters cost by; over valid_lanes it says how far the
# presented width follows the pending count.  :func:`fpm_update` owns
# the device-side carry arithmetic and :func:`fpm_logical` the
# host-side 64-bit reassembly.  max_probe_rounds is the worst flush's
# probe depth (a running max, not a sum).  Shared by device_bfs and
# sharded_device.
FPM_N = 8

# The single-chip engine's vector carries ``FPM_STEPS`` more words
# behind those (PR 37): the probe rounds run at each step of the
# ladder, summed over flushes and indexed by the SCHEDULE's entry
# ([dense, stage 1, stage 2, ...]; a stage that was not built, having
# no shrink to offer, reads 0 and its rounds count where they ran; a
# schedule with more entries sums its tail into the last word).  They
# sum to ``probe_rounds``, and a step no traffic enters shows as a 0
# that can be taken out of the schedule (``fpset_step_rounds``).  A
# vector of ``FPM_N`` words (the sharded engine's, an older checkpoint
# frame's) is updated as it always was.
#
# And one word behind the steps, at ``FPM_WRITE_SAVED`` (PR 42; the
# vector had eight step words and the default schedule's seven entries
# left the last at 0, so the width, and every program that writes all
# its lanes, is what it was): the lanes presented that the table's
# column scatters were NOT handed, a uint32 that wraps
# (``lookup_or_insert``'s ``write_saved``; the host folds its deltas
# at every fetch: ``fpset_write_lanes``, docs/observability.md).
FPM_STEPS = 7
FPM_WRITE_SAVED = FPM_N + FPM_STEPS
FPM_WIDE_N = FPM_WRITE_SAVED + 1

# length of the host-side LOGICAL view: [flushes, probe_rounds,
# failures, valid_lanes (64-bit), max_probe_rounds, lane_rounds
# (64-bit)]
FPM_LOGICAL_N = 6


def add_u32(lo_word, hi_word, n):
    """``n`` (non-negative, < 2^32) added to a hi/lo uint32 counter
    held as int32 bit patterns (bitcast, never a value conversion)."""
    lo = lax.bitcast_convert_type(lo_word, jnp.uint32)
    new_lo = lo + n.astype(jnp.uint32)
    carry = (new_lo < lo).astype(jnp.int32)
    return lax.bitcast_convert_type(new_lo, jnp.int32), hi_word + carry


def u64(lo_word, hi_word):
    """Host-side reassembly of a hi/lo uint32 counter (numpy int64
    scalars holding the fetched int32 bit patterns)."""
    import numpy as np

    return (hi_word << 32) | np.int64(np.uint32(lo_word & 0xFFFFFFFF))


def fpm_update(
    fpm, rounds, n_failed, n_valid, lane_rounds, step_rounds=(),
    write_saved=0,
):
    """One flush's device-side metrics update (jit-traceable).

    ``fpm`` is the int32[FPM_N] vector; ``n_valid`` (int32, < 2^31 per
    flush) and ``lane_rounds`` (uint32) accumulate into their LO words
    with uint32 wraparound and the carry lands in the HI words, so
    1B-state runs report honest duplicate ratios instead of a wrapped
    counter.  ``step_rounds`` (``lookup_or_insert``'s, one int32 a
    schedule entry) lands in the words behind ``FPM_N`` of a vector
    that has them, and ``write_saved`` (its seventh value) in the word
    at ``FPM_WRITE_SAVED``."""
    valid_lo, valid_hi = add_u32(fpm[3], fpm[5], n_valid)
    lanes_lo, lanes_hi = add_u32(fpm[6], fpm[7], lane_rounds)
    words = [
        fpm[0] + 1,
        fpm[1] + rounds,
        fpm[2] + n_failed,
        valid_lo,
        jnp.maximum(fpm[4], rounds),
        valid_hi,
        lanes_lo,
        lanes_hi,
    ]
    n_steps = min(fpm.shape[0] - FPM_N, FPM_STEPS)
    if n_steps > 0:
        steps = list(step_rounds)
        # (one entry there is taken as it is: ``sum`` would add a 0 to
        # it, an equation the vector's programs never had)
        tail = steps[n_steps - 1:]
        steps = steps[: n_steps - 1] + [
            tail[0] if len(tail) == 1 else sum(tail)
        ]
        steps += [0] * (n_steps - len(steps))
        words += [fpm[FPM_N + i] + d for i, d in enumerate(steps)]
    if fpm.shape[0] > FPM_WRITE_SAVED:
        if not isinstance(write_saved, int):
            write_saved = lax.bitcast_convert_type(write_saved, jnp.int32)
        words.append(fpm[FPM_WRITE_SAVED] + write_saved)
    return jnp.stack(words)


def fpm_logical(vec):
    """int64[FPM_LOGICAL_N] logical view of a fetched fpm vector:
    [flushes, probe_rounds, failures, valid_lanes, max_probe_rounds,
    lane_rounds] with the hi/lo words reassembled into 64-bit counts.
    Accepts the historical widths too (3-wide pre-r8, 5-wide r9-r11 and
    6-wide r12 frames restore zero-padded): a missing word reads as 0
    and a 5-wide vector's index-3 int32 reinterprets as the lo uint32
    word — identical for every pre-wrap value."""
    import numpy as np

    a = np.asarray(vec, np.int64).reshape(-1)
    v = np.zeros((FPM_N,), np.int64)
    v[: min(len(a), FPM_N)] = a[:FPM_N]
    return np.array(
        [v[0], v[1], v[2], u64(v[3], v[5]), v[4], u64(v[6], v[7])],
        np.int64,
    )


def fpm_step_rounds(vec, stages):
    """``fpset_step_rounds``: the rounds run at each entry of the
    schedule ``[dense, *stages]``, from a fetched ``FPM_WIDE_N``
    vector."""
    n = min(1 + len(stages), FPM_STEPS)
    return [int(x) for x in vec[FPM_N: FPM_N + n]]

# Width of the zero-sync WORK-UNIT vector (r14, fused-era cost
# attribution): the level megakernel accumulates per-stage work units
# inside its ``lax.while_loop`` body and returns them in the packed
# stats vector, so a single fused run carries enough information to
# attribute per-stage cost WITHOUT the ``-fuse stage`` differential
# rerun the r13 fusion destroyed.  Layout: [expand_rows,
# probe_lanes_lo, compact_elems_lo, append_rows, groups,
# probe_lanes_hi, compact_elems_hi].
#
# - ``expand_rows``: live frontier rows fed through expand windows
#   (masked past-frontier window tails are constant-factor overhead the
#   calibration absorbs); per level this sums to the frontier size, so
#   the run total is bounded by max_states + slack and fits int32.
# - ``probe_lanes``: lanes PRESENTED to the fpset flush — the full
#   accumulator width per flush dispatch, because the dense probe cost
#   is O(nq) per round whether a lane is valid or parked (valid-lane
#   counts live in the fpm vector).  Outgrows int32 on 1B-state runs,
#   so it carries hi/lo uint32 words (the r12 ``fpm_update`` pattern).
# - ``compact_elems``: lanes presented to ``compact.compact_rows``
#   (one row-matrix compaction per flush); hi/lo like probe_lanes.
# - ``append_rows``: deduped new states landed by the append body
#   (bounded by max_states — int32 safe).
# - ``groups``: flush-group while-iterations the megakernel ran (the
#   per-batch iteration count; the stage chain's equivalent is its
#   flush dispatch count).
#
# The counters are defined so the fused totals EQUAL the ``-fuse
# stage`` host dispatch-chain counts exactly (the differential parity
# tests pin it): rows = sum of live window rows, lanes/elems =
# accumulator width x flush count, appends = deduped states.
WKM_N = 7

# host-side LOGICAL view: [expand_rows, probe_lanes (64-bit),
# compact_elems (64-bit), append_rows, groups]
WKM_LOGICAL_N = 5


def wkm_update(wkm, rows, lanes, elems, appended, groups):
    """One flush group's device-side work-unit update (jit-traceable,
    called inside the fused megakernel's while body).  ``lanes`` and
    ``elems`` accumulate into uint32 lo words with the carry landing in
    the hi words (bitcast storage, the :func:`fpm_update` pattern) so
    1B-state runs report honest work totals instead of wrapped ones."""
    lanes_lo, lanes_hi = add_u32(wkm[1], wkm[5], lanes)
    elems_lo, elems_hi = add_u32(wkm[2], wkm[6], elems)
    return jnp.stack(
        [
            wkm[0] + rows,
            lanes_lo,
            elems_lo,
            wkm[3] + appended,
            wkm[4] + groups,
            lanes_hi,
            elems_hi,
        ]
    )


def wkm_logical(vec):
    """int64[WKM_LOGICAL_N] logical view of a fetched work vector:
    [expand_rows, probe_lanes, compact_elems, append_rows, groups]
    with the hi/lo words reassembled into 64-bit counts."""
    import numpy as np

    a = np.asarray(vec, np.int64).reshape(-1)
    v = np.zeros((WKM_N,), np.int64)
    v[: min(len(a), WKM_N)] = a[:WKM_N]
    return np.array(
        [v[0], u64(v[1], v[5]), u64(v[2], v[6]), v[3], v[4]], np.int64
    )


MAX_PROBES = 64
# staged-compaction schedule for the engine hot path: the CEILING on
# full-width rounds, then (shrink divisor, probe-round ceiling) per
# stage.  A stage hands over as soon as what is pending fits the next
# one (PR 28), so the ceilings only matter where that never happens:
# at load <= 1/2 the expected pending fraction at a ceiling is
# ~2^-rounds, well under 1/divisor (see module docstring).  The
# real-chip signal is the zero-sync ``fpset_lane_rounds`` counter over
# ``fpset_valid_lanes`` (lanes presented per valid lane; with
# ``fpset_max_probe_rounds`` and, a step, ``fpset_step_rounds``:
# docs/observability.md); ``scripts/profile.py ladder`` times other
# ladders at one flush shape by handing them to
# :func:`lookup_or_insert` as arguments.
#
# ``STAGES`` narrows by HALVES from 1/4 to 1/64 (PR 37): a step hands
# over once what is pending fits the next, so with halving steps a
# round is presented at most about twice the lanes that were pending
# when its step began.  With the 16x gap of the two-step ladder a
# ``cli check`` flush (65,536 lanes, one in ten valid: about 7,400
# pending) sat in the 16,384-lane buffer for the 2-3 rounds it takes
# to fall under 1,024: 6.10 lanes presented a valid one over the
# 9,445,152-state binding, 3.06 with the halves (counted; PERF.md §6
# "PR 37"; on a v5e a flush of that shape against 2^25 slots is 16.3 ms
# by the two-step ladder, 13.4 with one step added at 1/16 and 8.5 by
# the halves; the compactions as one rolled loop each: 8.7; my chip
# runs, PR 37, ``scripts/profile.py ladder``).  A step with no shrink
# to be had (``MIN_STAGE``) is not built, so a narrow batch pays
# nothing for the steps it cannot use: 1/256 of a ``cli check`` flush
# is under the floor (a true 1/256 step exists from 2^18 lanes up).
# A buffer WIDER than ``QUARTER_ABOVE`` lanes narrows by quarters, not
# halves (the flagship level's 26,738,688 lanes walk 1/4, 1/16, 1/64,
# 1/256): what a step costs the device scales with the batch and what
# it costs the compiler does not (``QUARTER_ABOVE`` below).
#
# ``STAGES_TWO_STEP`` is the ladder of PR 28-36, kept BY VALUE for the
# two callers whose programs are built anew a check or a table size
# and whose batches gain nothing from more steps: the sharded engine's
# flush (``engine/sharded_device.py``: every step is traced and
# lowered again a check there) and :func:`rehash_cols` (its chunks are
# packed to 80% valid before round 0: 2.09 lanes a key).
DENSE_ROUNDS = 4
STAGES = ((4, 16), (8, 24), (16, 32), (32, 40), (64, 48), (256, MAX_PROBES))
STAGES_TWO_STEP = ((4, 16), (64, MAX_PROBES))


# what a probe-overflow abort tells the user to do: under the module's
# ladders an overflow means the table broke its load-factor contract
OVERFLOW_HINT = (
    "raise visited_cap (the table broke its load-factor contract)"
)


def resolve_schedule(
    dense_rounds: Optional[int] = None, stages=None,
) -> Tuple[int, Tuple[Tuple[int, int], ...]]:
    """The effective probe schedule: the caller's own values, and the
    module's defaults for what it left out."""
    if dense_rounds is None:
        dense_rounds = DENSE_ROUNDS
    if stages is None:
        stages = STAGES
    return int(dense_rounds), tuple(tuple(s) for s in stages)


# stage-capacity floor: the 1/div shrink is a concentration argument
# that only holds for large batches (binomial tail at nq/16 expected
# pending vs nq/4 capacity).  Small batches get the full width — for
# nq below the floor the stages run in place, where overflow is
# impossible and compaction would save nothing anyway.
MIN_STAGE = 1 << 10
# a step is built from a buffer wider than this only if it narrows it
# to a QUARTER or less (PR 37).  On the device a step's price and its
# saving both go by the lanes, so the halves pay at any width: on the
# flagship level's one flush of 26,738,688 lanes they read 3.42 s
# against the two-step ladder's 4.17 (my chip runs, PR 37).  But a
# probe loop over millions of lanes is 15-30 s of compiling and 20-40
# MB of code in EVERY program that holds a flush (this module's
# ``lookup_or_insert`` alone, compiled for a v5e: 38 s and 70 MB by the
# two-step ladder, 79 s and 146 MB by the halves, 49 s and 103 MB by
# quarters; at 65,536 lanes 7.9 s whatever the ladder), and the
# flagship cell's compiling process went from 195 s to 335 of the 360
# a run is given; with quarters it is 292 (PERF.md §6 "PR 37").
# Quarters keep the step that matters there: round 1 runs at 1/16 of
# the batch instead of 1/4 (3.54 s a flush, 2,754,800 states a second
# against the two-step ladder's 2,357,800 and the halves' 2,742,900).
QUARTER_ABOVE = 1 << 20

_NO_LANE = jnp.int32(2**31 - 1)  # claims fill: above every real lane id

# A round whose buffer is NARROW against its table: up to
# ``NARROW_MAX_LANES`` lanes on a table of ``NARROW_MIN_SLOTS`` slots or
# more (:func:`narrow_against_table`).  Two things go by that rule,
# both chosen from the round's static shapes alone.
#
# (1) The arbitration (PR 40, :func:`win_among_lanes`): such a round
# picks the winner of a slot among its ``nq`` lanes and builds no
# ``claims`` array, where the ``nq * nq`` pairs are no more than
# ``LANE_ARB_PAIRS_A_SLOT`` a slot of the table — up to 8,192 lanes
# from 2^22 slots, 16,384 from 2^24, nothing wider and nothing on a
# smaller table.
# Measured on a v5e (``scripts/profile.py arbitrate``, my chip runs,
# PR 40), microseconds a round, arbitration alone: the pairwise
# min-reduce 1.5 / 4.4 / 15 / 66 / 262 at 1,024 / 2,048 / 4,096 /
# 8,192 / 16,384 lanes (about 1 ps a pair, whatever the table; as two
# compares and an or-reduce 2.4 / 8.1 / 28 / 102 / 404; one sort by
# slot and id and a scatter back 11 / 28 / 51 / 75 / 122, and 5.7 s to
# compile at 16,384 lanes against 0.8); the ``claims`` array 16 / 30 /
# 59 / 117 / 232 on 2^18 and 2^20 slots (14 ns a lane: it goes by the
# lane scattered too), 90 / 152 / 274 / 520 at 2^24 and 320 / 432 /
# 659 / 1,110 / 2,014 at 2^25 (a refill and a pass of the table, and
# 0.1 us a lane scattered into a table that large).  Inside
# ``probe_insert`` on a table at load 0.3, claims path less lane path
# a round: 1,024 lanes +16 (of 60) at 2^18 slots, +56 at 2^22, +148 at
# 2^24, +333 (of 621) at 2^25; 4,096 lanes +40 (of 227) / +56 / +490 /
# +698; 8,192 lanes -52 at 2^18, +32 at 2^20, +125 at 2^22, +336 at
# 2^24, +1,142 (of 3,514) at 2^25; 16,384 lanes -307 / -138 / -124 /
# +152 / +1,882 (of 6,826) at 2^18 / 2^20 / 2^22 / 2^24 / 2^25, all
# with the two-compare form, the min-reduce a third cheaper: the
# crossover follows ``nq * nq = 16 * cap``.  Under 2^22 slots the
# lanes win 16 to 40 us a round up to 4,096 lanes, on tables whose
# checks are short (a ``cli check`` of 253,361 states runs 316 rounds:
# 5 ms of 420), and XLA:CPU spends 0.7 ns a pair (0.7 ms a round at
# 1,024 lanes where its ``claims`` round is 15 us), which every test
# of tier-1 would pay: the floor keeps small tables, and the programs
# of the two small CLI cells, as they were.
#
# (2) The column write (PR 42, :func:`write_winners`): such a round
# hands the table's K scatters its WINNERS, packed to the front of the
# round's own buffer and written in chunks of ``WRITE_CHUNK`` lanes,
# and not every lane it presents, where it presents fewer than one
# lane to ``WRITE_SLOTS_A_LANE`` slots of the table.  Of the lanes a
# ``cli check`` of 9,445,152 states presents to its narrow rounds 18%
# win (5% at 1,024 lanes, 20-28% on the steps above; counted, PERF.md
# §5).  Measured on a v5e (``scripts/profile.py scatter``, my chip
# runs, PR 42; microseconds a round of two ``u32[cap + 1]`` columns at
# 1,024 / 2,048 / 4,096 / 8,192 / 16,384 lanes).  The full-width
# write, the same at every share of winners: 36 / 46 / 65 / 105 / 184
# at 2^20 slots (60 / 66 at 2^21); 205 / 331 / 162 / 201 / 279 at
# 2^22; - / 405 / 640 / 305 / 380 at 2^23; 216 / 428 / 848 / 1,391 /
# 597 at 2^24; 205 / 405 / 806 / 1,605 / 3,206 at 2^25: a scatter into
# a table of 2^22 slots and over costs 0.1 us a LANE HANDED to it,
# parked or winning, while it is handed fewer than a lane to 1,024
# slots, and 6 to 20 ns a lane from there on (4,096 lanes at 2^22
# slots are cheaper than 2,048); under 2^22 slots it is cheap at any
# width.  The winners' write at 2^25 slots (the same at 2^22 to 2^24
# within 5%): with no winner 23 / 37 / 66 / 123 / 238 (the packing:
# 14-22 ns a lane presented), one lane in twenty winning 47 / 61 / 114
# / 216 / 402, one in four 81 / 143 / 266 / 511 / 999, every lane 210
# / 412 / 816 / 1,621 / 3,234 (0.2 us a lane handed over, both
# columns, and some 1.5 us a trip).  At the shares counted it wins
# wherever the full-width write is in its dear regime (1,024 lanes:
# 47 against 205) and loses wherever that is in its cheap one (8,192
# lanes at 2^23: 507 against 305; 1,024 at 2^21: 74 against 60): the
# rule is the regime's own boundary.  Chunks of 64 / 128 / 256 / 512
# lanes read 36 / 47 / 76 / 126 at 1,024 lanes with one in twenty
# winning and within 4% of each other from 4,096 lanes up, and chunks
# of an eighth of the buffer 595-624 where 128 lanes read 402 at
# 16,384: a wide chunk pays more in the last trip's parked lanes than
# a narrow one in trips, so one width for every round.  The same
# packing with the lane indices scattered and the slots and key words
# gathered through them reads 30 / 62 / 115 / 222 / 436 with no winner
# (a gather is 12 ns a lane more than a scatter there), so the slots
# and the key words are scattered to their ranks themselves; packed
# by one sort on the flag it reads 11 / 12 / 13 / 14 / 20, and 3 s
# more to compile a loop at 16,384 lanes (4.6 against 1.5), which
# every program that holds a flush would pay a tier: not taken
# (PERF.md §7).  The pairs term is the arbitration's own price and
# the write has none.
NARROW_MAX_LANES = 1 << 14
NARROW_MIN_SLOTS = 1 << 22
LANE_ARB_PAIRS_A_SLOT = 16
WRITE_SLOTS_A_LANE = 1 << 10
WRITE_CHUNK = 128


def narrow_against_table(nq: int, cap: int) -> bool:
    """Whether a probe round of ``nq`` lanes is narrow against a table
    of ``cap`` slots: the static shapes alone decide, and the round's
    outputs are the same whatever follows from it."""
    return nq <= NARROW_MAX_LANES and cap >= NARROW_MIN_SLOTS


def arbitrates_among_lanes(nq: int, cap: int) -> bool:
    """Whether a probe round of ``nq`` lanes on a table of ``cap``
    slots arbitrates among its lanes (:func:`win_among_lanes`) and not
    through a ``claims`` array (:func:`win_by_claims`).  The static
    shapes alone decide; the winners are the same either way."""
    return (
        narrow_against_table(nq, cap)
        and nq * nq <= LANE_ARB_PAIRS_A_SLOT * cap
    )


def writes_winners(nq: int, cap: int) -> bool:
    """Whether a probe round of ``nq`` lanes on a table of ``cap``
    slots hands the table's column scatters its winners alone
    (:func:`write_winners`) and not every lane it presents.  The
    static shapes alone decide; the table is the same either way."""
    return narrow_against_table(nq, cap) and nq * WRITE_SLOTS_A_LANE < cap


def write_chunk(nq: int) -> int:
    """Lanes a trip of :func:`write_winners` hands the table for a
    round of ``nq`` lanes."""
    return min(nq, WRITE_CHUNK)


def win_by_claims(bid, s, lane_ids, cap: int):
    """The winner of every empty slot through a ``claims`` array of the
    table's size: refilled, bid into with a scatter-min of the lane
    ids, and read back — bool[nq], True for the bidding lane with the
    least ``lane_ids`` of its slot.  ``s`` is the lane's slot (``cap``
    for a parked lane), ``bid`` the lanes that bid for theirs.  It
    costs by the table (two passes over ``cap + 1`` words a round) and
    by the lane scattered, so it is the wide round's arbitration."""
    bid_slot = jnp.where(bid, s, cap)
    with spans.part("claims_fill"):
        claims = jnp.full((cap + 1,), _NO_LANE, jnp.int32)
    with spans.part("claims_bid"):
        claims = claims.at[bid_slot].min(lane_ids)
        return bid & (claims[s] == lane_ids)


def win_among_lanes(bid, s, lane_ids, cap: int):
    """The same winners found among the ``nq`` lanes themselves: a lane
    wins if the least ``lane_ids`` among the bidders of its slot is its
    own.  One pairwise ``[nq, nq]`` compare, select and min-reduce (the
    compiler fuses it: no ``[nq, nq]`` value exists) and nothing of the
    table's size, so it costs ``nq * nq`` pairs whatever the table."""
    bid_slot = jnp.where(bid, s, cap)  # no bidder shares a parked lane's
    with spans.part("claims_bid"):
        least = jnp.min(
            jnp.where(
                bid_slot[None, :] == bid_slot[:, None],
                lane_ids[None, :], _NO_LANE,
            ),
            axis=1,
        )
        return bid & (least == lane_ids)


def write_winners(tc, oc, win, s, kcols, chunk: int):
    """A narrow round's column write: the lanes of ``win`` write their
    keys ``kcols`` to their slots ``s`` (and 1 to the occupancy column
    ``oc``, where the layout has one), and the table's scatters are
    handed the WINNERS alone.  Their slots and key words are packed to
    the front of ``nq``-lane buffers at their rank (a prefix sum, and a
    scatter a buffer into ``nq + 1`` words, the losers on its own trash
    word), and the table is written in ``ceil(n_win / chunk)`` trips of
    ``chunk`` lanes each; the lanes of a trip past the winners go to
    the table's trash row.  A round with no winner makes no trip, one
    whose every lane wins scatters what the full-width write does, and
    the table's slots under ``cap`` come out the same words either way.

    Returns ``(tc', oc', lanes)``, ``lanes`` (uint32) what the table's
    scatters were handed a column: ``chunk`` times the trips."""
    nq = win.shape[0]
    cap = tc[0].shape[0] - 1
    rank = jnp.cumsum(win.astype(jnp.int32))  # a winner's rank, plus 1
    n_win = rank[nq - 1]
    at = jnp.where(win, rank - 1, nq)
    ws = jnp.full((nq + 1,), cap, jnp.int32).at[at].set(s)[:nq]
    ks = tuple(
        jnp.zeros((nq + 1,), jnp.uint32).at[at].set(k)[:nq] for k in kcols
    )
    trips = (n_win + (chunk - 1)) // chunk

    def trip(i, cols):
        # a last chunk that would pass the buffer's end is clamped back
        # over winners already written: the same words to the same slots
        start = (i * chunk,)
        ws_i = lax.dynamic_slice(ws, start, (chunk,))
        tc, oc = cols
        tc = tuple(
            c.at[ws_i].set(lax.dynamic_slice(k, start, (chunk,)))
            for c, k in zip(tc, ks)
        )
        if oc is not None:
            oc = oc.at[ws_i].set(1)
        return tc, oc

    tc, oc = lax.fori_loop(0, trips, trip, (tuple(tc), oc))
    return tc, oc, (trips * chunk).astype(jnp.uint32)


def slot_hash(kcols: Tuple[jax.Array, ...]) -> jax.Array:
    """Mix K key columns into a table-index basis (u32).  Exact keys
    are raw state words with heavily skewed low bits; the fmix chain
    spreads them (identical to ``hashtable._slot_hash`` for K=3, so the
    shim below stays layout-compatible)."""
    h = _fmix(kcols[0] ^ jnp.uint32(0x9E3779B9))
    for c in kcols[1:]:
        h = _fmix(h ^ c)
    return h


def empty_cols(cap: int, ncols: int) -> Tuple[jax.Array, ...]:
    """K SENTINEL-filled uint32 columns of ``cap + 1`` slots for a
    power-of-two ``cap``.  Slot ``cap`` is the write-only trash row
    that parked lanes scatter into (keeps every scatter dense)."""
    if cap & (cap - 1):
        raise ValueError(f"table capacity must be a power of two: {cap}")
    return tuple(
        jnp.full((cap + 1,), SENTINEL, jnp.uint32) for _ in range(ncols)
    )


def occupied_mask(tcols: Tuple[jax.Array, ...]) -> jax.Array:
    """bool[cap] — occupied (non-all-SENTINEL) slots, trash row
    excluded.  Used by rehash and checkpoint extraction."""
    cap = tcols[0].shape[0] - 1
    e = tcols[0][:cap] == SENTINEL
    for c in tcols[1:]:
        e = e & (c[:cap] == SENTINEL)
    return ~e


def all_sentinel(cols) -> jax.Array:
    e = cols[0] == SENTINEL
    for c in cols[1:]:
        e = e & (c == SENTINEL)
    return e


def probe_insert(
    tcols: Tuple[jax.Array, ...],
    kcols: Tuple[jax.Array, ...],
    valid: jax.Array,
    occ: Optional[jax.Array] = None,
    max_probes: int = MAX_PROBES,
    start_round: int | jax.Array = 0,
    lane_ids: Optional[jax.Array] = None,
    handover: int = 0,
):
    """One batched triangular-probing lookup-or-insert loop.

    Probe round r inspects slot ``(h + r(r+1)/2) & (cap-1)`` (covers
    every slot when cap is a power of two); lanes seeing their key
    resolve as duplicates; lanes seeing an empty slot bid for it with
    their lane id, and the least id of a slot wins it (the unique
    winner writes its key, and same-key losers resolve against the
    freshly written slot).  The winner is found through a ``claims``
    array of the table's size (:func:`win_by_claims`) or, where the
    buffer is narrow against the table, among the lanes themselves
    (:func:`win_among_lanes`): :func:`arbitrates_among_lanes` chooses
    from ``nq`` and ``cap``, and every output is the same either way.
    The winners' keys go to the table by a scatter a column: of every
    lane of the buffer, the others parked on the trash row, or, where
    the buffer is narrow against the table (:func:`writes_winners`, the
    same two shapes), of the winners alone (:func:`write_winners`): the
    table's slots under ``cap`` are the same words either way.

    ``occ`` selects the empty-slot encoding: ``None`` = all-SENTINEL
    key (the engines' layout), else an explicit occupancy column (the
    ``ops.hashtable`` compatibility layout).  ``start_round`` /
    ``lane_ids`` let the staged wrapper resume the probe sequence on a
    compacted buffer while bidding with ORIGINAL lane ids (preserving
    min-lane-wins).
    ``handover`` ends the loop as soon as no more than that many lanes
    are pending (the staged wrapper passes the next, narrower stage's
    capacity; the default 0 probes until every lane resolved).

    Returns ``(is_new, tcols', occ', pending, rounds, write_saved)``;
    ``pending`` lanes are unresolved after ``max_probes`` rounds or
    handed over (callers count the former as hard failures, never
    silent drops).  ``write_saved`` is the lanes presented over the
    loop's rounds LESS the lanes the table's scatters were handed a
    column: uint32 where the winners alone are written, the plain
    integer 0 where every lane is (so that such a loop carries nothing
    for it).
    """
    cap = tcols[0].shape[0] - 1
    nq = kcols[0].shape[0]
    if lane_ids is None:
        lane_ids = jnp.arange(nq, dtype=jnp.int32)
    h = slot_hash(kcols)
    capm = jnp.uint32(cap - 1)
    has_occ = occ is not None
    occ0 = occ if has_occ else jnp.zeros((0,), jnp.int32)
    arbitrate = (
        win_among_lanes if arbitrates_among_lanes(nq, cap)
        else win_by_claims
    )
    chunk = write_chunk(nq) if writes_winners(nq, cap) else 0

    def occupied_at(tc, oc, s, sv):
        if has_occ:
            return oc[s] == 1
        return ~all_sentinel(sv)

    def n_set(flags):
        return jnp.sum(flags.astype(jnp.int32))

    def cond(st):
        r, npend = st[0], st[1]
        return (r < max_probes) & (npend > handover)

    # A round's work is named by part (``spans.part``: docs/
    # observability.md "Parts"), inline, so that a device trace splits
    # the stage's seconds where the remedies differ.  The slot
    # arithmetic, the pending count and the loop's carry stay under no
    # part.
    def body(st):
        r, _, pending, is_new, tc, oc, *saved = st
        ru = r.astype(jnp.uint32)
        off = (ru * (ru + jnp.uint32(1))) >> 1
        slot = ((h + off) & capm).astype(jnp.int32)
        s = jnp.where(pending, slot, cap)  # parked lanes hit the trash row
        with spans.part("gather"):
            sv = tuple(c[s] for c in tc)
            occ_s = occupied_at(tc, oc, s, sv)
            eq = sv[0] == kcols[0]
            for cv, ck in zip(sv[1:], kcols[1:]):
                eq = eq & (cv == ck)
        found = pending & occ_s & eq
        pending = pending & ~found
        # bid for empty slots with the lane id; min wins
        bid = pending & ~occ_s
        win = arbitrate(bid, s, lane_ids, cap)
        if chunk:
            # the packing of the winners is the write's cost
            with spans.part("write"):
                tc, oc_w, handed = write_winners(
                    tc, oc if has_occ else None, win, s, kcols, chunk
                )
                if has_occ:
                    oc = oc_w
                saved = [saved[0] + (jnp.uint32(nq) - handed)]
        else:
            ws = jnp.where(win, s, cap)
            with spans.part("write"):
                tc = tuple(c.at[ws].set(k) for c, k in zip(tc, kcols))
                if has_occ:
                    oc = oc.at[ws].set(1)
        is_new = is_new | win
        pending = pending & ~win
        # same-key losers resolve against the newly written slot
        with spans.part("reread"):
            sv2 = tuple(c[s] for c in tc)
            eq2 = sv2[0] == kcols[0]
            for cv, ck in zip(sv2[1:], kcols[1:]):
                eq2 = eq2 & (cv == ck)
            occ2 = occupied_at(tc, oc, s, sv2)
        pending = pending & ~(occ2 & eq2)
        return (r + 1, n_set(pending), pending, is_new, tc, oc, *saved)

    st = (
        jnp.asarray(start_round, jnp.int32),
        n_set(valid),
        valid,
        jnp.zeros((nq,), jnp.bool_),
        tuple(tcols),
        occ0,
    ) + ((jnp.uint32(0),) if chunk else ())
    r, _, pending, is_new, tcols, occ_out, *saved = lax.while_loop(
        cond, body, st
    )
    return (
        is_new, tcols, (occ_out if has_occ else None), pending, r,
        saved[0] if chunk else 0,
    )


def ladder_steps(nq: int, dense_rounds: int, stages, max_probes=MAX_PROBES):
    """The ladder a batch of ``nq`` lanes walks under a schedule, as
    static ``(width, round ceiling, schedule entry)`` steps.  A stage
    with no shrink to be had (``MIN_STAGE``), or only a half of a
    buffer wider than ``QUARTER_ABOVE``, is not built: it just raises
    the ceiling of the step before it, which probes on in place."""
    ladder = [(nq, min(dense_rounds, max_probes), 0)]
    for j, (div, limit) in enumerate(stages, 1):
        limit = min(limit, max_probes)
        capi = max(nq // div, min(nq, MIN_STAGE))
        width, ceiling, entry = ladder[-1]
        if (
            capi >= width
            or limit <= dense_rounds
            or (width > QUARTER_ABOVE and 4 * capi > width)
        ):
            ladder[-1] = (width, max(ceiling, limit), entry)
        else:
            ladder.append((capi, limit, j))
    return ladder


def lane_arb_rounds(step_rounds, nq: int, cap: int, dense_rounds, stages):
    """Of the probe rounds by the schedule's entry (``fpset_step_rounds``,
    or the difference of two fetches), those run at a step that
    arbitrates among its lanes when the batch is ``nq`` lanes wide and
    the table ``cap`` slots: the host's fold of
    ``fpset_lane_arb_rounds``, from the same static shapes that
    :func:`probe_insert` chose by."""
    words = {
        min(entry, len(step_rounds) - 1)
        for width, _, entry in ladder_steps(nq, dense_rounds, stages)
        if arbitrates_among_lanes(width, cap)
    }
    return sum(int(step_rounds[w]) for w in words)


def lookup_or_insert(
    tcols: Tuple[jax.Array, ...],
    kcols: Tuple[jax.Array, ...],
    valid: jax.Array,
    max_probes: int = MAX_PROBES,
    dense_rounds: Optional[int] = None,
    stages=None,
    materialize: Optional[str] = None,
):
    """Engine hot path: staged batched lookup-or-insert (see module
    docstring for the why of the stages; ``materialize`` is the
    ladder's compactions', ``ops.compact.compact_by_flag``).

    Returns ``(is_new, tcols', n_failed, rounds, lane_rounds,
    step_rounds, write_saved)`` where
    ``is_new`` is in ORIGINAL lane order (exactly one True per distinct
    new key — the minimum valid lane), ``n_failed`` counts lanes
    dropped at a stage overflow or still pending at ``max_probes``
    (callers treat nonzero as a hard error), ``rounds`` is the probe
    rounds consumed (the per-flush probe metric) and ``lane_rounds``
    (uint32) the lanes presented to the table summed over those rounds:
    each stage's width times the rounds run at it; ``step_rounds`` is
    those rounds by the schedule's entry (a tuple ``[dense, *stages]``
    long: int32 scalars, and a plain 0 for a stage that was not built);
    ``write_saved`` is the part of ``lane_rounds`` that the table's
    column scatters were NOT handed, the steps that write their winners
    alone summed (:func:`probe_insert`: uint32, or the plain integer 0
    where no step of the ladder does).
    """
    nq = kcols[0].shape[0]
    K = len(kcols)
    dense_rounds, stages = resolve_schedule(dense_rounds, stages)
    ladder = ladder_steps(nq, dense_rounds, stages, max_probes)
    # the compactions of the batch and of its quarter (all that the
    # two-step ladder has) keep the process's materialization; a
    # narrower buffer's shift passes run as ONE loop.  A ``ptt_level2``
    # is traced, lowered and loaded anew for every table size, so the
    # halving steps' passes unrolled were paid on the host a tier (948
    # more equations a flush; a fresh process's first check of the 9m
    # binding 58 s against 50), and on a narrow buffer the loop's copy
    # more a pass costs the device next to nothing (PERF.md §6 "PR 37")
    mat = materialize or compact_ops.materialization()
    mat_narrow = "roll" if mat == "shift" else mat
    is_new = jnp.zeros((nq,), jnp.bool_)
    n_failed = jnp.int32(0)
    lane_rounds = jnp.uint32(0)
    step_rounds = [0] * (1 + len(stages))
    write_saved = 0
    r = jnp.int32(0)
    cur_keys, cur_ids, cur_pending, width = kcols, None, valid, nq
    for i, (capi, limit, entry) in enumerate(ladder):
        if capi < width:
            # order-preserving compaction of the pending lanes (+ their
            # original lane ids) into the narrower stage buffer
            ids = (
                cur_ids
                if cur_ids is not None
                else jnp.arange(nq, dtype=jnp.int32)
            )
            with spans.part("narrow"):
                drop = (~cur_pending).astype(jnp.uint32)
                ccols, _ = compact_ops.compact_by_flag(
                    drop, tuple(cur_keys) + (ids.astype(jnp.uint32),),
                    need_idx=False,
                    materialize=mat if 4 * width >= nq else mat_narrow,
                )
            npend = jnp.sum(cur_pending.astype(jnp.int32))
            n_failed = n_failed + jnp.maximum(npend - capi, 0)
            with spans.part("narrow"):
                cur_keys = tuple(c[:capi] for c in ccols[:K])
                cur_ids = ccols[K][:capi].astype(jnp.int32)
            cur_pending = jnp.arange(capi, dtype=jnp.int32) < npend
            width = capi
        # a step ends as soon as what is pending fits the next one: a
        # round costs by the lane presented, parked or not, so the
        # ``limit`` is a ceiling and the pending count sets the width
        fits = ladder[i + 1][0] if i + 1 < len(ladder) else 0
        stage_new, tcols, _, cur_pending, r2, saved = probe_insert(
            tcols, cur_keys, cur_pending, max_probes=limit,
            start_round=r, lane_ids=cur_ids, handover=fits,
        )
        write_saved = write_saved + saved
        with spans.part("narrow"):
            is_new = _merge_new(is_new, stage_new, cur_ids, nq)
        step_rounds[entry] = r2 - r
        lane_rounds = lane_rounds + jnp.uint32(width) * (
            step_rounds[entry]
        ).astype(jnp.uint32)
        r = r2
    n_failed = n_failed + jnp.sum(cur_pending.astype(jnp.int32))
    return (
        is_new, tcols, n_failed, r, lane_rounds, tuple(step_rounds),
        write_saved,
    )


def _merge_new(is_new, stage_new, stage_ids, nq):
    """Scatter a stage's winner flags back to original lane order
    (only True flags are written — resolved lanes keep their bits)."""
    if stage_ids is None:
        return is_new | stage_new
    tgt = jnp.where(stage_new, stage_ids, nq)
    return is_new.at[tgt].set(True, mode="drop")


def flush_acc(
    tcols: Tuple[jax.Array, ...],
    kcols: Tuple[jax.Array, ...],
    n_acc,
    fpm: jax.Array,
    dense_rounds: Optional[int] = None,
    stages=None,
    materialize: Optional[str] = None,
):
    """One accumulator flush as a traced sub-function (round 13): mask
    the live prefix, probe-or-insert, count the new states, and ride
    the metrics vector — ``(tcols', n_new, flag_acc, fpm')`` with
    ``flag_acc`` the uint32 new-state flags in ORIGINAL lane order.

    This is the body the device engine's ``_fpflush_jit`` always ran;
    factoring it here lets the fused level megakernel chain it inside
    one dispatch while the per-stage jit keeps calling the identical
    trace — bit-for-bit the same flush either way.  Lanes past
    ``n_acc`` (a stale tail from a previous fill) and all-SENTINEL
    lanes (masked expand output) are invalid; min-lane-wins fixes the
    discovery order (it depends only on pre-flush membership and the
    lane, never on slot placement).
    """
    nq = kcols[0].shape[0]
    lanei = jnp.arange(nq, dtype=jnp.int32)
    amask = lanei < n_acc
    valid = amask & ~all_sentinel(kcols)
    is_new, tcols2, n_failed, rounds, lane_rounds, step_rounds, saved = (
        lookup_or_insert(
            tcols, kcols, valid, dense_rounds=dense_rounds,
            stages=stages, materialize=materialize,
        )
    )
    n_new = jnp.sum(is_new.astype(jnp.int32))
    fpm2 = fpm_update(
        fpm, rounds, n_failed, jnp.sum(valid.astype(jnp.int32)),
        lane_rounds, step_rounds, saved,
    )
    return tcols2, n_new, is_new.astype(jnp.uint32), fpm2


def lookup(
    tcols: Tuple[jax.Array, ...],
    kcols: Tuple[jax.Array, ...],
    valid: jax.Array,
    max_probes: int = MAX_PROBES,
):
    """Read-only membership probe: bool[nq] (True = key present).
    Lanes resolve on their key (member) or the first empty slot in
    their probe sequence (non-member)."""
    cap = tcols[0].shape[0] - 1
    nq = kcols[0].shape[0]
    h = slot_hash(kcols)
    capm = jnp.uint32(cap - 1)

    def cond(st):
        r, pending = st[0], st[1]
        return (r < max_probes) & jnp.any(pending)

    def body(st):
        r, pending, member = st
        ru = r.astype(jnp.uint32)
        off = (ru * (ru + jnp.uint32(1))) >> 1
        s = jnp.where(
            pending, ((h + off) & capm).astype(jnp.int32), cap
        )
        sv = tuple(c[s] for c in tcols)
        empty = all_sentinel(sv)
        eq = sv[0] == kcols[0]
        for cv, ck in zip(sv[1:], kcols[1:]):
            eq = eq & (cv == ck)
        member = member | (pending & ~empty & eq)
        pending = pending & ~empty & ~eq
        return (r + 1, pending, member)

    _, _, member = lax.while_loop(
        cond, body, (jnp.int32(0), valid, jnp.zeros((nq,), jnp.bool_))
    )
    return member


# Slots of the old table a rehash step takes (:func:`rehash_cols`): wide
# enough for the ladder to narrow and for a round's ``claims`` fill of
# the NEW table to be paid tens of times a doubling, bounded so that the
# transient stays old + new table + a few columns of one chunk (a rehash
# runs when memory is shortest).  Not wider: a program's code goes by
# the widths of its probe loops, the chip keeps code in HBM, and a first
# process compiles a program for every tier (PERF.md §6 "PR 35").
REHASH_CHUNK = 1 << 18

# A chunk's occupied slots are packed into a buffer of this share of
# its width before round 0.  The callers' contract is load <= 1/2, so a
# chunk holds chunk/2 keys give or take a few sqrt(chunk): the eighth
# above it is 11 standard deviations at the narrowest chunk that is
# packed (2^11 slots) and over 100 at ``REHASH_CHUNK``.  A key that
# does not fit is counted as a failure, as a stage overflow is.
REHASH_PACK_NUM, REHASH_PACK_DEN = 5, 8

# Width of the vector a rehash hands back: [failures, keys,
# lane_rounds_lo, lane_rounds_hi] — the lanes presented to the new
# table summed over probe rounds as hi/lo uint32 words (the
# :func:`fpm_update` pattern), so that ONE fetch reads the fail-stop
# count and the counters.  :func:`rhm_logical` is the host's view.
RHM_N = 4


def rhm_logical(vec) -> Tuple[int, int, int]:
    """``(failures, keys, lane_rounds)`` as Python integers from
    fetched rehash vectors (``[RHM_N]``, or ``[shards, RHM_N]``, summed)."""
    import numpy as np

    v = np.asarray(vec, np.int64).reshape(-1, RHM_N)
    lane_rounds = sum(int(u64(lo, hi)) for lo, hi in v[:, 2:])
    return int(v[:, 0].sum()), int(v[:, 1].sum()), lane_rounds


def rehash_cols(
    old_cols: Tuple[jax.Array, ...],
    new_cols: Tuple[jax.Array, ...],
    chunk: int = REHASH_CHUNK,
    max_probes: int = MAX_PROBES,
    materialize: Optional[str] = None,
):
    """Re-insert every occupied slot of ``old_cols`` into ``new_cols``
    (larger, or as large and empty) — fully on device, so it is usable
    inside jit and shard_map bodies alike.  A `fori_loop` over chunks
    of the old table: a chunk's occupied slots are packed to the front
    (``REHASH_PACK_NUM / REHASH_PACK_DEN`` of its width; a chunk no
    wider than ``MIN_STAGE`` goes as it is) and go through
    :func:`lookup_or_insert`'s two-step ladder (``DENSE_ROUNDS`` /
    ``STAGES_TWO_STEP``), so that every round presents the
    lanes still pending and little else (module docstring, "On-device
    growth").

    Returns ``(new_cols, rhm)``, ``rhm`` the int32[RHM_N] vector of
    :func:`rhm_logical`: failures, the old table's keys, and the lanes
    presented for them.  The keys are distinct by construction and the
    new table's load is <= 1/2 (<= 1/4 after a doubling), so a nonzero
    failure count means the caller's capacity contract was broken
    (fail-stop upstream, like every other capacity violation here).

    The body is a module-level ``jax.jit`` of its own, keyed on the
    shapes and the three static values: a program that is traced again
    for every checker (the sharded engine's, nine tiers a check) finds
    the ladder's equations from the process's first trace.
    """
    materialize = materialize or compact_ops.materialization()
    return _rehash_cols(
        tuple(old_cols), tuple(new_cols), chunk=chunk,
        max_probes=max_probes,
        # a rehash program is built anew for every table size, so its
        # shift passes are one loop each, not log2(chunk) copies
        materialize="roll" if materialize == "shift" else materialize,
    )


@functools.partial(
    jax.jit, static_argnames=("chunk", "max_probes", "materialize")
)
def _rehash_cols(old_cols, new_cols, *, chunk, max_probes, materialize):
    ocap = old_cols[0].shape[0] - 1
    chunk = min(chunk, ocap)
    if ocap % chunk:
        raise ValueError("rehash chunk must divide the old capacity")
    width = max(
        chunk * REHASH_PACK_NUM // REHASH_PACK_DEN, min(chunk, MIN_STAGE)
    )

    def body(i, carry):
        new, rhm = carry
        ks = tuple(
            lax.dynamic_slice(c, (i * chunk,), (chunk,))
            for c in old_cols
        )
        occm = ~all_sentinel(ks)
        n_occ = jnp.sum(occm.astype(jnp.int32))
        if width < chunk:
            packed, _ = compact_ops.compact_by_flag(
                (~occm).astype(jnp.uint32), ks, need_idx=False,
                materialize=materialize,
            )
            ks = tuple(c[:width] for c in packed)
            occm = jnp.arange(width, dtype=jnp.int32) < n_occ
        _new_flags, new, n_failed, _r, lane_rounds, _, _ = lookup_or_insert(
            new, ks, occm, max_probes=max_probes,
            dense_rounds=DENSE_ROUNDS, stages=STAGES_TWO_STEP,
            materialize=materialize,
        )
        lanes_lo, lanes_hi = add_u32(rhm[2], rhm[3], lane_rounds)
        rhm = jnp.stack(
            [
                rhm[0] + n_failed + jnp.maximum(n_occ - width, 0),
                rhm[1] + n_occ,
                lanes_lo,
                lanes_hi,
            ]
        )
        return new, rhm

    return lax.fori_loop(
        0, ocap // chunk, body,
        (tuple(new_cols), jnp.zeros((RHM_N,), jnp.int32)),
    )


class FPSet:
    """Host-side convenience wrapper (tests, probes, host-loop engines):
    owns the column tuple, the entry count, growth, and cumulative
    probe/occupancy/failure metrics.  The device engines inline the
    functional core above in their own jitted programs instead."""

    def __init__(
        self,
        ncols: int,
        cap: int = 1 << 10,
        telemetry=None,
        dense_rounds: Optional[int] = None,
        stages=None,
    ):
        from pulsar_tlaplus_tpu.obs import telemetry as obs

        self.cols = empty_cols(cap, ncols)
        self.ncols = ncols
        self.n = 0
        self.dense_rounds, self.stages = resolve_schedule(
            dense_rounds, stages
        )
        self.stats = {
            "inserts": 0, "probe_rounds": 0, "lane_rounds": 0,
            "failures": 0,
        }
        # optional JSONL stream (obs.telemetry): one ``fpset_insert``
        # record per batched insert — host-loop users get the same
        # per-flush visibility the device engines emit
        self.tel = obs.as_telemetry(telemetry)
        self._tel_owned = obs.owns_stream(telemetry)

    def close(self) -> None:
        """Close a telemetry stream this FPSet opened (a caller-passed
        Telemetry instance stays the caller's to close)."""
        if self._tel_owned:
            self.tel.close()

    @property
    def cap(self) -> int:
        return self.cols[0].shape[0] - 1

    @property
    def occupancy(self) -> float:
        return self.n / self.cap

    def reserve(self, n_entries: int):
        """Grow (double + on-device rehash) until ``n_entries`` fit at
        load factor <= 1/2."""
        while 2 * n_entries > self.cap:
            new = empty_cols(self.cap * 2, self.ncols)
            self.cols, rhm = rehash_cols(self.cols, new)
            if rhm_logical(rhm)[0]:
                raise RuntimeError("fpset rehash overflow")
        return self

    def insert(self, kcols, valid=None):
        """Batched insert; returns the is_new bool vector (lane order).
        Grows first so the load-factor contract always holds."""
        kcols = tuple(jnp.asarray(c, jnp.uint32) for c in kcols)
        nq = kcols[0].shape[0]
        if valid is None:
            valid = jnp.ones((nq,), jnp.bool_)
        self.reserve(self.n + nq)
        is_new, self.cols, n_failed, rounds, lane_rounds, _, _ = (
            lookup_or_insert(
                self.cols, kcols, valid,
                dense_rounds=self.dense_rounds, stages=self.stages,
            )
        )
        nf = int(n_failed)
        from pulsar_tlaplus_tpu.utils import faults

        if "fpset_fail" in faults.poll(
            "flush", self.stats["inserts"] + 1
        ):
            # injected stage overflow (PTT_FAULT=fpset_fail@flush:N):
            # exercises the fail-stop contract below without needing a
            # genuinely overloaded table
            nf += 1
        self.n += int(jnp.sum(is_new.astype(jnp.int32)))
        self.stats["inserts"] += 1
        self.stats["probe_rounds"] += int(rounds)
        self.stats["lane_rounds"] += int(lane_rounds)
        self.stats["failures"] += nf
        self.tel.emit(
            "fpset_insert",
            inserts=self.stats["inserts"],
            probe_rounds=int(rounds),
            failures=nf,
            n=self.n,
            occupancy=round(self.occupancy, 4),
        )
        if nf:
            raise RuntimeError(
                f"fpset probe overflow ({nf} lanes unresolved) — "
                "grow the table before exceeding load factor 1/2"
            )
        return is_new

    def contains(self, kcols, valid=None):
        kcols = tuple(jnp.asarray(c, jnp.uint32) for c in kcols)
        if valid is None:
            valid = jnp.ones((kcols[0].shape[0],), jnp.bool_)
        return lookup(self.cols, kcols, valid)
