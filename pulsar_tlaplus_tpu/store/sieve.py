"""Device-side sieve ops for the tiered store (traced sub-functions).

The sieve principle (arXiv:1208.5542): keys already confirmed visited
must never cross the slow link.  Three device-side ops enforce it —
the engine jits them per capacity tier (the same ``_jits`` cache
discipline as every other tier-keyed program):

- :func:`tag_generation` stamps newly-inserted fpset slots with the
  current eviction epoch at level boundaries, so age is a per-slot
  observable without touching the insert hot path (the megakernel's
  probe loop is unchanged — tagging is one masked ``where`` over the
  table per boundary).
- :func:`extract_cold` selects slots at or below a cutoff epoch,
  compacts their keys densely, SORTS them (so the host-side cold run
  is searchable and delta-compressible without a host sort), and
  clears the slots.  The caller must rehash the survivors afterwards
  (open-addressing probe chains break across holes — device_bfs owns
  that step), and the freshly rebuilt table restarts at epoch 1.
- :func:`sieve_new` packs exactly the lanes the hot filter flagged new
  — the only keys that ever cross to the host for cold-tier miss
  resolution — and :func:`unflag_lanes` merges the resolved verdicts
  back by clearing the false-new lanes BEFORE the compaction/append
  that assigns gids, which is what keeps tiered discovery order
  state-for-state identical to the untiered run.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from pulsar_tlaplus_tpu.ops import compact as compact_ops
from pulsar_tlaplus_tpu.ops.dedup import SENTINEL
from pulsar_tlaplus_tpu.ops.fpset import all_sentinel

_BIG_LANE = jnp.int32(2**31 - 1)


def _occupied_full(tcols) -> jax.Array:
    """bool[cap + 1] occupancy with the trash row forced empty."""
    cap = tcols[0].shape[0] - 1
    occ = ~all_sentinel(tcols)
    lane = jnp.arange(cap + 1, dtype=jnp.int32)
    return occ & (lane < cap)


def tag_generation(tcols, gen: jax.Array, epoch) -> jax.Array:
    """Stamp occupied-but-untagged slots with ``epoch`` (int32).  The
    generation column is 0 for empty/untagged slots, so calling this
    once per level boundary gives every key the epoch of the first
    boundary after its insertion — the age signal eviction sorts by."""
    occ = _occupied_full(tcols)
    fresh = occ & (gen == 0)
    return jnp.where(fresh, jnp.int32(epoch), gen)


def extract_cold(
    tcols: Tuple[jax.Array, ...],
    gen: jax.Array,
    cutoff,
):
    """Select slots with ``1 <= gen <= cutoff``, pack their keys
    densely, sort them, and clear the slots.

    Returns ``(tcols_holed, gen_cleared, ev_cols_sorted, n_evicted)``
    — ``ev_cols_sorted`` are full-table-width columns whose first
    ``n_evicted`` lanes hold the evicted keys in unsigned
    lexicographic column order (SENTINEL padding sorts last).  The
    holed table MUST be rehashed before serving lookups again."""
    cap1 = tcols[0].shape[0]
    occ = _occupied_full(tcols)
    cold = occ & (gen >= 1) & (gen <= jnp.int32(cutoff))
    n_ev = jnp.sum(cold.astype(jnp.int32))
    drop = (~cold).astype(jnp.uint32)
    packed, _ = compact_ops.compact_by_flag(
        drop, tuple(tcols), need_idx=False
    )
    lane = jnp.arange(cap1, dtype=jnp.int32)
    masked = tuple(
        jnp.where(lane < n_ev, c, SENTINEL) for c in packed
    )
    ev_sorted = lax.sort(masked, num_keys=len(masked), is_stable=False)
    tcols_holed = tuple(
        jnp.where(cold, SENTINEL, c) for c in tcols
    )
    gen_cleared = jnp.where(cold, jnp.int32(0), gen)
    return tcols_holed, gen_cleared, ev_sorted, n_ev


def sieve_new(ak_cols, flag_acc):
    """Pack the hot-filter survivors: the accumulator lanes flagged
    new, as dense key columns + their ORIGINAL lane ids.  Returns
    ``(kcols..., lane_ids, n_new)`` — only the ``n_new`` prefix is
    meaningful; these are the only keys that cross the link."""
    nq = ak_cols[0].shape[0]
    lane = jnp.arange(nq, dtype=jnp.uint32)
    drop = flag_acc ^ jnp.uint32(1)
    packed, _ = compact_ops.compact_by_flag(
        drop, tuple(ak_cols) + (lane,), need_idx=False
    )
    n_new = jnp.sum(flag_acc.astype(jnp.int32))
    return (*packed[:-1], packed[-1].astype(jnp.int32), n_new)


def unflag_lanes(flag_acc, lanes, n):
    """Clear ``lanes[:n]`` in the new-state flag vector — the miss
    verdict merge: lanes the cold tiers resolved as already-visited
    stop being new BEFORE the compaction that assigns gids, so tiered
    gid assignment is identical to the untiered run's."""
    p = lanes.shape[0]
    idx = jnp.where(
        jnp.arange(p, dtype=jnp.int32) < n, lanes, _BIG_LANE
    )
    return flag_acc.at[idx].set(jnp.uint32(0), mode="drop")
