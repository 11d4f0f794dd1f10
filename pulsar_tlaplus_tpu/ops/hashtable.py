"""Open-addressing visited-set hash table in HBM (SURVEY.md §2.2-E3,
§7-L3) — now a thin compatibility layer over :mod:`.fpset`.

Round 6 promoted this design to the device hot path as the growable,
K-column, staged-compaction FPSet in ``ops/fpset.py`` (see its module
docstring for the probing/bidding algorithm and the discovery-order
guarantee).  The host-loop engines
(``engine/core.py``, ``engine/bfs.py``, ``engine/sharded.py``) keep
this module's original fixed 3-column + occupancy-column API on
``fpset.probe_insert``'s triangular probing and min-lane bidding.

Layout: four uint32[cap + 1] columns — three key words plus an
occupancy column.  ``cap`` is a power of two; slot ``cap`` is the
write-only trash row that parked lanes scatter into.  Batched
lookup-or-insert resolves races entirely on device; lanes still pending
after ``max_probes`` rounds are reported in the returned failure count
(callers treat nonzero as a hard error, never a silent drop).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from pulsar_tlaplus_tpu.ops import fpset

MAX_PROBES = fpset.MAX_PROBES


def empty_table(cap: int) -> Tuple[jax.Array, ...]:
    """(t1, t2, t3, occ) columns for a power-of-two ``cap``."""
    if cap & (cap - 1):
        raise ValueError(f"table capacity must be a power of two: {cap}")
    z = jnp.zeros((cap + 1,), jnp.uint32)
    return z, z, z, jnp.zeros((cap + 1,), jnp.int32)


def _slot_hash(k1: jax.Array, k2: jax.Array, k3: jax.Array) -> jax.Array:
    """Mix the three key words into a table index basis (u32)."""
    return fpset.slot_hash((k1, k2, k3))


def lookup_insert(
    t1: jax.Array,
    t2: jax.Array,
    t3: jax.Array,
    occ: jax.Array,
    k1: jax.Array,
    k2: jax.Array,
    k3: jax.Array,
    valid: jax.Array,
    max_probes: int = MAX_PROBES,
):
    """Batched lookup-or-insert of keys into the table.

    Returns ``(is_new, t1', t2', t3', occ', n_failed)`` where ``is_new[i]``
    is True iff lane i's key was absent and this call inserted it (exactly
    one lane wins per distinct new key — the minimum lane id), and
    ``n_failed`` counts lanes still unresolved after ``max_probes`` rounds
    (callers must treat nonzero as an error — see module docstring).
    """
    is_new, (t1, t2, t3), occ, pending, _rounds, _ = fpset.probe_insert(
        (t1, t2, t3), (k1, k2, k3), valid, occ=occ,
        max_probes=max_probes,
    )
    return is_new, t1, t2, t3, occ, jnp.sum(pending.astype(jnp.int32))


_REHASH_STEP = jax.jit(lookup_insert)


def rehash_into(
    old: Tuple[jax.Array, ...],
    new: Tuple[jax.Array, ...],
    chunk: int = 1 << 16,
):
    """Move every occupied entry of ``old`` into the (larger) ``new``
    table.  Host-driven chunked loop; returns the new columns.

    Used when the engine grows the table past load factor 1/2 — the
    hash-table analog of the sorted path's pad-and-carry growth.
    """
    t1, t2, t3, occ = old
    n1, n2, n3, nocc = new
    cap = t1.shape[0] - 1
    step = _REHASH_STEP
    for start in range(0, cap, chunk):
        sl = slice(start, min(start + chunk, cap))
        is_new, n1, n2, n3, nocc, failed = step(
            n1, n2, n3, nocc,
            t1[sl], t2[sl], t3[sl],
            occ[sl] == 1,
        )
        if int(failed):
            raise RuntimeError(
                "hash table rehash overflow — raise visited capacity"
            )
        del is_new
    return n1, n2, n3, nocc
