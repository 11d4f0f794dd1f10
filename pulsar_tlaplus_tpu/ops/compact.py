"""Sort-free stream compaction — the log-shift pass on every hot path.

Every engine hot path ends with the same primitive: "move the value
columns whose ``drop`` flag is 0 to the front, preserving original
order" — the append (device + sharded), the fpset's staged
pending-compaction, and the liveness sweep's edge compaction.

It is prefix-sum stream compaction: one exclusive prefix sum of the
drop flags gives every kept element its destination, and a sort-free
materialization moves the columns (a sort's run cost is width-linear
data movement across O(log^2 n) comparator stages).  The materialization is
picked for the backend's memory system at trace time:

- **Accelerators (the TPU hot path): masked doubling shifts** — the
  scan-then-shift frontier compaction of tensor-core BFS frameworks
  (BLEST, arXiv:2512.21967).  ``log2(n)`` passes; bit b of an
  element's remaining shift distance decides whether it rides the
  ``2^b`` shift.  Every pass is a contiguous copy + elementwise select
  per column — the cheapest ops on the TPU memory system (9-30 ns/elem
  contiguous vs 17-50 ns/elem latency-bound random access, BASELINE.md
  environment facts) — and there is no comparator network, so the
  compile is trivial.
- **The CPU backend (the virtual-mesh test/differential tier):
  prefix-sum + branchless-binary-search gather** — XLA:CPU lowers
  sorts AND scatters to serial per-element loops (measured here:
  ~140 ns/elem scatter, ~480 ns/elem 3-operand sort) while its gathers
  vectorize at ~2 ns/elem, so the shift passes' 10-19 full-array
  sweeps lose to one ``log2(n)``-round branchless binary search over
  the inclusive kept-count (the ``dedup.bsearch_member`` idiom) + one
  gather per column.  Same outputs element-for-element (the CPU
  profile is flat — there is no contiguous-vs-random asymmetry to
  exploit).

``PTT_COMPACT_MATERIALIZE=shift|gather`` overrides the choice, so a CPU
test can run the materialization the TPU picks.

A caller whose program is built anew for every table size (the table
rehash, ``ops/fpset.py: rehash_cols``) passes ``materialize="roll"``:
the shift passes as ONE loop over the bit, the same values pass for
pass.  A program's host cost (trace, lowering, compile) goes by its
equations, and three compactions of 16 to 18 unrolled passes were
2,700 of a rehash program's 3,700; rolled they are a copy more a pass
on the device, which a rehash does not notice and the level kernel,
whose compactions stay unrolled, was not asked to pay (PERF.md §6
"PR 35").

Correctness sketch for the shift passes (the property test hammers
both materializations with random masks): ``delta`` (dropped elements
before position i) is monotone non-decreasing and increases by at most
1 per position, so among KEPT elements the partial positions
``i - (delta_i mod 2^b)`` are strictly increasing before every pass —
two kept elements can never collide, and the element destined for slot
j lands there on its final moving pass and never moves again.  Dropped
elements never move (their remaining distance starts at 0) and slots
vacated without replacement have their distance zeroed, so stale
copies never travel; both are eventually overwritten inside the kept
prefix and are DON'T-CARE beyond it (callers consume only the
``n_kept`` prefix; the tests pin the prefix element-for-element against
a numpy reference).
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def materialization() -> str:
    """The materialization this process compacts with (see module
    docstring): the default of the compactions below, read at trace
    time.  A program that outlives its trace (``engine/bodies.py``)
    reads it when it is dispatched and passes it as a static argument
    instead, so that the environment is part of the program's key."""
    env = os.environ.get("PTT_COMPACT_MATERIALIZE")
    if env in ("shift", "gather"):
        return env
    if env:
        raise ValueError(
            f"PTT_COMPACT_MATERIALIZE must be shift|gather: {env!r}"
        )
    return "gather" if jax.default_backend() == "cpu" else "shift"


def _shifted(x: jax.Array, d) -> jax.Array:
    """``x`` shifted left by ``d``: out[i] = x[i + d], zero-padded
    (``d`` a Python int, or traced: one dynamic slice of the padded
    column)."""
    if isinstance(d, int):
        return jnp.concatenate([x[d:], jnp.zeros((d,), x.dtype)])
    return lax.dynamic_slice(
        jnp.concatenate([x, jnp.zeros_like(x)]), (d,), x.shape
    )


def _shift_compact(drop, vals, rolled=False):
    """Masked doubling-shift materialization (the TPU path): move every
    kept element left by its drop-prefix-sum distance, one bit of the
    distance per pass — contiguous copies and selects only.  ``rolled``
    runs the passes as ONE loop over the bit (the same values, pass for
    pass): ``log2(n)`` times fewer equations to trace, lower and
    compile, and as much less code, for a copy more a pass."""
    n = drop.shape[0]
    keep = drop == 0
    # delta[i] = dropped elements strictly before i = how far a kept
    # element at i must move left; exclusive prefix sum of the flags
    drop_u = drop.astype(jnp.uint32)
    delta = jnp.cumsum(drop_u) - drop_u
    # remaining shift distance, travelling WITH each element.  Dropped
    # elements get 0 so they never ride a shift (a dropped element
    # pulled over a kept one was the classic corruption mode).
    rem = jnp.where(keep, delta, jnp.uint32(0))

    def one_pass(rem, vals, d):
        du = jnp.uint32(d)
        rem_s = _shifted(rem, d)
        # pull from i+d when THAT element's remaining distance has this
        # bit set; stale/dropped slots have rem 0 and are never pulled
        take = (rem_s & du) != 0
        vals = [jnp.where(take, _shifted(v, d), v) for v in vals]
        # a slot whose occupant left with nothing arriving holds a
        # stale copy: zero its distance so it can never move again
        rem_keep = jnp.where((rem & du) != 0, jnp.uint32(0), rem)
        return jnp.where(take, rem_s - du, rem_keep), vals

    if rolled:
        rem, vals = lax.fori_loop(
            0, (n - 1).bit_length(),
            lambda b, c: one_pass(c[0], c[1], jnp.int32(1) << b),
            (rem, list(vals)),
        )
        return vals
    d = 1
    while d < n:
        rem, vals = one_pass(rem, vals, d)
        d <<= 1
    return vals


def _gather_compact(drop, vals):
    """Prefix-sum + branchless-binary-search gather materialization
    (the CPU path): ``src[j]`` = the j-th kept original index, found by
    an unrolled binary search over the inclusive kept-count vector
    (``dedup.bsearch_member``'s idiom), then one vectorized gather per
    column.  Positions past the kept count gather garbage — the shared
    tail contract."""
    n = drop.shape[0]
    kc = jnp.cumsum((drop == 0).astype(jnp.int32))
    tgt = jnp.arange(1, n + 1, dtype=jnp.int32)
    lo = jnp.zeros((n,), jnp.int32)
    hi = jnp.full((n,), n, jnp.int32)
    for _ in range(max(1, n.bit_length())):
        mid = (lo + hi) >> 1
        less = kc[mid] < tgt
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    src = jnp.clip(lo, 0, n - 1)
    return [v[src] for v in vals], src


def compact_by_flag(
    drop: jax.Array, cols, need_idx: bool = True,
    materialize: Optional[str] = None,
) -> Tuple[tuple, Optional[jax.Array]]:
    """Sort-free stable compaction of ``cols`` to the front where
    ``drop == 0`` (module docstring; ``materialize`` is ``"shift"``,
    ``"gather"`` or ``"roll"``, by default the process's,
    :func:`materialization`).

    The kept prefix is in original order; positions past the kept
    count are don't-care.
    ``idx[j]`` is the original row of compacted position ``j`` (valid
    in the kept prefix); pass ``need_idx=False`` to skip carrying the
    index column when the caller discards it.
    """
    n = drop.shape[0]
    vals = list(cols)
    if (materialize or materialization()) == "gather":
        # the search's src vector IS the original-index map — idx
        # rides for free, no extra column travels
        out, src = _gather_compact(drop, vals)
        return tuple(out), (src if need_idx else None)
    if need_idx:
        vals.append(jnp.arange(n, dtype=jnp.uint32))
    out = _shift_compact(drop, vals, rolled=materialize == "roll")
    idx = None
    if need_idx:
        idx = out[-1].astype(jnp.int32)
        out = out[:-1]
    return tuple(out), idx


def compact_rows(
    arows: jax.Array, flag_keep: jax.Array,
    materialize: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Compact a word-major ``[W, N]`` packed-row matrix to the front
    where ``flag_keep`` (uint32 0/1) is set, preserving original order
    — the device append's stream-compaction step, shared as a traced
    sub-function by the per-stage ``_compact_jit`` and the fused level
    megakernel (round 13).  Returns ``(compacted [W, N], idx)`` where
    ``idx[j]`` is the original lane of compacted position ``j``."""
    drop = flag_keep ^ jnp.uint32(1)
    cols = tuple(arows[j] for j in range(arows.shape[0]))
    ccols, idx = compact_by_flag(drop, cols, materialize=materialize)
    return jnp.stack(ccols), idx
