"""Fingerprint keys, sorting, and visited-set membership — the TPU-native
equivalent of TLC's ``FPSet`` (SURVEY.md §2.2-E3).

Design: a state's dedup key is 3 x uint32 (96 bits).

- When the packed state fits in <= 3 words, the key *is* the packed state —
  dedup is exact (strictly stronger than TLC, whose 64-bit Rabin
  fingerprints accept a small collision probability).  This covers the
  shipped ``compaction.cfg`` (42 bits) and all differential-test configs.
- Wider states use three independent murmur3-style 32-bit hashes (96-bit
  effective fingerprint; collision expectation n^2/2^97 — e.g. ~1e-11 at a
  billion states, far below TLC's 64-bit regime).

The visited set is a sorted 3-column uint32 array padded with the all-ones
sentinel; membership is an unrolled branchless binary search (vectorized
over queries), insertion is concat + ``lax.sort`` (v0 of the mesh-sharded
FPSet; SURVEY.md §7-L3 replaces this with ownership-sharded tables routed
over ICI).

No 64-bit integers anywhere: TPU-friendly, jax x64 stays off.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

SENTINEL = jnp.uint32(0xFFFFFFFF)

_C1 = jnp.uint32(0xCC9E2D51)
_C2 = jnp.uint32(0x1B873593)


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _fmix(h: jax.Array) -> jax.Array:
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def murmur3_words(words: jax.Array, seed: int) -> jax.Array:
    """murmur3_32 over the trailing word axis.  words: u32[..., W] -> u32[...]."""
    w = words.shape[-1]
    h = jnp.full(words.shape[:-1], seed, jnp.uint32)
    for i in range(w):
        k = words[..., i] * _C1
        k = _rotl(k, 15) * _C2
        h = h ^ k
        h = _rotl(h, 13) * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return _fmix(h ^ jnp.uint32(4 * w))


def make_keys(packed: jax.Array, total_bits: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """packed u32[N, W] -> 3 x u32[N] dedup key columns.

    Exact (identity) when the state fits in < 96 bits, hashed otherwise.
    The all-SENTINEL triple is reserved as the empty/invalid marker: it is
    unreachable in exact mode (padding bits above ``total_bits`` are
    always zero, and at exactly 96 bits we fall through to hashing), and
    remapped with negligible collision cost in hashed mode.
    """
    n, w = packed.shape
    if w <= 3 and total_bits < 96:
        cols = [packed[:, i] for i in range(w)]
        while len(cols) < 3:
            cols.append(jnp.zeros((n,), jnp.uint32))
        return cols[0], cols[1], cols[2]
    h1 = murmur3_words(packed, 0x9E3779B9)
    h2 = murmur3_words(packed, 0x85EBCA6B)
    h3 = murmur3_words(packed, 0xC2B2AE35)
    all_sent = (h1 == SENTINEL) & (h2 == SENTINEL) & (h3 == SENTINEL)
    return h1, h2, jnp.where(all_sent, h3 ^ jnp.uint32(1), h3)


class KeySpec:
    """Dedup-key layout for one state layout (SURVEY.md §2.2-E3).

    Chooses the number of uint32 key columns and exact-vs-hashed mode:

    - ``total_bits < 64`` (W <= 2): the packed state IS the key — 2 exact
      columns (strictly stronger than TLC's 64-bit Rabin fingerprints);
    - ``total_bits < 96`` (W <= 3): 3 exact columns, as before;
    - wider states: murmur3 fingerprints — ``fp_bits=64`` (2 columns,
      TLC's fingerprint-width regime, collision probability reported
      like TLC's) or ``fp_bits=96`` (3 columns).  Default 64: one fewer
      operand in every dedup sort = ~25% less sort traffic, and XLA
      lowers the smaller comparator measurably faster.

    The all-SENTINEL tuple is reserved as the empty marker (unreachable
    in exact mode because at least one pad bit above ``total_bits`` is
    zero; remapped with negligible collision cost in hashed mode).
    """

    def __init__(self, total_bits: int, W: int, fp_bits: int | None = None):
        if W <= 2 and total_bits < 64:
            self.ncols, self.exact = 2, True
        elif W <= 3 and total_bits < 96:
            self.ncols, self.exact = 3, True
        else:
            if fp_bits is None:
                fp_bits = 64
            if fp_bits not in (64, 96):
                raise ValueError("fp_bits must be 64 or 96")
            self.ncols, self.exact = fp_bits // 32, False
        self.total_bits = total_bits
        self.W = W

    # value identity: a key spec is a static argument of the traced
    # units (engine/bodies.py), so two checkers of one layout must
    # present equal specs
    def _key(self):
        return (self.ncols, self.exact, self.total_bits, self.W)

    def __eq__(self, other):
        return type(other) is KeySpec and other._key() == self._key()

    def __hash__(self):
        return hash(self._key())

    def make(self, packed: jax.Array) -> Tuple[jax.Array, ...]:
        """packed u32[N, W] -> ``ncols`` x u32[N] key columns."""
        n, w = packed.shape
        if self.exact:
            cols = [packed[:, i] for i in range(w)]
            while len(cols) < self.ncols:
                cols.append(jnp.zeros((n,), jnp.uint32))
            return tuple(cols)
        h = [
            murmur3_words(packed, seed)
            for seed in (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35)[: self.ncols]
        ]
        all_sent = h[0] == SENTINEL
        for c in h[1:]:
            all_sent = all_sent & (c == SENTINEL)
        h[-1] = jnp.where(all_sent, h[-1] ^ jnp.uint32(1), h[-1])
        return tuple(h)

    _warned: set = set()

    def warn_if_hashed(self, max_states: int):
        """One stderr note when hashed-fingerprint mode engages by
        default (ADVICE r3): dedup turned probabilistic silently for
        wide states — surface it up front, not only in the final
        report.  Engines call this when the caller did not pick
        ``fp_bits`` explicitly.  Deduplicated per key configuration
        (ADVICE r4: a bench/test run builds several checkers and the
        note used to repeat for each)."""
        if self.exact:
            return
        cfg = (self.total_bits, self.ncols, max_states)
        if cfg in KeySpec._warned:
            return
        KeySpec._warned.add(cfg)
        import sys

        print(
            f"note: state is {self.total_bits} bits wide -> "
            f"{32 * self.ncols}-bit hashed fingerprints (TLC's regime); "
            f"expected fp collisions at {max_states} states: "
            f"{self.collision_prob(max_states):.3g} "
            "(fp_bits=96 available)",
            file=sys.stderr,
        )

    def collision_prob(self, n_states: int) -> float:
        """Expected number of fingerprint collisions at ``n_states``
        distinct states (birthday bound) — 0.0 in exact mode.  TLC
        prints the analogous estimate after every run."""
        if self.exact:
            return 0.0
        return float(n_states) * float(n_states) / 2.0 ** (
            32 * self.ncols + 1
        )


def _lex_less(
    a1: jax.Array, a2: jax.Array, a3: jax.Array,
    b1: jax.Array, b2: jax.Array, b3: jax.Array,
) -> jax.Array:
    """(a1,a2,a3) < (b1,b2,b3) lexicographically, unsigned."""
    return (a1 < b1) | (
        (a1 == b1) & ((a2 < b2) | ((a2 == b2) & (a3 < b3)))
    )


def sort_perm(
    invalid: jax.Array, k1: jax.Array, k2: jax.Array, k3: jax.Array
) -> jax.Array:
    """Stable permutation ordering valid lanes by key; invalid lanes last."""
    n = k1.shape[0]
    iota = jnp.arange(n, dtype=jnp.uint32)
    _, _, _, _, perm = jax.lax.sort(
        (invalid.astype(jnp.uint32), k1, k2, k3, iota),
        num_keys=4,
        is_stable=True,
    )
    return perm.astype(jnp.int32)


def bsearch_member(
    vk1: jax.Array, vk2: jax.Array, vk3: jax.Array, n_visited: jax.Array,
    q1: jax.Array, q2: jax.Array, q3: jax.Array,
) -> jax.Array:
    """Membership of queries in the sorted visited columns.  bool[N]."""
    cap = vk1.shape[0]
    nq = q1.shape[0]
    lo = jnp.zeros((nq,), jnp.int32)
    hi = jnp.full((nq,), n_visited, jnp.int32)
    for _ in range(max(1, cap.bit_length())):
        mid = (lo + hi) >> 1
        m1, m2, m3 = vk1[mid], vk2[mid], vk3[mid]
        less = _lex_less(m1, m2, m3, q1, q2, q3)
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    at = jnp.clip(lo, 0, cap - 1)
    eq = (vk1[at] == q1) & (vk2[at] == q2) & (vk3[at] == q3)
    return (lo < n_visited) & eq


def merge_sorted(
    vk1: jax.Array, vk2: jax.Array, vk3: jax.Array,
    nk1: jax.Array, nk2: jax.Array, nk3: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Merge new key columns (sentinel-padded) into the sorted visited set.

    Returns sorted columns of size ``cap`` (callers guarantee the real keys
    fit; sentinels sort to the tail and are sliced off).
    """
    cap = vk1.shape[0]
    c1 = jnp.concatenate([vk1, nk1])
    c2 = jnp.concatenate([vk2, nk2])
    c3 = jnp.concatenate([vk3, nk3])
    s1, s2, s3 = jax.lax.sort((c1, c2, c3), num_keys=3, is_stable=False)
    return s1[:cap], s2[:cap], s3[:cap]
