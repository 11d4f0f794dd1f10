"""The plain reference's side of every comparison: what the comparisons
under ``benchmark/comparisons/`` and the controls under
``benchmark/controls/`` share.

Every comparison holds what the timed path produced (the answers a
driver collected in its window) against the benchmark's own plain
reference, ``benchmark/ref/pyeval.py``.  All are exact: each number
compared has the limit 0, because a model checker's guarantees
(exhaustive breadth-first search, exact distinct-state count, shortest
counterexample) leave no tolerance.  A comparison returns a list of
checks ``{"name", "got", "want", "limit", "ok"}``; ``run.py`` prints
every one and sets ``correct`` to their conjunction.
"""

from __future__ import annotations

import zlib

from benchmark.ref import pyeval as pe

FALLBACK_TEXT = "falling back to the generic interpreter"
RECOVERY_TEXT = "recovered from device-memory exhaustion"


def chk(name, got, want):
    """One exact comparison: ``got`` is a count of mismatches or a value
    held against ``want``; the limit on their difference is 0."""
    return {"name": name, "got": got, "want": want, "limit": 0,
            "ok": got == want}


# ------------------------------------------------------------ reference


def bfs_levels(c, max_levels=None, fingerprint=None):
    """Breadth-first search by the reference: ``(level sizes, seen)``.
    ``fingerprint`` (state -> int), when given, replaces the exact
    visited set by a set of fingerprints: what a checker whose
    fingerprints are that narrow would count (the control)."""
    key = fingerprint or (lambda s: s)
    seen = set()
    frontier = []
    for s in pe.initial_states(c):
        k = key(s)
        if k not in seen:
            seen.add(k)
            frontier.append(s)
    sizes = [len(frontier)]
    while frontier and (max_levels is None or len(sizes) < max_levels):
        new = []
        for s in frontier:
            for _a, t in pe.successors(c, s):
                k = key(t)
                if k not in seen:
                    seen.add(k)
                    new.append(t)
        if not new:
            break
        sizes.append(len(new))
        frontier = new
    return sizes, seen


def narrow_fingerprint(bits: int, salt: int):
    """A ``bits``-wide fingerprint of a reference state (CRC-32 of its
    printed form, salted), for the narrow-fingerprint control."""
    mask = (1 << bits) - 1
    pre = f"{salt}:".encode()
    return lambda s: zlib.crc32(pre + repr(tuple(s)).encode()) & mask


def replay_lanes(c, lanes):
    """Follow a chain of the engine's successor lanes from the single
    initial state through the reference.  A lane below
    ``|KeySet| * |ValueSet|`` is the producer appending that (key, value)
    (when the producer is modelled); the lanes after them are the spec's
    other actions in ``Next`` order.  Returns the state reached, or None
    where a lane is not enabled in the reference."""
    inits = list(pe.initial_states(c))
    if len(inits) != 1:
        raise ValueError(
            "lane replay needs a configuration with one initial state "
            "(the producer modelled)"
        )
    s = inits[0]
    kv = (c.num_keys + 1) * (c.num_values + 1) if c.model_producer else 0
    for lane in lanes:
        nxt = None
        if lane < kv:
            want = (len(s.messages) + 1, lane // (c.num_values + 1),
                    lane % (c.num_values + 1))
            for a, t in pe.successors(c, s):
                if a == 0 and t.messages[-1] == want:
                    nxt = t
                    break
        else:
            aid = lane - kv + 1
            for a, t in pe.successors(c, s):
                if a == aid:
                    nxt = t
                    break
        if nxt is None:
            return None
        s = nxt
    return s


def fallback_or_recovery(answers):
    return sum(
        1 for a in answers
        if FALLBACK_TEXT in a["text"] or RECOVERY_TEXT in a["text"]
    )
