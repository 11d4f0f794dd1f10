"""The least bytes ANY materialisation of a behaviour graph's edges has
to move, reckoned from what the program counted and the configuration's
widths.

The liveness engine (``engine/liveness.py``) turns the explorer's row
store into the ``<Next>_vars`` edge list the host analyses.  However it
joins successors to states, it has to read every state's packed row once
(``W`` words), read the key -> gid table once (``K`` key words and the
gid a state), write and read every successor lane's key once (``A``
lanes a state, ``K`` words each, twice), and write the kept edges (a
lane index and a destination a kept edge):

    4 * (n * (W + K + 1) + 2 * n * A * K + 2 * E)

bytes, with ``n`` = ``distinct_states`` and ``E`` = ``sweep_edges`` of a
check's liveness ``result`` stats, ``W``, ``K`` and ``A`` the
configuration's ``shapes.state_words``, ``.key_columns`` and
``.successor_lanes``.  It is reckoned from the graph and not from the
lanes the sweep sorted (``sweep_sort_lanes``), so a later sweep that
sorts less reads a higher share of the same work.
"""

from __future__ import annotations

import statistics

WORD_BYTES = 4


def graph_bytes(check: dict, shapes: dict):
    """Least bytes one check's edge materialisation moved, from its
    ``result`` stats; None where the program has no sweep counters (an
    older commit) or its sweep did not run."""
    n, e = check.get("distinct_states"), check.get("sweep_edges")
    if n is None or e is None or not check.get("sweep_chunks"):
        return None
    w, k, a = (shapes["state_words"], shapes["key_columns"],
               shapes["successor_lanes"])
    return WORD_BYTES * (n * (w + k + 1) + 2 * n * a * k + 2 * e)


def window_bytes(ctx):
    """The sum over the window's checks that carry the counters; None
    where none does."""
    shapes = ctx["config"]["shapes"]
    found = [graph_bytes(a.get("stats", {}), shapes)
             for a in ctx["out"]["answers"]]
    found = [b for b in found if b is not None]
    return sum(found) if found else None


def median_over_checks(ctx, value):
    """Median over the window's checks of ``value(stats)``, skipping
    the checks where it is None; None where every one is (a commit
    without the counters)."""
    vals = [value(a.get("stats", {})) for a in ctx["out"]["answers"]]
    vals = [v for v in vals if v is not None]
    return statistics.median(vals) if vals else None


def share_pct(moved_bytes: float, device_s: float,
              hbm_bytes_per_s: float) -> float:
    """``moved_bytes`` over ``device_s`` device seconds, as a percentage
    of the memory's peak."""
    return 100.0 * moved_bytes / device_s / hbm_bytes_per_s
