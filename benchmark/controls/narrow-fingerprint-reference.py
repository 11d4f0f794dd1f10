"""Control ``narrow-fingerprint-reference``: the reference's own
breadth-first search in the program's place, deduplicating on a
``bits``-wide fingerprint instead of the state (the configuration states
64 bits; 32 is the step below).  Pure Python: needs no chip."""

from __future__ import annotations

from benchmark.lib import reference, tlafmt


def answers(ctx, seed):
    c = tlafmt.constants_from_cfg(ctx["traffic"]["cfg_path"])
    sizes, seen = reference.bfs_levels(
        c, fingerprint=reference.narrow_fingerprint(
            ctx["control"]["bits"], seed))
    text = (f"{len(seen)} distinct states found, search depth "
            f"(diameter) {len(sizes)}.\n")
    return [{"rc": 0, "text": text, "level_sizes": sizes}]
