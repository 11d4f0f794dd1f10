"""The edge sweep's share of its roofline: the least bytes any
materialisation of the window's behaviour graphs has to move
(``benchmark/lib/sweep_bytes.py``, from the program's
``distinct_states`` and ``sweep_edges`` and the configuration's
``shapes``) over the device seconds under the four ``ptt.sweep_*``
scopes, against the chip's HBM peak (``benchmark/lib/peaks.json``).
Memory bounds it, not compute: the sweep sorts, compares and copies.  It
cannot pass 100."""

from benchmark.lib import program_spans, sweep_bytes, xplane_fast

SCOPES = ("sweep_expand", "sweep_join", "sweep_prop", "sweep_compact")


def read(ctx, params):
    moved = sweep_bytes.window_bytes(ctx)
    if not moved or not ctx.get("peaks"):
        return None  # no sweep counted, or a test's run off the chip
    xplane_fast.prime(ctx)
    secs = [program_spans.stage_seconds(ctx, s) for s in SCOPES]
    if not all(s is not None for s in secs) or sum(secs) <= 0:
        return None  # no sweep traced
    return sweep_bytes.share_pct(moved, sum(secs),
                                 ctx["peaks"]["hbm_bytes_per_s"])
