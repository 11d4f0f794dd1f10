"""The table growth's share of its roofline: the least bytes the
window's table doublings have to move (``benchmark/lib/grow_bytes.py``,
from the program's ``grow_rehash_slots``) over the device seconds under
the ``ptt.rehash`` scope, against the chip's HBM peak
(``benchmark/lib/peaks.json``).  Memory bounds it, not compute: a rehash
hashes and compares, it multiplies nothing.  It cannot pass 100."""

from benchmark.lib import grow_bytes, program_spans, xplane_fast


def read(ctx, params):
    moved = grow_bytes.window_bytes(ctx)
    if not moved or not ctx.get("peaks"):
        return None  # no growth counted, or a test's run off the chip
    xplane_fast.prime(ctx)
    secs = program_spans.stage_seconds(ctx, "rehash")
    if not secs:
        return None  # no rehash traced
    return grow_bytes.share_pct(moved, secs, ctx["peaks"]["hbm_bytes_per_s"])
