"""The eviction's share of its roofline: the least bytes the window's
evictions have to move (``benchmark/lib/spill_bytes.py``, from the
program's ``spill_evict_slots`` and ``spill_keys_evicted``) over the
device seconds under the ``ptt.spill_evict`` scope, against the chip's
HBM peak (``benchmark/lib/peaks.json``).  Memory bounds it, not compute:
an eviction compares, compacts and sorts, it multiplies nothing.  It
cannot pass 100."""

from benchmark.lib import grow_bytes, program_spans, spill_bytes, xplane_fast


def read(ctx, params):
    moved = spill_bytes.window_evict_bytes(ctx)
    if not moved or not ctx.get("peaks"):
        return None  # no eviction counted, or a test's run off the chip
    xplane_fast.prime(ctx)
    secs = program_spans.stage_seconds(ctx, "spill_evict")
    if not secs:
        return None  # no eviction traced
    return grow_bytes.share_pct(moved, secs, ctx["peaks"]["hbm_bytes_per_s"])
