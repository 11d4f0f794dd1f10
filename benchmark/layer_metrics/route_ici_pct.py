"""The key exchange's share of one chip's interconnect peak: the bytes a
shard sent through both exchanges in the window that cross the
interconnect (``benchmark/lib/route_bytes.py``: all but the block it
keeps), over the device seconds under ``ptt.route`` (mean of the
planes), against the chip's ICI peak (``benchmark/lib/ici_peaks.json``).
The scope holds the bucketing arithmetic as well as the collectives, so
this is a floor on what the collectives alone reach; it cannot pass
100."""

import json
import os

from benchmark.lib import plug, program_spans, route_bytes, xplane_fast


def read(ctx, params):
    sent = [b for b, _n in route_bytes.per_check(ctx)]
    if not sent or not ctx.get("peaks"):
        return None  # no exchange counted, or a test's run off the chip
    xplane_fast.prime(ctx)
    secs = program_spans.stage_seconds(ctx, "route")
    if not secs:
        return None  # no exchange traced
    import jax

    return share_pct(sum(sent), ctx["config"]["layout"]["chips"], secs,
                     ici_peak(jax.devices()[0].device_kind))


def ici_peak(device_kind):
    """One chip's interconnect peak in bytes a second; a device that is
    not in the table is an error, not a default."""
    with open(os.path.join(plug.BENCH_DIR, "lib", "ici_peaks.json"),
              encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(
            f"device_kind {device_kind!r} is not in lib/ici_peaks.json")
    return peaks[device_kind]["ici_bytes_per_s"]


def share_pct(sent_bytes, chips, route_s, ici_bytes_per_s):
    """``sent_bytes`` a shard put through the exchanges, of which what
    crosses the interconnect, over ``route_s`` device seconds, as a
    percentage of the peak."""
    crossed = route_bytes.crossing(sent_bytes, chips)
    return 100.0 * crossed / route_s / ici_bytes_per_s
