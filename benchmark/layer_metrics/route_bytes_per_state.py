"""Bytes a shard sends through the key exchange (``K`` key planes out,
one flag plane back, at the capacity the exchange is compiled to) per
distinct state the check found: median over the window's checks
(``benchmark/lib/route_bytes.py``)."""

import statistics

from benchmark.lib import route_bytes


def read(ctx, params):
    vals = [b / n for b, n in route_bytes.per_check(ctx) if n > 0]
    return statistics.median(vals) if vals else None
