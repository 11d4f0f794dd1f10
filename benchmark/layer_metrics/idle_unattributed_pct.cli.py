"""Of the device's idle time inside the window's ``ptt:check`` spans,
the share that lies in no phase span of the engine and no ``ptt:cli.*``
span (``benchmark/lib/program_spans.py``); prints idle seconds by
innermost span, and one level further down by host event."""

from benchmark.lib import program_spans


def read(ctx, params):
    return program_spans.idle_unattributed_pct(ctx)
