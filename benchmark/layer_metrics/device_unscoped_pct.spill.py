"""Share of the window's device self time under none of the program's
``ptt.`` stage scopes (``benchmark/lib/program_spans.py``): transfers,
fills, and what the host dispatches eagerly.  Prints the seconds of
every scope (the tiered store's ``ptt.spill_*`` among them)."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.unscoped_pct(ctx)
