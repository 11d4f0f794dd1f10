"""Device self seconds of the window's operations under the program's
``ptt.spill_sieve`` stage scope (``benchmark/lib/program_spans.py``):
the pack of a flush's hot-filter survivors, the only keys that cross to the host (``store/sieve.py: sieve_new``).
Summed over the window's checks."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "spill_sieve")
