"""Device self seconds of the window's operations under the program's
``ptt.spill_evict`` stage scope (``benchmark/lib/program_spans.py``):
an eviction's compaction and three-operand sort of the table's cold generations (``store/sieve.py: extract_cold``).
Summed over the window's checks."""

from benchmark.lib import program_spans, xplane_fast


def read(ctx, params):
    xplane_fast.prime(ctx)
    return program_spans.stage_seconds(ctx, "spill_evict")
