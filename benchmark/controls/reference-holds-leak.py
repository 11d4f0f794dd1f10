"""Control ``reference-holds-leak``: the program's own check on the
cell's own arguments, compared as a run's is, but with the reference
holding every dumped state to one invariant more than the configuration
states (``invariant`` of the control's entry: ``CompactedLedgerLeak``,
which upstream's own README says the spec violates).  Behaviours of the
cell's depth meet that violation with near certainty, so the comparison
has to read ``behaviour_wrong_early_violation`` and nothing else: the
reference does evaluate invariants at the states the files hold, and a
dump that left states out, or a comparison that skipped them, would come
out correct."""

from __future__ import annotations

import os

from benchmark.lib import plug

NEEDS_DEVICE = True


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    drv = plug.load_file("drivers", ctx["traffic"]["driver"]).Driver(
        ctx["config"], ctx["traffic"], ctx["root"], ctx["work_dir"], 0, seed)
    drv.load()
    return [dict(drv.one(), hold_also=[ctx["control"]["invariant"]])]
