"""Control ``program-no-budget``: the program on the cell's own
arguments without ``-hbm-budget`` and its value: the same binding checked
with the whole visited set on the device.  The count, the diameter and
every level size are the reference's and no tiered line is printed, so
the comparison, which holds a check to its budget, reads not correct: a
run that ignores its budget is another deployment, not a faster one."""

from __future__ import annotations

import os

from benchmark.lib import plug

NEEDS_DEVICE = True
ONE_RUN = True  # the answer does not depend on the seed


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    argv = list(ctx["traffic"]["argv"])
    i = argv.index("-hbm-budget")
    del argv[i: i + 2]
    t = dict(ctx["traffic"], argv=argv)
    drv = plug.load_file("drivers", t["driver"]).Driver(
        ctx["config"], t, ctx["root"], ctx["work_dir"], 0, seed)
    drv.load()
    return [drv.one()]
