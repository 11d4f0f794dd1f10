"""Control ``program-recover-dropped``: the program on the cell's own
cycle with ``-recover`` left out of leg 2: the check is preempted, the
frame is on disk, and the second leg finds the verdict afresh.  The
count, the diameter and every level size are the reference's and no
recovered line is printed, so the comparison, which holds a cycle to its
frame, reads ``not_resumed`` alone: a restart that throws the frame away
is another deployment, not this one."""

from __future__ import annotations

import os

from benchmark.lib import plug

NEEDS_DEVICE = True
ONE_RUN = True  # the answer does not depend on the seed


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    t = dict(ctx["traffic"], recover=False)
    drv = plug.load_file("drivers", t["driver"]).Driver(
        ctx["config"], t, ctx["root"], ctx["work_dir"], 0, seed)
    drv.load()
    return [drv.one()]
