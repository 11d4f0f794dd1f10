"""Control ``program-simulate``: the program's own cheaper path, ``cli
check -simulate N`` (random walks instead of the exhaustive breadth-first
search), on the cell's own arguments: it gives no verdict, or a
counterexample that is not a shortest one."""

from __future__ import annotations

import os

from benchmark.lib import plug

NEEDS_DEVICE = True


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    t = dict(ctx["traffic"],
             argv=ctx["traffic"]["argv"] + ctx["control"]["extra_argv"]
             + ["-sim-seed", str(seed % (1 << 31))])
    drv = plug.load_file("drivers", t["driver"]).Driver(
        ctx["config"], t, ctx["root"], ctx["work_dir"], 0, seed)
    drv.load()
    return [drv.one()]
