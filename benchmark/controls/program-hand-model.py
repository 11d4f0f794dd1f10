"""Control ``program-hand-model``: the program on the cell's own
arguments without ``-compile``: the same binding checked through the
hand-written model of ``models/registry.py``.  The count, the diameter
and every level size are the reference's; the banner is the hand
model's and no compiled line is printed, so the comparison, which holds
a check to kernels generated from the ``.tla`` text, reads
``compiled_line_missing`` and ``hand_model_banner`` and nothing else: a
check through ``models/compaction.py`` is another deployment, not a
faster one."""

from __future__ import annotations

import os

from benchmark.lib import plug

NEEDS_DEVICE = True
ONE_RUN = True  # the answer does not depend on the seed


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    argv = [a for a in ctx["traffic"]["argv"] if a != "-compile"]
    t = dict(ctx["traffic"], argv=argv)
    drv = plug.load_file("drivers", t["driver"]).Driver(
        ctx["config"], t, ctx["root"], ctx["work_dir"], 0, seed)
    drv.load()
    return [drv.one()]
