"""Control ``program-fairness-none``: the program asked the same
property with no fairness assumed (``-fairness none`` in the cell's own
arguments): ``<>P`` is then violated by stuttering at the initial state,
the CLI answers VIOLATED with exit code 1 and prints no edge of the
graph, so the comparison, which holds the verdict under ``wf_next``,
reads not correct."""

from __future__ import annotations

import os

from benchmark.lib import plug

NEEDS_DEVICE = True
ONE_RUN = True  # the answer does not depend on the seed


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    argv = list(ctx["traffic"]["argv"])
    argv[argv.index("-fairness") + 1] = ctx["control"]["fairness"]
    t = dict(ctx["traffic"], argv=argv)
    drv = plug.load_file("drivers", t["driver"]).Driver(
        ctx["config"], t, ctx["root"], ctx["work_dir"], 0, seed)
    drv.load()
    return [drv.one()]
