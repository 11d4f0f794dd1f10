"""Driver ``repeat-cli-tiered``: ``repeat-cli`` (every method is its
own) for a cell whose checks run under a device-memory budget, entered
only with a program that can run such a cell.

It refuses at once, before any check and with another exit code than 0,
a checkout whose CLI cannot say what a check spilled
(``pulsar_tlaplus_tpu.cli.tiered_line``: the line the cell's comparison
holds every check to).  That is the commit before the tiered store was
made a supported deployment: there a first check of the cell's binding
slices every fetch eagerly at a data-dependent length, an executable a
length, and is 40 minutes and more on a cold cache (PERF.md 6, PR 41:
killed at 1,800 s in level 19 of 24), so a run of the cell on it could
only be killed by its time limit.
"""

from __future__ import annotations

import sys

from benchmark.lib import plug

_BASE = plug.load_file("drivers", "repeat-cli")


class Driver(_BASE.Driver):
    def load(self):
        super().load()
        if not hasattr(self.cli, "tiered_line"):
            sys.exit(
                "benchmark: refused: this checkout's "
                f"{self.config['program']['cli']} prints no tiered line "
                "(it has no tiered_line): it cannot run a cell whose "
                "checks are held to a device-memory budget")
