"""Driver ``repeat-cli-compiled``: ``repeat-cli`` (every method is its
own) for a cell whose checks run kernels generated from the ``.tla``
text, entered only with a program that can say so.

It refuses at once, before any check and with another exit code than 0,
a checkout whose CLI cannot name the path and the widths a check ran at
(``pulsar_tlaplus_tpu.cli.compiled_line``: the line the cell's
comparison holds every check to).  That is the commit before the
compiled path was made a supported deployment: its ``-compile`` finds
the right count (PERF.md 6, PR 49: run by hand), but no check of it can
be held to its path, so every check of a window would read
``compiled_line_missing`` and a run of the cell on it could only come
out not correct.
"""

from __future__ import annotations

import sys

from benchmark.lib import plug

_BASE = plug.load_file("drivers", "repeat-cli")


class Driver(_BASE.Driver):
    def load(self):
        super().load()
        if not hasattr(self.cli, "compiled_line"):
            sys.exit(
                "benchmark: refused: this checkout's "
                f"{self.config['program']['cli']} prints no compiled line "
                "(it has no compiled_line): it cannot run a cell whose "
                "checks are held to the spec->kernel compiler's path and "
                "widths")
