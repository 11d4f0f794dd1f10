"""Driver ``repeat-cli-simulate``: ``repeat-cli`` (every method is its
own) for a cell whose checks run in simulation mode, entered only with a
program that can say what it walked.

It refuses at once, before any check and with another exit code than 0,
a checkout whose CLI cannot name the swarm, the budget and the walk
stream of a simulation (``pulsar_tlaplus_tpu.cli.simulated_line``: the
line the cell's comparison holds every check to) and cannot dump its
behaviours.  That is the commit before simulation mode was made a
supported deployment: its ``-simulate`` has no ``-sim-dump`` (argparse
would end every check with exit 2), and a step of it at the cell's
width takes 11.5 GB of transient by the TPU compiler's own reckoning
(PERF.md 6, PR 52).

Two things a check's command line takes from the run and not from the
traffic file: the harness's ``--seed`` as ``-sim-seed`` (the walk
stream is a function of it), and a ``-sim-dump`` prefix of the check's
own under the work directory, emptied before the check.
"""

from __future__ import annotations

import os
import shutil
import sys

from benchmark.lib import plug

_BASE = plug.load_file("drivers", "repeat-cli")


class Driver(_BASE.Driver):
    def __init__(self, config, traffic, root, work_dir, trace, seed):
        super().__init__(config, traffic, root, work_dir, trace, seed)
        self.seed = seed

    def load(self):
        super().load()
        if not hasattr(self.cli, "simulated_line"):
            sys.exit(
                "benchmark: refused: this checkout's "
                f"{self.config['program']['cli']} prints no simulated line "
                "(it has no simulated_line): it cannot run a cell whose "
                "checks are held to the behaviours they dump")

    def _argv(self):
        dump_dir = os.path.join(self.work_dir, f"sim_dump_{self.n}")
        shutil.rmtree(dump_dir, ignore_errors=True)
        os.makedirs(dump_dir)
        self.dump_prefix = os.path.join(dump_dir, "behaviour")
        argv, tel = super()._argv()
        return argv + ["-sim-seed", str(self.seed),
                       "-sim-dump", self.dump_prefix], tel

    def one(self):
        ans = super().one()
        ans["dump_prefix"] = self.dump_prefix
        return ans
