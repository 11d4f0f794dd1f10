"""Driver ``repeat-cli``: the same ``cli.main([...])`` call, back to back
in one process for ``--seconds``.

What a user who runs ``cli check`` again and again with the compile cache
warm waits for.  A check starts only if the median so far says it ends
inside the window (the first always starts).  Every check's standard
output (the verdict, the counterexample) and standard error (the CLI's
own per-level progress lines) are kept for the comparison; only the
traced run adds ``-telemetry FILE``, whose result event carries the
engine's counters for the per-layer readers.
"""

from __future__ import annotations

import contextlib
import importlib
import io
import json
import os
import re
import statistics
import sys
import time

from benchmark.lib.plug import span

# the CLI's progress line, as device_bfs._log prints it to stderr
LEVEL_LINE = re.compile(r"^\s*level (\d+): \+(\d+) \(total (\d+),", re.M)


def level_sizes_from_progress(text: str):
    """Per-level sizes from the progress lines of one check: level 1 is
    the first line's total less what that level added.  None where the
    lines do not number the levels 2, 3, ... without a gap."""
    rows = [tuple(int(x) for x in m.groups())
            for m in LEVEL_LINE.finditer(text)]
    if not rows or [r[0] for r in rows] != list(range(2, len(rows) + 2)):
        return None
    return [rows[0][2] - rows[0][1]] + [r[1] for r in rows]


class Driver:
    def __init__(self, config, traffic, root, work_dir, trace, seed):
        self.config, self.traffic = config, traffic
        self.root, self.work_dir, self.trace = root, work_dir, trace
        self.n = 0

    def _argv(self):
        argv = [
            os.path.join(self.root, a) if a.startswith("specs/") else a
            for a in self.traffic["argv"]
        ]
        tel = None
        if self.trace:
            tel = os.path.join(self.work_dir, f"telemetry_{self.n}.jsonl")
            if os.path.exists(tel):
                os.remove(tel)
            argv += ["-telemetry", tel]
        self.n += 1
        return argv, tel

    def one(self):
        argv, tel = self._argv()
        out, err = io.StringIO(), io.StringIO()
        t0 = time.perf_counter()
        with span("cli.main"), contextlib.redirect_stdout(out), \
                contextlib.redirect_stderr(err):
            try:
                rc = self.cli.main(argv)
            except SystemExit as e:  # cli reports set-up errors this way
                rc = e.code if isinstance(e.code, int) else 2
                out.write(f"\n(sys.exit: {e.code})\n")
        wall = time.perf_counter() - t0
        ans = {"rc": rc, "text": out.getvalue(), "wall_s": wall,
               "level_sizes": level_sizes_from_progress(err.getvalue()),
               "engine_wall_s": None, "stats": {}}
        if rc != self.traffic["exit_code"]:
            # not what the cell expects: show what the program said
            sys.stderr.write(err.getvalue())
        if tel and os.path.exists(tel):
            with open(tel, encoding="utf-8") as f:
                events = [json.loads(x) for x in f if x.strip()]
            res = [e for e in events if e.get("event") == "result"]
            if res:
                ans["engine_wall_s"] = res[-1].get("wall_s")
                ans["stats"] = res[-1].get("stats", {})
        return ans

    def load(self):
        self.traffic["cfg_path"] = os.path.join(self.root, self.traffic["cfg"])
        self.cli = importlib.import_module(self.config["program"]["cli"])

    def setup(self, seconds):
        self.load()
        # warm-up: the cell's own check once, so that every program it
        # meets is in the cache and the process has paid its one-off
        # imports
        self.warm = self.one()

    def window(self, seconds):
        want_rc = self.traffic["exit_code"]
        answers = []
        guess = self.warm["wall_s"]
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if answers and now + guess > seconds:
                break
            answers.append(self.one())
            guess = statistics.median(a["wall_s"] for a in answers)
        wall = time.perf_counter() - t0
        stats = {
            "walls_s": [a["wall_s"] for a in answers],
            "engine_walls_s": [a["engine_wall_s"] for a in answers],
            "checks": [a["stats"] for a in answers],
        }
        return {
            "window_s": wall,
            "end_to_end": {
                "verdict_s": statistics.median(a["wall_s"] for a in answers),
            },
            "attempted": len(answers),
            "failed": sum(1 for a in answers if a["rc"] != want_rc),
            "answers": answers,
            "stats": stats,
        }

    def after_window(self, out):
        pass
