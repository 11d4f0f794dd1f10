"""Driver ``repeat-cli-recover``: ``repeat-cli``'s window rule over
CYCLES of a check that is preempted and recovered, the driver playing
the cluster manager.

A cycle is two calls of ``cli.main`` in this process, compile cache
warm.  Leg 1 is the traffic's ``argv`` with ``-checkpoint F``, ``F`` a
new path under the run's work directory every cycle.  The driver hands
it a standard-error stream that reads the CLI's own progress lines and,
at the first whole line numbered ``kill.at_level`` or more, sends this
process the traffic's signal, once (``signal.raise_signal``: what a
preempted TPU VM is sent).  The program frames at its next level
boundary and returns 3.  Leg 2 is the same call with ``-recover`` and
starts at once; it has to return 0.  The cycle's wall is leg 1's plus
leg 2's (the restart of a process between them is set-up's kind and is
not in it): the cell's ``verdict_s`` is the median over the window's
cycles.  ``attempted`` counts cycles; one with either exit code wrong is
``failed``.  The warm-up is one whole cycle; the first cycle of a window
always starts, a further one only if the median so far says it ends
inside the window.

An answer is a cycle's: ``rc`` 0 where both legs returned what the
traffic expects (else 1), ``rcs`` the two codes, ``text`` leg 2's
standard output, ``level_sizes`` the two legs' progress lines joined
(``benchmark/lib/ckpt_bytes.py``), ``legs`` what each leg printed and,
traced, counted, ``frame_after_leg1`` whether ``F`` was there, and
``stats`` the cycle's counters: those of ``SUMMED`` added over the legs,
those of ``LARGEST`` their larger, leg 2's restore and resume keys, and
``dispatches_per_level`` the two legs' dispatches over the levels of the
whole search.

It refuses at once, before any check and with another exit code than 0,
a checkout whose CLI cannot say what a recovered check resumed from
(``pulsar_tlaplus_tpu.cli.recovered_line``: the line the cell's
comparison holds every cycle to), as ``repeat-cli-tiered`` does for the
tiered line: on such a commit the frames' slices and the restore's
buffers are built eagerly at data-dependent lengths, an executable a
length, and the cell is not a supported deployment there.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import signal
import sys
import time

from benchmark.lib import ckpt_bytes, plug
from benchmark.lib.plug import span

_BASE = plug.load_file("drivers", "repeat-cli")

# a cycle's counter is the sum over its legs ...
SUMMED = (
    "host_ckpt_s", "ckpt_gather_s", "ckpt_pack_s", "ckpt_npz_s",
    "ckpt_write_s", "ckpt_frames", "ckpt_bytes", "ckpt_raw_bytes",
    "ckpt_d2h_bytes", "ckpt_states", "ckpt_retries", "hbm_recovered",
    "host_init_s", "host_dispatch_s", "host_fetch_s", "host_grow_s",
    "host_account_s", "host_result_s", "host_unaccounted_s", "jit_host_s",
    "jit_body_traces", "jit_backend_compiles", "grow_events",
    "stats_fetches",
)
# ... or the larger of the two ...
LARGEST = ("grow_wall_max_s", "level_wall_max_s")
# ... or leg 2's own
OF_LEG2 = (
    "restore_s", "restore_load_s", "restore_unpack_s", "restore_upload_s",
    "restore_h2d_bytes", "resume_level", "resume_states",
    "resume_levels_run",
)


def cycle_stats(legs):
    """The cycle's counters from its legs' ``result`` stats; ``{}``
    where a leg carries none (an untraced run)."""
    sts = [leg["stats"] for leg in legs]
    if not all(sts):
        return {}
    out = {k: sum(st[k] for st in sts) for k in SUMMED
           if all(k in st for st in sts)}
    out.update({k: max(st[k] for st in sts) for k in LARGEST
                if all(k in st for st in sts)})
    out.update({k: sts[-1][k] for k in OF_LEG2 if k in sts[-1]})
    levels = [leg["last_level"] for leg in legs]
    if all(levels) and all("dispatches_per_level" in st for st in sts):
        # a leg's own ratio is over every level of its result, the
        # frame's among them: its dispatches come back by that count
        out["dispatches_per_level"] = sum(
            st["dispatches_per_level"] * n for st, n in zip(sts, levels)
        ) / levels[-1]
    return out


class KillAtLevel(io.StringIO):
    """Standard error as the cluster manager reads it: at the first
    whole progress line numbered ``at_level`` or more it sends this
    process ``signum``, once.  It sends nothing while the signal's
    handler is the default one (the program has armed no watcher: the
    signal would end the benchmark, not the check)."""

    def __init__(self, at_level, signum):
        super().__init__()
        self.at_level, self.signum = at_level, signum
        self.sent_at = None
        self.unarmed = False
        self._line = ""

    def write(self, s):
        n = super().write(s)
        if self.sent_at is None and not self.unarmed:
            *whole, self._line = (self._line + s).split("\n")
            for line in whole:
                m = ckpt_bytes.LEVEL_LINE.match(line)
                if m and int(m.group(1)) >= self.at_level:
                    if signal.getsignal(self.signum) in (
                            signal.SIG_DFL, signal.SIG_IGN, None):
                        self.unarmed = True
                    else:
                        self.sent_at = int(m.group(1))
                        signal.raise_signal(self.signum)
                    break
        return n


class Driver(_BASE.Driver):
    def load(self):
        super().load()
        if not hasattr(self.cli, "recovered_line"):
            sys.exit(
                "benchmark: refused: this checkout's "
                f"{self.config['program']['cli']} cannot say what a "
                "recovered check resumed from (it has no recovered_line): "
                "it cannot run a cell whose cycles are held to their frame")
        self.frames_dir = os.path.join(self.work_dir, "frames")
        os.makedirs(self.frames_dir, exist_ok=True)
        self.cycles = 0

    def _leg(self, extra, err):
        argv, tel = self._argv()
        argv += extra
        out = io.StringIO()
        t0 = time.perf_counter()
        with span("cli.main"), contextlib.redirect_stdout(out), \
                contextlib.redirect_stderr(err):
            try:
                rc = self.cli.main(argv)
            except SystemExit as e:  # cli reports set-up errors this way
                rc = e.code if isinstance(e.code, int) else 2
                out.write(f"\n(sys.exit: {e.code})\n")
        wall = time.perf_counter() - t0
        rows = ckpt_bytes.progress_rows(err.getvalue())
        leg = {"rc": rc, "text": out.getvalue(), "wall_s": wall,
               "progress": rows, "stats": {},
               "last_level": rows[-1][0] if rows else None}
        if tel and os.path.exists(tel):
            with open(tel, encoding="utf-8") as f:
                events = [json.loads(x) for x in f if x.strip()]
            res = [e for e in events if e.get("event") == "result"]
            if res:
                leg["stats"] = res[-1].get("stats", {})
                # a resumed leg that closed no level printed no line
                leg["last_level"] = res[-1].get("diameter")
        return leg, err.getvalue()

    def one(self):
        """One cycle: leg 1 to the kill, leg 2 from the frame."""
        kill = self.traffic["kill"]
        # a new frame path a cycle; the cycle before's frame goes
        frame = os.path.join(self.frames_dir, f"cycle_{self.cycles}.npz")
        for name in os.listdir(self.frames_dir):
            os.remove(os.path.join(self.frames_dir, name))
        self.cycles += 1
        watch = KillAtLevel(kill["at_level"],
                            getattr(signal, kill["signal"]))
        leg1, err1 = self._leg(["-checkpoint", frame], watch)
        leg1["killed_at"] = watch.sent_at
        there = os.path.exists(frame)
        leg2, err2 = self._leg(
            ["-checkpoint", frame]
            + (["-recover"] if self.traffic.get("recover", True) else []),
            io.StringIO())
        rcs = [leg1["rc"], leg2["rc"]]
        ok = rcs == self.traffic["exit_codes"]
        if not ok:
            # not what the cell expects: show what the program said
            sys.stderr.write(err1 + err2)
        legs = [leg1, leg2]
        return {
            "rc": 0 if ok else 1, "rcs": rcs, "text": leg2["text"],
            "wall_s": leg1["wall_s"] + leg2["wall_s"],
            "level_sizes": ckpt_bytes.joined_level_sizes(
                leg1["progress"], leg2["progress"]),
            "legs": legs, "frame_after_leg1": there,
            "engine_wall_s": None, "stats": cycle_stats(legs),
        }

    def window(self, seconds):
        out = super().window(seconds)
        out["stats"]["leg_walls_s"] = [
            [leg["wall_s"] for leg in a["legs"]] for a in out["answers"]]
        return out
