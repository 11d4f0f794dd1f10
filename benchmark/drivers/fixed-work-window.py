"""Driver ``fixed-work-window``: one ``DeviceChecker.run()`` from the
host-seeded levels to a ``max_states`` cap.

A fixed amount of work, because the engine reads its clock only between
dispatches that last tens of seconds at this width, so the window cannot
be ended on the clock from outside.  ``--seconds`` is passed as
``time_budget_s``, a ceiling the work must end inside.  The program is
entered as ``bench.py`` enters it: ``DeviceChecker``, ``warmup(seed=True)``
beside the host seed on a thread, then ``run(seed=...)``.
"""

from __future__ import annotations

import threading
import time

from benchmark.lib.plug import load_attr, span


class Driver:
    def __init__(self, config, traffic, root, work_dir, trace, seed):
        self.config, self.traffic = config, traffic
        self.ck = None
        self.seed_rows = None

    def build(self, seconds):
        """The checker this cell times.  (The control builds the same one
        after narrowing the fingerprint.)"""
        prog = self.config["program"]
        constants = load_attr(prog["constants"])(**self.config["constants"])
        self.model = load_attr(prog["model"])(constants)
        kw = dict(self.config["checker_kw"])
        kw["max_states"] = self.traffic["max_states"]
        self.ck = load_attr(prog["checker"])(
            self.model, time_budget_s=float(seconds), progress=True, **kw
        )
        return self.ck

    def setup(self, seconds):
        ck = self.build(seconds)
        box = {}

        def _seed():
            # bench.py's set-up: the program's own oracle enumerates the
            # narrow early levels on a thread while the device programs
            # compile, and pushes them to the device
            try:
                box["seed"] = self.model.host_seed(**self.config["host_seed"])
                ck.prestage_seed(box["seed"])
            except Exception as e:  # noqa: BLE001 — re-raised below
                box["err"] = e

        th = threading.Thread(target=_seed)
        th.start()
        try:
            with span("warmup"):
                ck.warmup(seed=True)
        finally:
            th.join()
        if "err" in box:
            raise box["err"]
        self.seed_rows = box["seed"]

    def window(self, seconds):
        seed = self.seed_rows
        n_seed = len(seed[0])
        t0 = time.perf_counter()
        with span("run"):
            # run() returns after the engine's own last stats fetch
            r = self.ck.run(seed=seed)
        wall = time.perf_counter() - t0
        st = dict(self.ck.last_stats)
        levels = [int(x) for x in r.level_sizes]
        dispatches = int(round(
            float(st.get("dispatches_per_level", 0.0)) * max(len(levels), 1)
        ))
        failed = int(st.get("fpset_failures") or 0) + int(r.hbm_recovered)
        return {
            "window_s": wall,
            "fixed_work": True,
            "end_to_end": {
                "states_per_s": (r.distinct_states - n_seed) / wall,
            },
            "attempted": dispatches,
            "failed": failed,
            "answers": [{
                "seed_level_sizes": [int(x) for x in seed[3]],
                "level_sizes": levels,
                "distinct_states": int(r.distinct_states),
                "truncated": bool(r.truncated),
                "stop_reason": r.stop_reason,
                "stats": st,
            }],
            "stats": dict(st, distinct_states=int(r.distinct_states),
                          seed_states=n_seed, level_sizes=levels,
                          state_words=int(self.ck.W),
                          key_columns=int(self.ck.K)),
        }

    def after_window(self, out):
        """The parent and lane logs of the closed levels, for the sample
        replay (a device slice and one D2H copy each)."""
        import numpy as np

        (a,) = out["answers"]
        n = a["distinct_states"]
        bufs = getattr(self.ck, "last_bufs", None) or {}
        for name in ("parent", "lane"):
            buf = bufs.get(name)
            a[f"{name}_log"] = (
                np.asarray(buf[:n]) if buf is not None else np.zeros(0, int)
            )
