#!/usr/bin/env python3
"""The controls: each comparison shown to fail.

    python3 benchmark/control.py --workload NAME --seeds 1,2,3

A control puts in the program's place something that breaks one guarantee
the configuration states, hands its answers to the same comparison a run
uses, and has to come out as not correct.  The configuration file names
its control under ``control.kind``; the harness finds it as
``benchmark/controls/<kind>.py`` with an ``answers(ctx, seed)`` of its
own.  A control that crashes or gives no answers has failed too, and is
reported as such, apart from one that fails the comparison.

The benchmark's own runs never run a control.  Prints one JSON line per
seed and exits 0 only if every seed came out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark.lib import plug  # noqa: E402


def run_control(manifest, workload, seeds, seconds, require_tpu=True):
    man, cell, config, traffic = harness.load_cell(manifest, workload)
    kind, control = config["reference"]["comparison"], config["control"]
    if isinstance(kind, dict):
        kind, control = kind[traffic["expect"]], control[traffic["expect"]]
    if "cfg" in traffic:
        traffic["cfg_path"] = os.path.join(ROOT, traffic["cfg"])
    mod = plug.load_file("controls", control["kind"])
    if getattr(mod, "NEEDS_DEVICE", False):
        from pulsar_tlaplus_tpu.utils.device import setup_compile_cache

        harness.check_device(cell, require_tpu)
        setup_compile_cache()
    # a control whose run does not depend on the seed (ONE_RUN) is made
    # once, and its answers, or its crash, are compared under every seed
    one_run, made = getattr(mod, "ONE_RUN", False), None
    compare = plug.load_file("comparisons", kind).compare
    ctx = {"control": control, "config": config, "traffic": traffic,
           "cell": cell, "seconds": seconds, "root": ROOT,
           "work_dir": harness.WORK_DIR}
    results = []
    for seed in seeds:
        if made is None or not one_run:
            try:
                made = mod.answers(ctx, seed)
            except harness.Refused:
                raise
            except Exception as e:  # noqa: BLE001 — a control that crashes has failed
                made = e
        if isinstance(made, Exception):
            wrong = [{"name": "control_crashed", "want": None,
                      "got": f"{type(made).__name__}: {made}"}]
        else:
            wrong = [{k: c[k] for k in ("name", "got", "want")}
                     for c in compare(config, traffic, made, seed)
                     if not c["ok"]]
        results.append({"workload": workload, "control": control["kind"],
                        "seed": seed, "correct": not wrong, "wrong": wrong})
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    seconds = args.seconds or float(harness.read_json(manifest)["run_seconds"])
    try:
        results = run_control(
            manifest, args.workload,
            [int(s) for s in args.seeds.split(",")], seconds)
    except harness.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 3
    for r in results:
        print(json.dumps(r, default=str), flush=True)
    return 0 if all(not r["correct"] for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
