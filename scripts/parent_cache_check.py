#!/usr/bin/env python3
"""The parent-cache check: did a change move any program's HLO?

JAX's persistent compile cache is keyed on a program's HLO and its name
(PERF.md §6, PR 30).  So a cache directory filled by the PARENT tree and
then presented to the CHANGE answers the question a refactor of traced
code has to answer: every program the change builds is either found
there — same HLO, same name, the executable the parent ran — or it is
written as a new entry, whose file name starts with the program's name.

    python scripts/parent_cache_check.py --parent /path/to/parent-tree \\
        [--paths level,stage,leak] [--cache-root DIR] [--json FILE]

For each path: the parent runs it against an empty directory of the
path's own (the directory's path is part of the key, so both sides get
the same one), then the change — this checkout — runs it against what
the parent left.  The new entries are the programs that missed; the
goal is none.  Exit code 1 if any path missed.

This launcher never imports JAX: the children run one after the other
and each owns the chip for its lifetime.  On the CPU give the children
their devices yourself (``JAX_PLATFORMS=cpu XLA_FLAGS=
--xla_force_host_platform_device_count=8`` for ``workers4``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CLI = ["-m", "pulsar_tlaplus_tpu.cli", "check", "specs/compaction.tla"]
_SMALL = ["-config", "specs/compaction.cfg"]

# name -> (argv after the interpreter, extra environment)
PATHS = {
    # the two claimed cells' own command lines
    "level253k": (_CLI + ["-config", "specs/compaction_253k.cfg"], {}),
    "leak": (_CLI + _SMALL + ["-invariant", "CompactedLedgerLeak"], {}),
    # the other ways through the changed code (PR 31's seven, at the
    # shipped binding: 45,198 states)
    "level": (_CLI + _SMALL + ["-fuse", "level"], {}),
    "stage": (_CLI + _SMALL + ["-fuse", "stage"], {}),
    "workers4": (_CLI + _SMALL + ["-workers", "4"], {}),
    "hbm": (_CLI + _SMALL + ["-hbm-budget", "24M"], {}),
    "termination": (_CLI + _SMALL + ["-property", "Termination"], {}),
    "shift": (_CLI + _SMALL, {"PTT_COMPACT_MATERIALIZE": "shift"}),
    # the spec->kernel compiler's programs (PR 49: the cell cli-compiled)
    "compiled": (_CLI + _SMALL + ["-compile"], {}),
    # the benchmark's device-bound program (needs the chip)
    "scaled": (
        ["benchmark/run.py", "--workload", "scaled-window", "--seed",
         "3000000433", "--seconds", "40", "--trace", "0"],
        {},
    ),
}


def entries(cache_dir: str) -> set:
    """The cache's executables (JAX also keeps ``-atime`` files)."""
    if not os.path.isdir(cache_dir):
        return set()
    return {f for f in os.listdir(cache_dir) if f.endswith("-cache")}


def program_of(entry: str) -> str:
    """``jit_ptt_level-<hash>-cache`` -> ``jit_ptt_level``."""
    return entry.rsplit("-", 2)[0]


def run(tree: str, argv, env_extra, cache_dir: str) -> dict:
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir, **env_extra)
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable] + argv, cwd=tree, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    return {
        "rc": p.returncode,
        "seconds": round(time.perf_counter() - t0, 3),
        "stdout_tail": p.stdout[-400:],
        "stderr_tail": "\n".join(
            ln for ln in p.stderr.splitlines()
            if "cpu_aot_loader" not in ln
        )[-400:],
    }


def check_path(name: str, parent: str, cache_root: str) -> dict:
    argv, env_extra = PATHS[name]
    cache_dir = os.path.join(cache_root, name)
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    side_p = run(parent, argv, env_extra, cache_dir)
    filled = entries(cache_dir)
    side_c = run(REPO, argv, env_extra, cache_dir)
    missed = sorted(entries(cache_dir) - filled)
    return {
        "path": name,
        "parent_entries": len(filled),
        "change_missed": len(missed),
        "missed_programs": sorted({program_of(e) for e in missed}),
        "missed_entries": missed,
        "parent": side_p,
        "change": side_c,
        # a side that failed proves nothing about the other
        "ok": (
            not missed and bool(filled)
            and side_p["rc"] == side_c["rc"]
        ),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", required=True,
                    help="a checkout of the parent commit (git archive)")
    ap.add_argument("--paths", default="level253k,leak",
                    help="comma-separated, of: " + ", ".join(PATHS))
    ap.add_argument("--cache-root",
                    default=os.path.join(REPO, ".bench_work", "pcc"),
                    help="where the per-path cache directories go")
    ap.add_argument("--json", default=None,
                    help="also write the results to this file")
    args = ap.parse_args(argv)
    names = [n for n in args.paths.split(",") if n]
    unknown = [n for n in names if n not in PATHS]
    if unknown:
        ap.error(f"unknown path(s) {unknown}; known: {sorted(PATHS)}")
    results = []
    for name in names:
        r = check_path(name, os.path.abspath(args.parent), args.cache_root)
        results.append(r)
        print(json.dumps({
            k: r[k] for k in (
                "path", "parent_entries", "change_missed",
                "missed_programs", "ok",
            )
        } | {
            "parent_rc": r["parent"]["rc"],
            "change_rc": r["change"]["rc"],
            "parent_s": r["parent"]["seconds"],
            "change_s": r["change"]["seconds"],
        }), flush=True)
        if not r["ok"]:
            sys.stderr.write(
                f"[{name}] parent stderr: {r['parent']['stderr_tail']}\n"
                f"[{name}] change stderr: {r['change']['stderr_tail']}\n"
            )
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(results, f, indent=1)
    return 0 if all(r["ok"] for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
