#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the checker still starts on
the chip.

Drives the system's main path once, on one TPU, through the entry
points a user calls, and checks every result against the repo's own
references.  One process; nothing here sets ``JAX_PLATFORMS``.  Phases:

- ``device``      fails unless JAX's first device is a TPU (then nothing
                  else runs and no result is printed);
- ``cli-shipped`` ``cli check specs/compaction.tla`` = 45,198 states /
                  diameter 20 / exit 0; with ``-invariant
                  CompactedLedgerLeak`` = exit 1 and a 12-state trace;
                  the same counterexample from a ``DeviceChecker`` run
                  replayed step by step through ``ref/pyeval``;
- ``flagship``    ``bench.scaled_config()`` (618-bit states, 64-bit
                  fingerprints) on ``DeviceChecker(**BENCH_CHECKER_KW)``
                  at full width, host-seeded exactly as ``bench.py``
                  runs it, ``max_states`` cut so the run stops inside
                  level 7: level 6 must be +17,150,616 (17,787,334
                  cumulative — native-checker ground truth, BASELINE.md)
                  with no fallback taken;
- ``sharded``     with >= 4 devices, ``cli check -workers 4`` on the
                  producer-on config = 253,361 states / diameter 23;
                  with fewer, a named skip.

Times, peak memory and states reached are printed as observations of
this run, not as metrics.  Exit code 0 and a last stdout line
``{"ok": true, "device": {...}}`` only when every phase passed.

    python3 chip_smoke.py [--max-states N]
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import re
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# native-checker ground truth at the flagship constants (BASELINE.md
# "Level-size ground truth"): the host seed covers levels 1-5
SEED_STATES, SEED_LEVELS = 636_718, 5
LEVEL6_NEW, LEVEL6_CUM = 17_150_616, 17_787_334


class Failed(Exception):
    """A phase's check did not hold."""


def check(cond, msg):
    if not cond:
        raise Failed(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def run_cli(argv):
    """``cli.main(argv)`` with its stdout captured: ``(rc, text)``.
    ``cli`` reports usage and set-up errors through ``sys.exit``."""
    from pulsar_tlaplus_tpu import cli

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        try:
            rc = cli.main(argv)
        except SystemExit as e:
            rc = e.code if isinstance(e.code, int) else 2
            buf.write(f"\n(sys.exit: {e.code})\n")
    text = buf.getvalue()
    # the interpreter fallback (cli._check_compiled_spec) would pass a
    # count check without the device having checked anything, and an
    # HBM recovery (cli._report's note) one at degraded capacity
    check(
        "falling back to the generic interpreter" not in text,
        f"cli {argv}: fell back to the host interpreter",
    )
    check(
        "recovered from device-memory exhaustion" not in text,
        f"cli {argv}: ran out of device memory and recovered",
    )
    return rc, text


def counts_of(text):
    m = re.search(
        r"(\d+) distinct states found, search depth \(diameter\) (\d+)",
        text,
    )
    check(m is not None, f"no result line in cli output:\n{text[-800:]}")
    return int(m.group(1)), int(m.group(2))


# ---------------------------------------------------------------- phases


def phase_device(jax):
    from pulsar_tlaplus_tpu.utils.device import setup_compile_cache

    cache_dir = setup_compile_cache()
    dev = jax.devices()[0]
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — version report only
        libtpu = "?"
    say(
        "device",
        f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache={cache_dir}",
    )
    check(
        dev.platform == "tpu",
        f"JAX found no TPU (first device is {dev.platform!r})",
    )


def phase_cli_shipped(jax):
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ref import pyeval as pe

    spec = os.path.join(ROOT, "specs", "compaction.tla")
    rc, text = run_cli(["check", spec])
    n, d = counts_of(text)
    say("cli-shipped", f"check: exit {rc}, {n} states, diameter {d}")
    check((rc, n, d) == (0, 45198, 20), "shipped cfg != 45,198 / 20 / exit 0")

    rc, text = run_cli(["check", spec, "-invariant", "CompactedLedgerLeak"])
    steps = len(re.findall(r"^State \d+:", text, re.M))
    say(
        "cli-shipped",
        f"check -invariant CompactedLedgerLeak: exit {rc}, "
        f"{steps}-state trace",
    )
    check(
        "Invariant CompactedLedgerLeak is violated" in text,
        "leak invariant not reported violated",
    )
    check((rc, steps) == (1, 12), "leak counterexample != exit 1 / depth 12")

    # the same counterexample from the engine, replayed through the
    # Python reference: every step a real transition, only the last
    # state violating (the CLI's engine tiers)
    c = pe.SHIPPED_CFG
    r = DeviceChecker(
        CompactionModel(c), invariants=("CompactedLedgerLeak",),
        sub_batch=4096, visited_cap=1 << 16, frontier_cap=1 << 14,
    ).run()
    check(r.violation == "CompactedLedgerLeak", f"violation={r.violation}")
    check(len(r.trace) == 12 and r.diameter == 12, "engine trace depth != 12")
    inv = pe.INVARIANTS["CompactedLedgerLeak"]
    check(r.trace[0] in set(pe.initial_states(c)), "trace[0] not initial")
    for i, (s, act, t) in enumerate(
        zip(r.trace, r.trace_actions, r.trace[1:])
    ):
        name = act if isinstance(act, str) else pe.ACTION_NAMES[act]
        succ = [
            st for a, st in pe.successors(c, s)
            if pe.ACTION_NAMES[a] == name
        ]
        check(t in succ, f"step {i + 1} ({name}) is not a transition")
        check(inv(c, s), f"state {i + 1} violates before the end")
    check(not inv(c, r.trace[-1]), "final state does not violate")
    say("cli-shipped", "engine trace replays through ref/pyeval (12 steps)")


def phase_flagship(jax, max_states):
    import bench
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel

    model = CompactionModel(bench.scaled_config())
    kw = dict(bench.BENCH_CHECKER_KW, max_states=max_states)
    ck = DeviceChecker(model, progress=True, **kw)
    say(
        "flagship",
        f"{model.layout.total_bits}-bit states / {model.layout.W} words, "
        f"{model.A} lanes, sub_batch={ck.G}, table 2^"
        f"{ck.TCAP.bit_length() - 1} slots, max_states={max_states}",
    )
    # bench.py's set-up: the Python oracle enumerates the narrow early
    # levels on a thread while the device programs compile
    box = {}

    def _seed():
        try:
            box["seed"] = model.host_seed(
                max_level_states=800_000, max_total=1_000_000
            )
            ck.prestage_seed(box["seed"])
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread
            box["err"] = e

    t0 = time.time()
    seed_t = threading.Thread(target=_seed)
    seed_t.start()
    try:
        compile_s = ck.warmup(seed=True)
    finally:
        seed_t.join()
    if "err" in box:
        raise box["err"]
    seed = box["seed"]
    setup_s = time.time() - t0
    say(
        "flagship",
        f"set-up {setup_s:.1f}s (compile warmup {compile_s:.1f}s; "
        f"seed {len(seed[0])} states / {len(seed[3])} levels)",
    )
    check(
        (len(seed[0]), len(seed[3])) == (SEED_STATES, SEED_LEVELS),
        "host seed != 636,718 states / 5 levels",
    )
    r = ck.run(seed=seed)
    sizes = list(r.level_sizes)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(
        "flagship",
        f"run {r.wall_s:.1f}s: {r.distinct_states} states, "
        f"{len(sizes)} levels, stop_reason={r.stop_reason}, "
        f"peak_bytes_in_use={peak}",
    )
    check(len(sizes) >= 7, f"run ended before level 7: {sizes}")
    say(
        "flagship",
        f"level 6: +{sizes[5]} (cumulative {sum(sizes[:6])})",
    )
    check(
        (sizes[5], sum(sizes[:6])) == (LEVEL6_NEW, LEVEL6_CUM),
        "level 6 != +17,150,616 / 17,787,334 (native ground truth)",
    )
    check(
        r.truncated and r.stop_reason == "max_states",
        f"stop_reason={r.stop_reason} (an HBM stop is a failure)",
    )
    check(r.hbm_recovered == 0, f"hbm_recovered={r.hbm_recovered}")
    check(ck.fuse == "level", f"fell back to fuse={ck.fuse}")
    check(
        ck.last_stats.get("fpset_failures") == 0,
        f"fpset_failures={ck.last_stats.get('fpset_failures')}",
    )
    check(r.violation is None and not r.deadlock, "unexpected violation")


def phase_sharded(jax):
    n = len(jax.devices())
    if n < 4:
        return f"skipped ({n} device)"
    rc, text = run_cli([
        "check", os.path.join(ROOT, "specs", "compaction.tla"),
        "-config", os.path.join(ROOT, "specs", "compaction_253k.cfg"),
        "-workers", "4",
    ])
    states, diam = counts_of(text)
    say("sharded", f"-workers 4: exit {rc}, {states} states, diameter {diam}")
    check("-sharded 4" in text, "-workers 4 did not run the 4-device mesh")
    check(
        (rc, states, diam) == (0, 253361, 23),
        "-workers 4 != 253,361 / 23 / exit 0",
    )
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--max-states", type=int, default=32_000_000,
        help="flagship state cap: past level 6 (17,787,334), inside "
        "level 7 (default 32M)",
    )
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    t_all = time.time()
    import jax

    try:
        phase_device(jax)
    except Failed as e:
        print(f"device: FAIL — {e}", file=sys.stderr, flush=True)
        return 2
    print("device: PASS", flush=True)
    dev = jax.devices()[0]
    phases = [
        ("cli-shipped", lambda: phase_cli_shipped(jax)),
        ("flagship", lambda: phase_flagship(jax, args.max_states)),
        ("sharded", lambda: phase_sharded(jax)),
    ]
    failed = []
    for name, fn in phases:
        gc.collect()  # the previous phase's device buffers
        t0 = time.time()
        try:
            note = fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest
            if not isinstance(e, Failed):
                traceback.print_exc()
            print(
                f"{name}: FAIL ({time.time() - t0:.1f}s) — "
                f"{type(e).__name__}: {e}",
                flush=True,
            )
            failed.append(name)
            continue
        print(f"{name}: {note or 'PASS'} ({time.time() - t0:.1f}s)",
              flush=True)
    print(f"total {time.time() - t_all:.1f}s", flush=True)
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
