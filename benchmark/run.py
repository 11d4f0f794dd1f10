#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1
    python3 benchmark/run.py --selfcheck

Knows no cell, configuration, driver, comparison or metric by name:
``BENCHMARK.json`` names the cell's configuration file and traffic mix,
the traffic file names its driver (``benchmark/drivers/<name>.py``), the
configuration names its reference comparison
(``benchmark/comparisons/<name>.py``), and each per-layer metric has a
reader of its own under ``benchmark/layer_metrics/``.  There is no table
of names in the code: ``benchmark/lib/plug.py`` finds each by its file.

A driver is a class ``Driver(config, traffic, root, work_dir, trace,
seed)`` with ``setup(seconds)`` (everything up to the window: the
program's imports, warm-up of the cell's own shapes, the host seed),
``window(seconds)`` (the measured work) and ``after_window(out)`` (what
the comparison needs from the device, fetched once the clock has stopped
and peak memory has been read).  ``window`` returns ``window_s``,
``end_to_end`` (``{metric: value}``, taken by the benchmark itself),
``attempted``, ``failed``, ``answers`` (what the timed path produced)
and ``stats`` (the program's own counters, for the per-layer readers).
A comparison is ``compare(config, traffic, answers, seed)`` returning
checks (``benchmark/lib/reference.py``).  The program is entered only
through what its users call: ``pulsar_tlaplus_tpu.cli.main`` and
``DeviceChecker``.

Fails, printing no result, unless JAX's first device is a TPU whose
``device_kind`` is in ``benchmark/lib/peaks.json`` and the host has the
chips the cell asks for.  The last line of standard output is the result:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.  ``--selfcheck`` recomputes
the trace reduction on a recorded sample and needs no chip.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORK_DIR = os.path.join(ROOT, ".bench_work")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Refused(Exception):
    """The run cannot be made here; no result is printed."""


def say(msg):
    print(f"[benchmark] {msg}", flush=True)


def read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(manifest_path, workload):
    """``(manifest, cell, config, traffic)`` for a workload name."""
    man = read_json(manifest_path)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in {manifest_path}")
    cell = cells[workload]
    cfg_entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    # a manifest's paths are relative to the checkout; a traffic mix lies
    # in traffic/ beside the directory of its configuration
    cfg_file = os.path.join(ROOT, cfg_entry["file"])
    traffic_file = os.path.join(
        os.path.dirname(os.path.dirname(cfg_file)), "traffic",
        cell["traffic"] + ".json")
    return man, cell, read_json(cfg_file), read_json(traffic_file)


def check_device(cell, require_tpu=True):
    """The device as JAX reports it and its row of the peaks table.
    ``require_tpu=False`` is the hook of the benchmark's own tests, which
    drive the rest of a run on the CPU; the command line never sets it."""
    import jax

    devs = jax.devices()
    d = devs[0]
    peaks = read_json(os.path.join(HERE, "lib", "peaks.json"))["devices"]
    if require_tpu:
        if d.platform != "tpu":
            raise Refused(f"JAX found no TPU (first device is {d.platform!r})")
        if d.device_kind not in peaks:
            raise Refused(
                f"device_kind {d.device_kind!r} is not in the peaks table"
            )
        if len(devs) < cell["chips"]:
            raise Refused(
                f"the cell needs {cell['chips']} chips; JAX has {len(devs)}"
            )
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    return device, peaks.get(d.device_kind, {})


class CompileCounter:
    """``jax.monitoring`` compile requests and persistent-cache hits,
    counted only while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.requests = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if self.on and event == COMPILE_EVENT:
            self.requests += 1

    def _event(self, event, **_kw):
        if self.on and event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def counts(self):
        return {"requests": self.requests, "cache_hits": self.cache_hits}


class TraceWindow:
    """A profiler trace of the whole window, with the host's Python
    tracer off (the benchmark's own spans and JAX's annotations name the
    idle gaps), under the one host span the reduction takes as the
    window."""

    def __init__(self, trace_dir):
        self.dir = trace_dir

    def start(self):
        import jax

        from benchmark.lib.trace_reduce import WINDOW_SPAN

        shutil.rmtree(self.dir, ignore_errors=True)
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=po)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()

    def stop(self):
        import jax

        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def memory_peak_bytes(n_chips):
    import jax

    peak = 0
    for d in jax.devices()[:max(n_chips, 1)]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def read_layer_metric(name, ctx):
    """The metric's own reader: ``layer_metrics/<name>.py`` with
    ``read(ctx, params)``, else ``<name>.json`` naming a function of
    ``lib/readers.py`` and its parameters."""
    from benchmark.lib import plug, readers

    if os.path.exists(plug.path_of("layer_metrics", name)):
        return plug.load_file("layer_metrics", name).read(ctx, {})
    desc = read_json(plug.path_of("layer_metrics", name, ".json"))
    return getattr(readers, desc["reader"])(ctx, desc.get("params", {}))


def metrics_of(man, cell, group):
    return [
        m for m in man[group]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]


def run_cell(manifest_path, workload, seed, seconds, trace,
             require_tpu=True):
    """Drive one run; returns the result line as a dict."""
    from benchmark.lib import plug, reference, trace_reduce

    man, cell, config, traffic = load_cell(manifest_path, workload)
    try:
        from pulsar_tlaplus_tpu.utils.device import setup_compile_cache
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}") from e
    import jax

    device, peaks = check_device(cell, require_tpu)
    cache_dir = setup_compile_cache()
    say(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']} ({traffic['driver']}), seed {seed}, "
        f"{seconds} s, trace {trace}; device {device}; compile cache "
        f"{cache_dir}")
    os.makedirs(WORK_DIR, exist_ok=True)
    counter = CompileCounter()
    driver = plug.load_file("drivers", traffic["driver"]).Driver(
        config, traffic, ROOT, WORK_DIR, trace, seed
    )
    driver.setup(seconds)
    setup_s = time.perf_counter() - T_PROCESS
    say(f"set-up {setup_s:.1f} s")

    tracer = None
    if trace:
        tracer = TraceWindow(os.path.join(WORK_DIR, "trace"))
        tracer.start()
    counter.on = True
    try:
        out = driver.window(seconds)
    finally:
        counter.on = False
        if tracer is not None:
            tracer.stop()
    peak = memory_peak_bytes(cell["chips"])
    say(f"window {out['window_s']:.3f} s of {seconds} s allowed; "
        f"attempted {out['attempted']}, failed {out['failed']}")
    if "walls_s" in out["stats"]:
        say("walls of the window's checks: "
            + ", ".join(f"{w:.4f}" for w in out["stats"]["walls_s"]))
    driver.after_window(out)

    # correct: what the window produced against the plain reference
    t_ref = time.perf_counter()
    kind = config["reference"]["comparison"]
    if isinstance(kind, dict):
        kind = kind[traffic["expect"]]
    checks = plug.load_file("comparisons", kind).compare(
        config, traffic, out["answers"], seed)
    if out.get("fixed_work"):
        # fixed work has to end inside the seconds allowed; a window that
        # is filled on the clock ends when its last check does
        checks.append(reference.chk(
            "window_inside_seconds", out["window_s"] <= seconds, True))
    checks.append(reference.chk("failed", out["failed"], 0))
    for c in checks:
        say(f"compare[{kind}] {c['name']}: got {c['got']!r}, want "
            f"{c['want']!r}, limit on the difference {c['limit']}: "
            f"{'ok' if c['ok'] else 'WRONG'}")
    correct = all(c["ok"] for c in checks)
    say(f"reference comparison took {time.perf_counter() - t_ref:.1f} s")

    result = {
        "correct": bool(correct),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
    }
    if not trace:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in metrics_of(man, cell, "end_to_end"):
            result["metrics"][m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"]}
        return result
    xplane = trace_reduce.load_xplane(trace_reduce.find_xplane(tracer.dir))
    if not require_tpu and not any(
            trace_reduce.is_device_plane(p["name"]) for p in xplane["planes"]):
        # the tests' CPU runs have no device plane: stand the host's own
        # spans in for one, so that the rest of the traced path runs
        xplane["planes"].append({"name": "/device:TEST:0", "lines": [
            ln for p in xplane["planes"] for ln in p["lines"]]})
    reduced = trace_reduce.reduce(xplane)
    ctx = {
        "out": out, "trace": reduced, "compiles": counter.counts(),
        "memory_peak_bytes": peak, "peaks": peaks, "config": config,
        "traffic": traffic, "setup_s": setup_s,
    }
    for m in metrics_of(man, cell, "per_layer"):
        v = read_layer_metric(m["name"], ctx)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    result["device"]["busy_s"] = reduced["busy_s"]
    result["device"]["window_s"] = reduced["window_s"]
    result["breakdown"] = {
        "device_ops": reduced["device_ops"],
        "idle_gaps": reduced["idle_gaps"],
    }
    say(f"trace: {reduced['devices']} device plane(s), window "
        f"{reduced['window_s']:.3f} s, busy {reduced['busy_s']:.3f} s, "
        f"{reduced['idle_gap_count']} idle gaps, longest "
        f"{reduced['idle_gap_longest_s']:.4f} s; compiles {counter.counts()}")
    return result


def selfcheck() -> int:
    """Recompute the trace reduction on the recorded sample."""
    from benchmark.lib import trace_reduce

    d = os.path.join(HERE, "selfcheck")
    want = read_json(os.path.join(d, "expected.json"))
    got = trace_reduce.reduce(read_json(os.path.join(d, "trace_sample.json")),
                              top=len(want["device_ops"]))
    bad = 0
    for k, w in want.items():
        g = got[k]
        if isinstance(w, float):
            ok = abs(g - w) <= 1e-9 * max(1.0, abs(w))
        elif isinstance(w, list):
            ok = len(g) == len(w) and all(
                a[0] == b[0] and abs(a[1] - b[1]) <= 1e-9 * max(1.0, abs(b[1]))
                for a, b in zip(g, w))
        else:
            ok = g == w
        print(f"selfcheck {k}: got {g!r}, stored {w!r}: "
              f"{'ok' if ok else 'WRONG'}")
        bad += not ok
    print(json.dumps({"selfcheck_ok": bad == 0}))
    return 1 if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--selfcheck", action="store_true")
    args = ap.parse_args(argv)
    if args.selfcheck:
        return selfcheck()
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    if args.workload is None:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(read_json(manifest)["run_seconds"])
    try:
        result = run_cell(manifest, args.workload, args.seed, args.seconds,
                          args.trace)
    except Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr, flush=True)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
