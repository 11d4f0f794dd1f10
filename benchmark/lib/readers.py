"""The shared per-layer readers.  ``benchmark/layer_metrics/<name>.json``
names a function of this module under ``reader`` and gives its ``params``; a metric
that needs arithmetic not found here brings its own
``benchmark/layer_metrics/<name>.py`` with a ``read(ctx, params)``.

``ctx`` holds what a traced run gathered: ``out`` (the driver's result:
``stats``, ``answers``, ``window_s``), ``trace`` (the reduced device
trace, or None), ``compiles`` (compile counters of the window),
``memory_peak_bytes``, ``peaks`` (this device's row of the peaks table),
``config`` and ``traffic``.  A reader that finds nothing to read returns
None, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import statistics


def trace_value(ctx, params):
    """A number of the reduced device trace (``idle_pct``, ...)."""
    tr = ctx.get("trace")
    return None if tr is None else tr.get(params["key"])


def memory_peak_gb(ctx, params):
    b = ctx.get("memory_peak_bytes")
    return b / 1e9 if b else None


def compiles_in_window(ctx, params):
    """Backend compilations between window start and end: compile
    requests less those the persistent cache answered."""
    c = ctx.get("compiles")
    return None if c is None else c["requests"] - c["cache_hits"]


def stat(ctx, params):
    """One of the program's own counters: ``stats[key]`` of a run that
    made one, else the median over the window's checks."""
    st = ctx["out"]["stats"]
    if params["key"] in st:
        return st[params["key"]]
    vals = [c[params["key"]] for c in st.get("checks", [])
            if params["key"] in c]
    return statistics.median(vals) if vals else None


def work_units_per_state(ctx, params):
    """The in-kernel work counters (rows expanded, lanes probed, elements
    compacted, rows appended) over the states found in the window."""
    st = ctx["out"]["stats"]
    work = [v for k, v in st.items()
            if k.startswith("work_") and k != "work_groups"]
    found = st.get("distinct_states", 0) - st.get("seed_states", 0)
    return sum(work) / found if work and found > 0 else None


def cli_outside_engine_s(ctx, params):
    """Median over the window's checks of the wall of ``cli.main`` less
    the engine's own run wall (telemetry ``result`` event): parsing,
    model build, tracing, trace reconstruction, printing."""
    st = ctx["out"]["stats"]
    pairs = [
        (w, e) for w, e in
        zip(st.get("walls_s", []), st.get("engine_walls_s", []))
        if e is not None
    ]
    return statistics.median(w - e for w, e in pairs) if pairs else None


def level_bytes(st) -> float:
    """The least bytes a window's levels have to move, from the work
    counters and the widths: every expanded row read once (W words),
    every probed lane's key read from the table once (K words), every
    appended state's row, key and two log entries written once."""
    w, k = st["state_words"], st["key_columns"]
    return 4.0 * (
        st.get("work_expand_rows", 0) * w
        + st.get("work_probe_lanes", 0) * k
        + st.get("work_append_rows", 0) * (w + k + 2)
    )


def level_kernel_hbm_pct(ctx, params):
    """Those bytes over the device time of the level program (the busy
    time of the traced window), against the device's peak bandwidth."""
    tr, st = ctx.get("trace"), ctx["out"]["stats"]
    if tr is None or "work_expand_rows" not in st or not ctx.get("peaks"):
        return None
    return (100.0 * level_bytes(st) / tr["busy_s"]
            / ctx["peaks"]["hbm_bytes_per_s"])

