"""From a profiler trace to device busy time, idle share, the operations
that took most time and the longest idle gaps.

Two steps, so that the arithmetic can be checked without a chip:
``load_xplane`` walks an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists (``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``), and ``reduce`` turns such
lists into numbers.  ``run.py --selfcheck`` runs ``reduce`` on a small
recorded trace kept in ``benchmark/selfcheck/`` and compares with the
values stored beside it.

Definitions: a device plane is one whose name starts with
``/device:TPU:`` (or any ``/device:`` plane that is not the host's).
Busy is the union of the intervals of its operation line (``XLA Ops``;
where a plane has none, ``XLA Modules``; else every line), clipped to
the traced window; ``busy_s`` is its mean over the device planes and the
idle share is ``1 - busy_s / window_s``.  The window is the host span
``bench:trace-window`` where the trace has it, else first device event
to last.  An operation's time is its self time: its duration less the
events nested inside it on the same line, so a ``while`` that wraps a
whole level does not hide what runs inside it.  An idle gap is named by
the innermost ``bench:`` host span covering its midpoint and, after a
``>``, by the innermost other host event of a millisecond or more that
covers it (a compiler pass, a program load, a jit call), digits dropped
so that like events add up; gaps of one name are summed.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from benchmark.lib.plug import SPAN_PREFIX

WINDOW_SPAN = SPAN_PREFIX + "trace-window"
OP_LINES = ("XLA Ops", "XLA Modules")


def find_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                               "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


HOST_EVENT_MIN_NS = 1e6


def keep_host_event(name: str, duration_ns: float) -> bool:
    """Of the host's planes: the benchmark's own spans, and whatever else
    lasted a millisecond or more (compiler passes, program loads, jit
    calls), by which an idle gap is named."""
    return name.startswith(SPAN_PREFIX) or duration_ns >= HOST_EVENT_MIN_NS


def load_xplane(path: str):
    """Device planes whole; of the host's planes only the events
    ``keep_host_event`` admits."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        dev = is_device_plane(pl.name)
        lines = []
        for ln in pl.lines:
            evs = [
                [short_name(e.name) if dev else e.name,
                 float(e.start_ns), float(e.duration_ns)]
                for e in ln.events
                if dev or keep_host_event(e.name, e.duration_ns)
            ]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """A device operation's name as the breakdown carries it: the profile
    gives whole HLO lines (``%fusion.12 = s32[26738688]{0:T(1024)}
    fusion(...)``); kept are the instruction, its result shape and its
    opcode (``%fusion.12 s32[26738688] fusion``)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    if rhs.startswith("("):  # a tuple of shapes: skip to its closing bracket
        depth = end = 0
        for end, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "(tuple)", rhs[end + 1:].lstrip()
    else:
        shape, _, rest = rhs.partition(" ")
        shape = shape.split("{")[0]
    return f"{lhs} {shape} {rest.split('(')[0]}"[:80]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.split(":")[1]


def _union(intervals):
    """Sorted disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _op_line(plane):
    for want in OP_LINES:
        for ln in plane["lines"]:
            if ln["name"] == want:
                return [ln]
    return plane["lines"]


def _self_times(events):
    """``{name: self seconds}`` of one line's events (nested intervals)."""
    out = {}
    stack = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, name, d])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def reduce(trace: dict, top: int = 10) -> dict:
    devs = [p for p in trace["planes"] if is_device_plane(p["name"])]
    if not devs:
        raise ValueError("the trace has no device plane")
    host = [
        (e[1], e[1] + e[2], e[0])
        for p in trace["planes"] if not is_device_plane(p["name"])
        for ln in p["lines"] for e in ln["events"]
    ]
    spans = [h for h in host if h[2].startswith(SPAN_PREFIX)]
    others = [h for h in host if not h[2].startswith(SPAN_PREFIX)]
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if win:
        w0, w1 = win[0][0], win[0][1]
    else:
        all_ev = [e for p in devs for ln in _op_line(p) for e in ln["events"]]
        w0 = min(e[1] for e in all_ev)
        w1 = max(e[1] + e[2] for e in all_ev)
    busy, ops = [], {}
    gaps = []
    for i, p in enumerate(devs):
        evs = [e for ln in _op_line(p) for e in ln["events"]]
        u = _union(
            (max(e[1], w0), min(e[1] + e[2], w1)) for e in evs
        )
        busy.append(sum(e - s for s, e in u) / 1e9)
        for ln in _op_line(p):
            for k, v in _self_times(ln["events"]).items():
                ops[k] = ops.get(k, 0.0) + v / len(devs)
        if i == 0:
            edges = [w0] + [x for s, e in u for x in (s, e)] + [w1]
            gaps = [
                (edges[j], edges[j + 1])
                for j in range(0, len(edges), 2)
                if edges[j + 1] > edges[j]
            ]
    busy_s = sum(busy) / len(busy)
    window_s = (w1 - w0) / 1e9
    # name each gap by the innermost events over its midpoint
    mids = sorted(((s + e) / 2, e - s) for s, e in gaps)
    keys = [m for m, _ in mids]

    def innermost(events):
        best = [None] * len(mids)
        for s, e, name in events:
            for j in range(bisect.bisect_left(keys, s),
                           bisect.bisect_right(keys, e)):
                if best[j] is None or e - s < best[j][0]:
                    best[j] = (e - s, name)
        return best

    in_span = innermost(h for h in spans if h[2] != WINDOW_SPAN)
    in_other = innermost(others)
    by_name = {}
    for (mid, length), b, o in zip(mids, in_span, in_other):
        name = b[1][len(SPAN_PREFIX):] if b else "outside-benchmark-spans"
        if o:
            name += ">" + re.sub(r"\d+", "", o[1])[:48]
        by_name[name] = by_name.get(name, 0.0) + length / 1e9
    longest = max((g[1] - g[0] for g in gaps), default=0.0) / 1e9
    return {
        "devices": len(devs),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
        "device_ops": [
            [k, v] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gap_count": len(gaps),
        "idle_gap_longest_s": longest,
    }
