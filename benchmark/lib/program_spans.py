"""What the program names from inside: its stage scopes on the device's
operations and its ``ptt:`` host spans, read from the window's trace.

The program (``pulsar_tlaplus_tpu/obs/spans.py``) traces its level
kernel under ``jax.named_scope`` names (``ptt.expand``, ``ptt.probe``,
``ptt.compact``, ``ptt.append``, ``ptt.levelctl``, ...), which land in
the ``op_name`` path XLA keeps for every operation, and runs its host
phases under ``jax.profiler.TraceAnnotation`` spans named ``ptt:<name>``.
Both lie in the ``.xplane.pb`` that ``run.py`` writes under
``<checkout>/.bench_work/trace``.  ``trace_reduce.load_xplane`` drops
short host events, and ``jax.profiler.ProfileData`` does not hand out
the stat that carries the scope, so this module reads the file itself,
once per run (the result is cached on ``ctx``), keeping for each device
operation its scope and for the host its ``ptt:`` spans.

A program that has no scopes or spans (an older commit, or executables a
compile cache handed back from before they had scopes) gives a trace
with none: every reader here then returns None, and says so, instead of
reporting 0.

Definitions.  A device operation belongs to the innermost (last)
``ptt.<stage>`` of its ``op_name`` path; its time is its self time
(``trace_reduce._self_times``: duration less what is nested in it),
clipped to the traced window.  Idle time is the window's time inside the
``ptt:check`` spans (where the trace has none: the whole window) that no
device operation covers.  It is split exactly at span boundaries, not
assigned whole by a gap's midpoint, and each piece goes to the innermost
``ptt:`` span over it; what lies only in ``ptt:check`` or ``ptt:run``
(the two containers) is unattributed.
"""

from __future__ import annotations

import bisect
import os
import re

from benchmark.lib import plug, trace_reduce

SPAN = "ptt:"
SCOPE = re.compile(r"ptt\.([a-z_]+)")
CONTAINERS = ("ptt:check", "ptt:run")
UNSCOPED = "(no scope)"
CACHE_KEY = "_program_spans"


def say(msg):
    print(f"[benchmark] {msg}", flush=True)


def trace_dir() -> str:
    # where run.py's TraceWindow writes: WORK_DIR/trace
    return os.path.join(os.path.dirname(plug.BENCH_DIR), ".bench_work",
                        "trace")


def scope_of(*texts) -> str:
    """The innermost (last) ``ptt.`` scope of an operation's ``op_name``
    path (its ``tf_op`` stat), else of its name."""
    for t in texts:
        if t and "ptt." in t:
            found = SCOPE.findall(t)
            if found:
                return found[-1]
    return UNSCOPED


# ---- the .xplane.pb, read as protobuf wire format -----------------------
# An operation's op_name path is the ``tf_op`` stat of its event *metadata*
# (XEventMetadata.stats), which jax.profiler.ProfileData does not hand out
# (it yields an event's own stats only: on a TPU ``device_offset_ps``,
# ``device_duration_ps`` and ``Time Scale Multiplier``; my chip run, PR
# 27).  So the file is read here directly.  Field numbers are those of
# tsl/profiler/protobuf/xplane.proto: XSpace.planes=1; XPlane.name=2,
# .lines=3, .event_metadata=4, .stat_metadata=5 (maps: key=1, value=2);
# XLine.name=2, .timestamp_ns=3, .events=4; XEvent.metadata_id=1,
# .offset_ps=2, .duration_ps=3; XEventMetadata.name=2, .stats=5;
# XStat.metadata_id=1, .str_value=5, .ref_value=7; XStatMetadata.name=2.


def _varint(buf, i):
    r = shift = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << shift
        if b < 0x80:
            return r, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` pair for a length-delimited field; fixed-width
    fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wt == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an .xplane.pb")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, span):
    """``(name, [line spans], {metadata id: (name, tf_op)})``."""
    name, lines, emeta, smeta = "", [], [], {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            k, val = _map_entry(buf, v)
            if val is not None:
                smeta[k] = next(
                    (_text(buf, x) for g, x in _fields(buf, *val) if g == 2),
                    "")
    tf_op_ids = {k for k, n in smeta.items() if n == "tf_op"}
    meta = {}
    for m in emeta:
        k, val = _map_entry(buf, m)
        if val is None:
            continue
        mname, tf_op = "", ""
        for g, x in _fields(buf, *val):
            if g == 2:
                mname = _text(buf, x)
            elif g == 5 and tf_op_ids:
                sid, sval = 0, ""
                for h, y in _fields(buf, *x):
                    if h == 1:
                        sid = y
                    elif h == 5:
                        sval = _text(buf, y)
                    elif h == 7:
                        sval = smeta.get(y, "")
                if sid in tf_op_ids:
                    tf_op = sval
        meta[k] = (mname, tf_op)
    return name, lines, meta


def _line(buf, span):
    """``(name, [(metadata id, start_ns, duration_ns)])``."""
    name, t0_ns, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        out.append((mid, t0_ns + off / 1e3, dur / 1e3))
    return name, out


def walk_xplane(path: str) -> dict:
    """``{"device": [[(scope, start_ns, dur_ns), ...] per device plane],
    "spans": [(start, end, name)], "window": (w0, w1) or None,
    "host": [(start, end, name)] of a millisecond or more}``."""
    with open(path, "rb") as f:
        buf = f.read()
    device, spans, host, window = [], [], [], None
    for f, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        pname, lines, meta = _plane(buf, v)
        if trace_reduce.is_device_plane(pname):
            named = dict(_line(buf, ln) for ln in lines)
            ops = next((named[n] for n in trace_reduce.OP_LINES
                        if n in named), None)
            if ops is not None:
                scope = {k: scope_of(tf, nm) for k, (nm, tf) in meta.items()}
                device.append([(scope.get(mid, UNSCOPED), s, d)
                               for mid, s, d in ops])
            continue
        for ln in lines:
            for mid, s, d in _line(buf, ln)[1]:
                name = meta.get(mid, ("", ""))[0]
                if name.startswith(SPAN):
                    spans.append((s, s + d, name))
                elif name == trace_reduce.WINDOW_SPAN:
                    window = (s, s + d)
                elif (d >= trace_reduce.HOST_EVENT_MIN_NS
                      and not name.startswith(plug.SPAN_PREFIX)):
                    host.append((s, s + d, name))
    return {"device": device, "spans": spans, "window": window,
            "host": host}


def load(ctx, walked=None):
    """The walked trace of this run, reduced once and kept on ``ctx``;
    None where the run wrote no trace.  ``walked`` stands in for the
    file (the tests' recorded sample)."""
    if CACHE_KEY in ctx:
        return ctx[CACHE_KEY]
    if walked is None:
        try:
            walked = walk_xplane(trace_reduce.find_xplane(trace_dir()))
        except FileNotFoundError:
            walked = None
    ctx[CACHE_KEY] = None if walked is None else reduce(walked)
    return ctx[CACHE_KEY]


def _clip(s, e, win):
    return (s, e) if win is None else (max(s, win[0]), min(e, win[1]))


def _busy(events, win):
    """Sorted disjoint union of one plane's operations inside ``win``."""
    return trace_reduce._union(
        _clip(s, s + d, win) for _n, s, d in events)


def _covered(union, starts, prefix, s, e):
    """Length of ``[s, e)`` that ``union`` covers."""
    if e <= s or not union:
        return 0.0
    i = bisect.bisect_right(starts, s) - 1
    j = bisect.bisect_left(starts, e)
    i = max(i, 0)
    total = prefix[j] - prefix[i]
    if union[i][0] < s:  # the first interval starts before s
        total -= min(s, union[i][1]) - union[i][0]
    if j > 0 and union[j - 1][1] > e:  # the last one ends after e
        total -= union[j - 1][1] - max(e, union[j - 1][0])
    return max(total, 0.0)


def innermost_segments(spans):
    """``[(start, end, name)]``: the time under ``spans`` (``(start,
    end, name)``, nested) cut so that each piece carries the innermost
    span over it."""
    out, stack, cur = [], [], None

    def close(upto):
        nonlocal cur
        while stack and stack[-1][1] <= upto:
            _s, e, name = stack.pop()
            if e > cur:
                out.append((cur, e, name))
                cur = e

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])  # a child cannot outlast its parent
            if s > cur:
                out.append((cur, s, stack[-1][2]))
        if e <= s:
            continue
        cur = s if cur is None or not stack else max(cur, s)
        stack.append((s, e, name))
    close(float("inf"))
    return out


def reduce(walked: dict) -> dict:
    """Per-scope device seconds, and idle seconds by innermost span."""
    win = walked["window"]
    planes = walked["device"]
    by_scope = {}
    for events in planes:
        clipped = [
            [n, s2, e2 - s2]
            for n, s, d in events
            for s2, e2 in [_clip(s, s + d, win)] if e2 > s2
        ]
        for scope, secs in trace_reduce._self_times(clipped).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + secs / len(planes)
    out = {
        "device_planes": len(planes),
        "scope_s": by_scope,
        "device_self_s": sum(by_scope.values()),
        "scoped": any(k != UNSCOPED for k in by_scope),
        "span_count": len(walked["spans"]),
        "idle_by_span_s": None,
        "idle_by_span_and_event_s": None,
    }
    if not planes or not walked["spans"]:
        return out
    union = _busy(planes[0], win)
    starts = [u[0] for u in union]
    prefix = [0.0]
    for s, e in union:
        prefix.append(prefix[-1] + e - s)
    spans = [
        (s2, e2, name) for s, e, name in walked["spans"]
        for s2, e2 in [_clip(s, e, win)] if e2 > s2
    ]
    checks = [sp for sp in spans if sp[2] == "ptt:check"]
    if not checks and win is not None:
        # a program entered below the CLI: the window stands in for it
        spans.append((win[0], win[1], "ptt:check"))
    idle = {}
    segments = innermost_segments(spans)
    for s, e, name in segments:
        gap = (e - s) - _covered(union, starts, prefix, s, e)
        if gap > 0:
            idle[name] = idle.get(name, 0.0) + gap / 1e9
    out["idle_by_span_s"] = idle
    # the same idle time one level further down: under each innermost
    # span, the innermost other host event of a millisecond or more
    # (a jit call, a lowering, a compiler pass, a cache load)
    hsegs = innermost_segments(walked["host"])
    hstarts = [h[0] for h in hsegs]
    deep = {}

    def add(span_name, ev, s, e):
        gap = (e - s) - _covered(union, starts, prefix, s, e)
        if gap > 0:
            key = span_name[len(SPAN):] + (
                ">" + re.sub(r"\d+", "", ev)[:48] if ev else "")
            deep[key] = deep.get(key, 0.0) + gap / 1e9

    for s, e, name in segments:
        i = max(bisect.bisect_right(hstarts, s) - 1, 0)
        pos = s
        while i < len(hsegs) and hsegs[i][0] < e:
            a, b = max(hsegs[i][0], s), min(hsegs[i][1], e)
            if b > a:
                if a > pos:
                    add(name, "", pos, a)
                add(name, hsegs[i][2], a, b)
                pos = b
            i += 1
        if e > pos:
            add(name, "", pos, e)
    out["idle_by_span_and_event_s"] = deep
    return out


def stage_seconds(ctx, stage: str):
    """Device self seconds under ``ptt.<stage>``; None, said aloud,
    where the trace holds no ``ptt.`` scope at all."""
    sp = load(ctx)
    if sp is None or not sp["device_planes"]:
        return None
    if not sp["scoped"]:
        say(f"stage_device_s.{stage}: the trace holds no ptt. scope (the "
            "program has none, or a compile cache handed back executables "
            "from before it had them): nothing reported")
        return None
    return sp["scope_s"].get(stage, 0.0)


def unscoped_pct(ctx):
    sp = load(ctx)
    if sp is None or not sp["device_planes"] or not sp["scoped"]:
        return None
    say("device seconds by scope: " + ", ".join(
        f"{k} {v:.4f}" for k, v in
        sorted(sp["scope_s"].items(), key=lambda kv: -kv[1])))
    total = sp["device_self_s"]
    return (100.0 * sp["scope_s"].get(UNSCOPED, 0.0) / total
            if total > 0 else None)


def idle_unattributed_pct(ctx):
    """Of the idle time inside the checks, the share under no phase span
    and no ``ptt:cli.*`` span.  Prints idle seconds by innermost span."""
    sp = load(ctx)
    if sp is None or not sp["idle_by_span_s"]:
        if sp is not None and sp["device_planes"]:
            say("idle_unattributed_pct: the trace holds no ptt: span: "
                "nothing reported")
        return None
    idle = sp["idle_by_span_s"]
    total = sum(idle.values())
    say("idle seconds by innermost ptt: span: " + ", ".join(
        f"{k[len(SPAN):]} {v:.4f}" for k, v in
        sorted(idle.items(), key=lambda kv: -kv[1])))
    say("idle seconds by span > host event: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(
            sp["idle_by_span_and_event_s"].items(),
            key=lambda kv: -kv[1])[:16]))
    if set(idle) <= set(CONTAINERS) or total <= 0:
        return None  # only the containers: the engine has no phase spans
    loose = sum(v for k, v in idle.items() if k in CONTAINERS)
    return 100.0 * loose / total
