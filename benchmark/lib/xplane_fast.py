"""Device seconds by ``ptt.`` scope for a trace of millions of events.

``program_spans`` decodes the ``.xplane.pb`` varint by varint in Python
and reduces it event by event: a second or two on a one-chip cell, but
the four-chip cell's check makes 9,900 dispatches on four planes, 7.0M
device and 2.1M host events in a 450 MB file: some 57 s of walking (6.2
us an event) and 16 s of reducing, in a traced run that has 360 s in all
(my chip runs, PR 29).  Here
protobuf's own parser (``google.protobuf``, the ``upb`` C extension the
installation has) decodes the device planes against a descriptor built
in code from the same field numbers (those of
tsl/profiler/protobuf/xplane.proto that ``program_spans`` lists); the
host's plane is searched for the window's span alone, and numpy does
the arithmetic.  ``scope_table`` gives the part of
``program_spans.reduce(program_spans.walk_xplane(path))`` that the
device planes decide (``scope_s``, ``device_self_s``, ``scoped``,
``device_planes``; ``benchmark/tests/test_workers4.py`` holds the two to
each other) and no idle split.  ``prime`` puts it where
``program_spans.load`` caches its own, so ``stage_seconds`` and
``unscoped_pct`` then read as they do without it.  Where
``google.protobuf`` or numpy is missing, ``prime`` does nothing and
those readers walk the file themselves.
"""

from __future__ import annotations

from benchmark.lib import program_spans, trace_reduce

_PLANE = None


def plane_class():
    """The message class of ``XPlane``, with only the fields read here
    (an event's own stats stay unparsed)."""
    global _PLANE
    if _PLANE is not None:
        return _PLANE
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    t = descriptor_pb2.FieldDescriptorProto
    one, many = t.LABEL_OPTIONAL, t.LABEL_REPEATED
    messages = {
        "XStat": [("metadata_id", 1, t.TYPE_INT64, one, None),
                  ("str_value", 5, t.TYPE_BYTES, one, None),
                  ("ref_value", 7, t.TYPE_UINT64, one, None)],
        "XEvent": [("metadata_id", 1, t.TYPE_INT64, one, None),
                   ("offset_ps", 2, t.TYPE_INT64, one, None),
                   ("duration_ps", 3, t.TYPE_INT64, one, None)],
        "XLine": [("name", 2, t.TYPE_BYTES, one, None),
                  ("timestamp_ns", 3, t.TYPE_INT64, one, None),
                  ("events", 4, t.TYPE_MESSAGE, many, "XEvent")],
        "XEventMetadata": [("name", 2, t.TYPE_BYTES, one, None),
                           ("stats", 5, t.TYPE_MESSAGE, many, "XStat")],
        "XStatMetadata": [("name", 2, t.TYPE_BYTES, one, None)],
        "EventMetadataEntry": [
            ("key", 1, t.TYPE_INT64, one, None),
            ("value", 2, t.TYPE_MESSAGE, one, "XEventMetadata")],
        "StatMetadataEntry": [
            ("key", 1, t.TYPE_INT64, one, None),
            ("value", 2, t.TYPE_MESSAGE, one, "XStatMetadata")],
        "XPlane": [
            ("name", 2, t.TYPE_BYTES, one, None),
            ("lines", 3, t.TYPE_MESSAGE, many, "XLine"),
            ("event_metadata", 4, t.TYPE_MESSAGE, many,
             "EventMetadataEntry"),
            ("stat_metadata", 5, t.TYPE_MESSAGE, many, "StatMetadataEntry")],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane_fast.proto", package="benchmark_xplane_fast",
        syntax="proto3")
    for mname, fields in messages.items():
        m = fd.message_type.add(name=mname)
        for fname, number, ftype, label, of in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if of:
                f.type_name = f".{fd.package}.{of}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _PLANE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{fd.package}.XPlane"))
    return _PLANE


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _scopes(plane):
    """``{metadata id: scope}`` of a device plane: what
    ``program_spans._plane`` and ``scope_of`` make of its metadata."""
    smeta = {e.key: _text(e.value.name) for e in plane.stat_metadata
             if e.HasField("value")}
    tf_op_ids = {k for k, n in smeta.items() if n == "tf_op"}
    out = {}
    for e in plane.event_metadata:
        if not e.HasField("value"):
            continue
        tf_op = ""
        for s in e.value.stats:
            if s.metadata_id in tf_op_ids:
                tf_op = (_text(s.str_value) if s.str_value
                         else smeta.get(s.ref_value, ""))
        out[e.key] = program_spans.scope_of(tf_op, _text(e.value.name))
    return out


def _planes(path):
    """The file's planes as ``(name, bytes of the XPlane message)``,
    none of them decoded yet."""
    with open(path, "rb") as f:
        buf = f.read()
    view = memoryview(buf)
    for field, span in program_spans._fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name = next((program_spans._text(buf, v) for g, v in
                     program_spans._fields(buf, *span) if g == 2), "")
        yield name, view[span[0]:span[1]]


def _window(plane):
    """``(start, end)`` of the window's span on the host's plane."""
    ids = {e.key for e in plane.event_metadata
           if e.value.name == trace_reduce.WINDOW_SPAN.encode()}
    found = None
    for ln in plane.lines if ids else ():
        for e in ln.events:
            if e.metadata_id in ids:
                s = ln.timestamp_ns + e.offset_ps / 1e3
                found = (s, s + e.duration_ps / 1e3)
    return found


def self_seconds(scope_ids, start, dur, n_scopes):
    """``trace_reduce._self_times`` on arrays: self seconds of one
    line's (nested) events, summed by ``scope_ids``.  An event's parent
    is the last one before it, in order of start, that ends after it
    starts; its self time is its duration less its children's."""
    import numpy as np

    order = np.lexsort((-dur, start))
    k, s, d = scope_ids[order], start[order], dur[order]
    end = s + d
    n = len(s)
    # parent[i]: walk back from i - 1 along the candidates' own parents
    # until one still runs at s[i]; settles in about as many passes as
    # the events nest deep
    parent = np.arange(-1, n - 1)
    todo = np.flatnonzero(parent >= 0)
    while len(todo):
        todo = todo[end[parent[todo]] <= s[todo]]
        parent[todo] = parent[parent[todo]]
        todo = todo[parent[todo] >= 0]
    has = parent >= 0
    own = d - np.bincount(parent[has], weights=d[has], minlength=n)
    return np.bincount(k, weights=np.maximum(own, 0.0),
                       minlength=n_scopes) / 1e9


def scope_table(path: str) -> dict:
    """The device planes' part of ``program_spans.reduce``."""
    import numpy as np

    parse = plane_class().FromString
    planes, window = [], None  # planes: (operations' line, {id: scope})
    for pname, raw in _planes(path):
        if not trace_reduce.is_device_plane(pname):
            if window is None:
                window = _window(parse(bytes(raw)))
            continue
        plane = parse(bytes(raw))
        named = {_text(ln.name): ln for ln in plane.lines}
        ops = next((named[n] for n in trace_reduce.OP_LINES if n in named),
                   None)
        if ops is not None:
            planes.append((ops, _scopes(plane)))
    names = sorted({sc for _ops, scope in planes for sc in scope.values()}
                   | {program_spans.UNSCOPED})
    out = {
        "device_planes": len(planes), "scope_s": {}, "device_self_s": 0.0,
        "scoped": names != [program_spans.UNSCOPED], "span_count": 0,
        "idle_by_span_s": None, "idle_by_span_and_event_s": None,
    }
    if not out["scoped"]:
        # a program without scopes (on this engine, the parent of PR
        # 29): the readers report nothing then, so nothing is summed
        return out
    index = {sc: i for i, sc in enumerate(names)}
    total, seen = np.zeros(len(names)), set()
    for ops, scope in planes:
        flat = np.array(
            [x for e in ops.events
             for x in (e.metadata_id, e.offset_ps, e.duration_ps)],
            dtype=np.int64).reshape(-1, 3)
        # metadata id -> index of its scope, through sorted arrays
        mids = np.array(sorted(scope), dtype=np.int64)
        of_mid = np.array([index[scope[m]] for m in mids.tolist()],
                          dtype=np.int64)
        ids = np.full(len(flat), index[program_spans.UNSCOPED])
        if len(mids):
            at = np.minimum(np.searchsorted(mids, flat[:, 0]), len(mids) - 1)
            known = mids[at] == flat[:, 0]
            ids[known] = of_mid[at[known]]
        start = ops.timestamp_ns + flat[:, 1] / 1e3
        dur = flat[:, 2] / 1e3
        if window is not None:
            end = np.minimum(start + dur, window[1])
            start = np.maximum(start, window[0])
            keep = end > start
            ids, start, dur = ids[keep], start[keep], (end - start)[keep]
        total += self_seconds(ids, start, dur, len(names)) / len(planes)
        seen.update(np.unique(ids).tolist())
    out["scope_s"] = {names[i]: float(total[i]) for i in sorted(seen)}
    out["device_self_s"] = sum(out["scope_s"].values())
    return out


def prime(ctx):
    """Reduce this run's trace here, once, and leave the table where
    ``program_spans.load`` looks for its own."""
    if program_spans.CACHE_KEY in ctx:
        return
    try:
        ctx[program_spans.CACHE_KEY] = scope_table(
            trace_reduce.find_xplane(program_spans.trace_dir()))
    except (ImportError, FileNotFoundError):
        return  # no protobuf, or no trace: program_spans sees to both
