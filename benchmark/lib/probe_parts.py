"""The parts of a stage: device seconds by ``part.`` scope under a
``ptt.`` stage scope, from the window's ``.xplane.pb``.

The program names the parts of one stage's work with a second level of
``jax.named_scope`` (``pulsar_tlaplus_tpu/obs/spans.py: part``): inside a
probe round ``part.gather``, ``part.claims_fill``, ``part.claims_bid``,
``part.write``, ``part.reread``, and around the ladder's compactions,
slices and merges ``part.narrow`` (``ops/fpset.py``).  ``program_spans``
reads an operation's STAGE (the innermost ``ptt.`` scope of its
``op_name`` path) and its rule is the one used here, unedited; this
module reads the level below it.

Definitions.  An operation's stage is the innermost (last) ``ptt.<stage>``
of its path; its part is the innermost ``part.<name>`` BELOW that stage
scope (to the right of it in the path), else none: a probe round traced
by the rehash is stage ``rehash``, part ``claims_fill``.  Its time is its
self time (duration less what is nested in it), clipped to the window,
the mean of the device planes — ``xplane_fast.scope_table``'s, so the
parts of a stage and its seconds under no part sum to what
``stage_device_s.<stage>`` reads.  An operation's width is the largest
dimension among the shapes of its result; it is **table-sized** where
that is ``2^k + 1`` for ``k >= 10`` (a table column, or ``claims``, with
its trash row; no buffer of lanes has such a width), **flush-wide** where
it is the widest width under the stage that is not table-sized (the whole
batch, before the ladder's first hand-over), else **narrower**.

A trace with ``ptt.`` stages and not one part (an older commit, or
executables that a compile cache handed back from before they had parts)
gives None from every reader here, said aloud, never 0.

One pass over the file through ``xplane_fast``'s parser, once a run
(cached on ``ctx``); it prints the seconds by part and width under
``probe`` and ``rehash``, and the longest operations of ``probe`` under
no part.
"""

from __future__ import annotations

import re

from benchmark.lib import program_spans, trace_reduce
from benchmark.lib.program_spans import say
from benchmark.lib.xplane_fast import (_planes, _text, _window, plane_class,
                                       self_seconds)

PART = re.compile(r"/part\.([a-z_]+)(?=/)")
NO_PART = "(no part)"
CACHE_KEY = "_probe_parts"
PRINTED_STAGES = ("probe", "rehash")
WIDTHS = ("table-sized", "flush-wide", "narrower")
TABLE_MIN = 1 << 10


def stage_and_part(*texts):
    """``(stage, part)`` of an operation from its ``op_name`` path (its
    ``tf_op`` stat), else from its name: ``program_spans.scope_of``'s
    stage, and the last ``part.`` scope after it."""
    for t in texts:
        if t and "ptt." in t:
            last = None
            for last in program_spans.SCOPE.finditer(t):
                pass
            if last is not None:
                below = PART.findall(t[last.end():])
                return last.group(1), below[-1] if below else NO_PART
    return program_spans.UNSCOPED, NO_PART


def width_of(name: str) -> int:
    """The largest dimension among the result shapes of an HLO line
    (``%fusion.12 = (s32[16777217]{0}, u32[4096]{0}) fusion(...)``);
    0 where the name is no HLO line or the result a scalar."""
    _lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return 0
    if rhs.startswith("("):  # a tuple of shapes: up to its closing bracket
        depth = end = 0
        for end, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shapes = rhs[:end + 1]
    else:
        shapes = rhs.partition(" ")[0]
    shapes = re.sub(r"\{[^}]*\}", "", shapes)  # layouts hold numbers too
    dims = [int(d) for group in re.findall(r"\[([\d,]*)\]", shapes)
            for d in group.split(",") if d]
    return max(dims, default=0)


def is_table_sized(width: int) -> bool:
    return width > TABLE_MIN and (width - 1) & (width - 2) == 0  # 2^k + 1


def _metadata(plane):
    """``{metadata id: (name, tf_op)}`` of a device plane."""
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
        out[e.key] = (_text(e.value.name), tf_op)
    return out


def operations(path: str):
    """``[(stage, part, name, op_name path, self seconds)]`` of the
    file's distinct device operations inside the window, each the mean
    of the device planes, and the number of device planes."""
    import numpy as np

    parse = plane_class().FromString
    planes, window = [], None
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
            planes.append((ops, _metadata(plane)))
    out = {}
    for ops, meta in planes:
        flat = np.array(
            [x for e in ops.events
             for x in (e.metadata_id, e.offset_ps, e.duration_ps)],
            dtype=np.int64).reshape(-1, 3)
        mids, ids = np.unique(flat[:, 0], return_inverse=True)
        start = ops.timestamp_ns + flat[:, 1] / 1e3
        dur = flat[:, 2] / 1e3
        if window is not None:
            end = np.minimum(start + dur, window[1])
            start = np.maximum(start, window[0])
            keep = end > start
            ids, start, dur = ids[keep], start[keep], (end - start)[keep]
        secs = self_seconds(ids, start, dur, len(mids)) / len(planes)
        for mid, s in zip(mids.tolist(), secs.tolist()):
            if s <= 0.0:
                continue
            name, tf_op = meta.get(mid, ("", ""))
            key = (*stage_and_part(tf_op, name), name, tf_op)
            out[key] = out.get(key, 0.0) + s
    return [(*k, s) for k, s in out.items()], len(planes)


def table(ops, planes: int) -> dict:
    """``{"planes", "staged", "parted", "stage_s": {stage: seconds},
    "part_s": {stage: {part: seconds}}, "width_s": {stage: {part:
    {width class: seconds}}}, "unparted": {stage: [(name, op_name path,
    seconds)], longest first}}``."""
    out = {"planes": planes, "stage_s": {}, "part_s": {}, "width_s": {},
           "unparted": {}}
    widths = [width_of(name) for _stage, _part, name, _path, _s in ops]
    flush = {}  # stage -> its widest width that is not table-sized
    for (stage, *_rest), w in zip(ops, widths):
        if not is_table_sized(w):
            flush[stage] = max(flush.get(stage, 0), w)
    for (stage, part, name, path, s), w in zip(ops, widths):
        kind = (WIDTHS[0] if is_table_sized(w)
                else WIDTHS[1] if w and w == flush[stage] else WIDTHS[2])
        out["stage_s"][stage] = out["stage_s"].get(stage, 0.0) + s
        by_part = out["part_s"].setdefault(stage, {})
        by_part[part] = by_part.get(part, 0.0) + s
        by_width = out["width_s"].setdefault(stage, {}).setdefault(
            part, dict.fromkeys(WIDTHS, 0.0))
        by_width[kind] += s
        if part == NO_PART:
            out["unparted"].setdefault(stage, []).append((name, path, s))
    for names in out["unparted"].values():
        names.sort(key=lambda nps: -nps[2])
    out["staged"] = any(
        st != program_spans.UNSCOPED for st in out["stage_s"])
    out["parted"] = any(
        p != NO_PART for by in out["part_s"].values() for p in by)
    return out


def _print(tab):
    for stage in PRINTED_STAGES:
        by = tab["part_s"].get(stage)
        if not by:
            continue
        say(f"parts of ptt.{stage}, device s (table-sized / flush-wide / "
            f"narrower), of {tab['stage_s'][stage]:.4f}: " + ", ".join(
                f"{p} {s:.4f} (" + " / ".join(
                    f"{tab['width_s'][stage][p][w]:.4f}" for w in WIDTHS)
                + ")"
                for p, s in sorted(by.items(), key=lambda kv: -kv[1])))
    loose = tab["unparted"].get("probe", [])[:5]
    if loose:
        # with the end of its path: an operation the compiler built
        # itself has none of its own, and reads under its loop's
        say("ptt.probe under no part, longest: " + ", ".join(
            f"{trace_reduce.short_name(n)} {s:.4f} "
            f"[{'/'.join(p.split('/')[-3:]) or 'no op_name'}]"
            for n, p, s in loose))


def load(ctx, path=None):
    """This run's table, made once and kept on ``ctx``; None where the
    run wrote no trace, or protobuf's parser is missing.  ``path``
    stands in for the run's own file (the tests')."""
    if CACHE_KEY in ctx:
        return ctx[CACHE_KEY]
    try:
        if path is None:
            path = trace_reduce.find_xplane(program_spans.trace_dir())
        tab = table(*operations(path))
    except (ImportError, FileNotFoundError) as e:
        say(f"probe parts: no table ({e.__class__.__name__}: {e})")
        tab = None
    ctx[CACHE_KEY] = tab
    if tab is not None:
        if tab["parted"]:
            _print(tab)
        elif tab["staged"]:
            say("probe parts: the trace holds ptt. stages and no part. "
                "scope (the program has none, or a compile cache handed "
                "back executables from before it had them): nothing "
                "reported")
    return tab


def _parted(ctx):
    """The run's table where it has device planes and any part."""
    tab = load(ctx)
    return tab if tab and tab["planes"] and tab["parted"] else None


def part_seconds(ctx, stage: str, part: str):
    """Device self seconds under ``part.<part>`` of ``ptt.<stage>``;
    None where the trace holds no part at all."""
    tab = _parted(ctx)
    return tab and tab["part_s"].get(stage, {}).get(part, 0.0)


def unparted_pct(ctx, stage: str):
    """Of the seconds under ``ptt.<stage>``, the share under no part."""
    tab = _parted(ctx)
    total = tab["stage_s"].get(stage, 0.0) if tab else 0.0
    if total <= 0:
        return None
    return 100.0 * tab["part_s"][stage].get(NO_PART, 0.0) / total
