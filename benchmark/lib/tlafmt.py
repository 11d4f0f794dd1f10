"""Readers for the two text formats the benchmark takes from outside the
program: a TLC ``.cfg`` file's constant bindings, and the TLC-style
counterexample a ``cli check`` prints.  Both produce values of the
benchmark's own reference (``benchmark/ref/pyeval.py``), so a comparison
never passes through the program's parser or renderer.
"""

from __future__ import annotations

import re

from benchmark.ref import pyeval as pe

# .cfg constant name -> pyeval.Constants field, and how to read its value
_CFG_FIELDS = {
    "MessageSentLimit": ("message_sent_limit", "int"),
    "CompactionTimesLimit": ("compaction_times_limit", "int"),
    "ModelConsumer": ("model_consumer", "bool"),
    "ConsumeTimesLimit": ("consume_times_limit", "int"),
    "KeySpace": ("num_keys", "set"),
    "ValueSpace": ("num_values", "set"),
    "RetainNullKey": ("retain_null_key", "bool"),
    "MaxCrashTimes": ("max_crash_times", "int"),
    "ModelProducer": ("model_producer", "bool"),
}


def constants_from_cfg(path: str) -> pe.Constants:
    """The nine compaction constants of a ``.cfg`` file.  Sets are
    interned to ``1..n`` as the reference does (only their sizes enter
    the state space).  A constant that is missing is an error."""
    with open(path, encoding="utf-8") as f:
        text = re.sub(r"\\\*.*", "", f.read())
    got = {}
    for name, (field, kind) in _CFG_FIELDS.items():
        m = re.search(rf"\b{name}\s*=\s*(\{{[^}}]*\}}|\w+)", text)
        if m is None:
            raise ValueError(f"{path}: constant {name} is not bound")
        raw = m.group(1)
        if kind == "int":
            got[field] = int(raw)
        elif kind == "bool":
            if raw not in ("TRUE", "FALSE"):
                raise ValueError(f"{path}: {name} = {raw} is not a boolean")
            got[field] = raw == "TRUE"
        else:
            got[field] = len([x for x in raw.strip("{}").split(",") if x.strip()])
    c = pe.Constants(**got)
    c.validate()
    return c


_MSG = re.compile(r"\[id \|-> (\d+), key \|-> (\d+), value \|-> (\d+)\]")


def _seq(text: str) -> tuple:
    return tuple(tuple(int(x) for x in m.groups()) for m in _MSG.finditer(text))


def _parse_state(lines: dict, n_ledgers: int) -> pe.State:
    led_text = lines["compactedLedgers"]
    ledgers = []
    for i in range(1, n_ledgers + 1):
        m = re.search(rf"\b{i} :> (Nil|<<.*?>>)(?:, \d+ :> |\)$)", led_text)
        if m is None:
            raise ValueError(f"ledger slot {i} not found in: {led_text}")
        ledgers.append(None if m.group(1) == "Nil" else _seq(m.group(1)))
    cur = lines["cursor"]
    if cur == "Nil":
        cursor = None
    else:
        m = re.fullmatch(
            r"\[compactionHorizon \|-> (\d+), "
            r"compactedTopicContext \|-> (\d+)\]", cur)
        cursor = (int(m.group(1)), int(m.group(2)))
    p1t = lines["phaseOneResult"]
    if p1t == "Nil":
        p1 = None
    else:
        m = re.fullmatch(
            r"\[readPosition \|-> (\d+), latestForKey \|-> \((.*)\)\]", p1t)
        latest = tuple(
            (int(k), int(p))
            for k, p in re.findall(r"(\d+) :> (\d+)", m.group(2))
        )
        p1 = (int(m.group(1)), latest)
    return pe.State(
        messages=_seq(lines["messages"]),
        ledgers=tuple(ledgers),
        cursor=cursor,
        cstate=pe.PHASE_NAMES.index(lines["compactorState"]),
        p1=p1,
        horizon=int(lines["compactionHorizon"]),
        context=int(lines["compactedTopicContext"]),
        crash=int(lines["crashTimes"]),
        consume=int(lines["consumeTimes"]),
    )


def parse_trace(text: str, n_ledgers: int):
    """``(violated invariant name or None, [State...], [action name...])``
    from the text a ``cli check`` prints.  The action list has one entry
    per step (one fewer than states)."""
    m = re.search(r"^Error: Invariant (\w+) is violated\.", text, re.M)
    violated = m.group(1) if m else None
    states, actions = [], []
    cur = None
    for line in text.splitlines():
        h = re.fullmatch(r"State (\d+): <(.+)>", line)
        if h:
            if cur is not None:
                states.append(_parse_state(cur, n_ledgers))
            cur = {}
            if int(h.group(1)) != len(states) + 1:
                raise ValueError(f"trace states out of order at: {line}")
            if h.group(1) != "1":
                actions.append(h.group(2))
            continue
        v = re.fullmatch(r"/\\ (\w+) = (.*)", line)
        if v and cur is not None:
            cur[v.group(1)] = v.group(2)
        elif cur is not None and line.strip() == "":
            states.append(_parse_state(cur, n_ledgers))
            cur = None
    if cur is not None:
        states.append(_parse_state(cur, n_ledgers))
    return violated, states, actions


_RESULT = re.compile(
    r"(\d+) distinct states found, search depth \(diameter\) (\d+)"
)


def parse_counts(text: str):
    """``(distinct states, diameter)`` of a check's report, or None."""
    m = _RESULT.search(text)
    return (int(m.group(1)), int(m.group(2))) if m else None
