"""Comparison ``pyeval-prefix-plus-pinned-compiled``.

Every check of ``pyeval-prefix-plus-pinned`` (called, not copied: exit
code, distinct states, diameter and every level size against the
reference, which imports nothing of the program and knows nothing of
its compiler) and, from EVERY check's standard output, that the check's
kernels were generated from the ``.tla`` text on the command line at the
widths the configuration states (``shapes`` in its file), exactly:

- the compiled line is there, once (``compiled_line_missing``): the
  line ``cli.compiled_line`` prints after the verdict of a check through
  ``frontend/codegen.py``;
- no hand-written model's banner (``hand_model_banner``): a check
  through ``models/compaction.py`` is another deployment, not a faster
  one;
- the compiler did not decline the spec and no check went to the
  generic interpreter (``fell_back``);
- the line's module, state bits, state words, successor lanes and key
  kind are the configuration's (``widths_differ``);
- the auto-invariant ``__EvalError__`` (an evaluation error TLC would
  raise) is not named by any check's output (``eval_error``).

A traced run's checks carry the engine's ``result`` stats, and there
``fpset_failures`` has to be 0, ``key_exact`` false and the seven
``codegen_*`` / ``key_exact`` counters present with the line's widths
(``stats_differ``).  What has no per-layer metric is printed: every
compiled line, and a traced check's ``codegen_s`` and
``codegen_parse_s``.
"""

from __future__ import annotations

import re

from benchmark.lib import plug
from benchmark.lib.reference import FALLBACK_TEXT, chk

COMPILED_LINE = re.compile(
    r"^Compiled from the \.tla: module (?P<module>\S+), state width "
    r"(?P<bits>\d+) bits in (?P<words>\d+) words, (?P<lanes>\d+) "
    r"successor lanes, (?P<initial>\d+) initial states, keys "
    r"(?P<keys>exact|hashed), code generation (?P<codegen_s>\d+\.\d+) s "
    r"after (?P<parse_s>\d+\.\d+) s of parse and bind\.$", re.M)
# the banner of a check through a hand-written model of models/registry.py
# (the compiled path's says "via the spec->kernel compiler" before the
# parenthesis)
HAND_BANNER = re.compile(r"^tpu-tlc: checking \S+ @ \S+ \(state width", re.M)
DECLINED_TEXT = "spec->kernel compiler declined"
COUNTERS = ("codegen_s", "codegen_parse_s", "codegen_state_bits",
            "codegen_state_words", "codegen_lanes",
            "codegen_initial_states", "key_exact")


def parse_compiled_line(text: str):
    """The one compiled line of a check's output as a dict, or None
    where there is none, more than one, or one cut short."""
    found = list(COMPILED_LINE.finditer(text))
    if len(found) != 1:
        return None
    g = found[0].groupdict()
    return {"module": g["module"], "bits": int(g["bits"]),
            "words": int(g["words"]), "lanes": int(g["lanes"]),
            "initial": int(g["initial"]), "key_exact": g["keys"] == "exact",
            "codegen_s": float(g["codegen_s"]),
            "parse_s": float(g["parse_s"])}


def widths_of(line):
    return [line["module"], line["bits"], line["words"], line["lanes"],
            line["key_exact"]]


def compare(config, traffic, answers, seed):
    base = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    checks = base.compare(config, traffic, answers, seed)
    sh = config["shapes"]
    want = [config["program"]["module"], sh["state_bits"], sh["state_words"],
            sh["successor_lanes"], sh["key_exact"]]
    lines = [parse_compiled_line(a["text"]) for a in answers]
    for m in COMPILED_LINE.finditer("\n".join(a["text"] for a in answers)):
        print(f"[benchmark] {m.group(0)}", flush=True)
    checks.append(chk("compiled_line_missing",
                      sum(1 for ln in lines if ln is None), 0))
    checks.append(chk(
        "hand_model_banner",
        sum(1 for a in answers if HAND_BANNER.search(a["text"])), 0))
    checks.append(chk(
        "fell_back",
        sum(1 for a in answers
            if DECLINED_TEXT in a["text"] or FALLBACK_TEXT in a["text"]), 0))
    # a check with no line is counted once, as missing
    checks.append(chk(
        "widths_differ_from_" + "_".join(str(w) for w in want[1:4]),
        sum(1 for ln in lines if ln is not None and widths_of(ln) != want),
        0))
    checks.append(chk(
        "eval_error",
        sum(1 for a in answers if "__EvalError__" in a["text"]), 0))
    traced = [(a["stats"], ln) for a, ln in zip(answers, lines)
              if a.get("stats")]
    for st, _ln in traced:
        print("[benchmark] compiled check: "
              + ", ".join(f"{k} {st.get(k)}" for k in COUNTERS), flush=True)
    checks.append(chk(
        "fpset_failures",
        sum(1 for st, _ln in traced if st.get("fpset_failures", 0) != 0), 0))
    checks.append(chk(
        "stats_differ",
        sum(1 for st, ln in traced
            if any(k not in st for k in COUNTERS)
            or st["key_exact"] is not False
            or (ln is not None and [
                st["codegen_state_bits"], st["codegen_state_words"],
                st["codegen_lanes"], st["codegen_initial_states"]]
                != [ln["bits"], ln["words"], ln["lanes"], ln["initial"]])),
        0))
    return checks
