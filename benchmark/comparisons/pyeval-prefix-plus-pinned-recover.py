"""Comparison ``pyeval-prefix-plus-pinned-recover``.

Every check of ``pyeval-prefix-plus-pinned`` (called, not copied) on
each CYCLE of a preempted and recovered check as ONE search: exit codes
as the traffic states them, the distinct states and the diameter leg 2
printed, and every level's size from the two legs' progress lines
joined (the levels under leg 2's first from leg 1, the rest from leg 2:
``benchmark/lib/ckpt_bytes.py``), against the reference, which knows no
frames: the same operations on the same binding give the same levels.

And, for every cycle, that it was preempted and recovered as the
configuration states (``survivability`` in its file), exactly:

- leg 1 returned 3 and said that a resumable frame is on disk, and a
  file was at the frame's path when it returned;
- leg 2 returned 0 and printed the recovered line, once (a second leg
  that found the verdict afresh is another deployment: ``not_resumed``);
- the line's level is the last level leg 1 closed and is at or past the
  level of the kill; its states are the reference's cumulative count at
  that level; its levels expanded are the search's levels less that
  level, and leg 2's first progress line is numbered one past it: no
  level the frame closed was expanded again.

A traced run's legs carry the engine's ``result`` stats, and there
``ckpt_retries`` and ``hbm_recovered`` have to be 0, leg 1 has to have
written a frame every ``cadence_levels`` levels and the suspend frame,
``level // cadence_levels + 1`` in all, and leg 2 at least one.
"""

from __future__ import annotations

from benchmark.lib import ckpt_bytes, plug
from benchmark.lib.reference import chk


def compare(config, traffic, answers, seed):
    base = plug.load_file("comparisons", "pyeval-prefix-plus-pinned")
    checks = base.compare(config, traffic, answers, seed)
    prefix, stored = base.wanted_sizes(config, traffic)
    sizes = prefix + stored
    sv = config["survivability"]
    cadence, kill_at = sv["cadence_levels"], sv["kill_at_level"]
    for m in ckpt_bytes.RECOVERED_LINE.finditer(
            "\n".join(a["text"] for a in answers)):
        print(f"[benchmark] {m.group(0)}", flush=True)
    cycles = [a for a in answers if len(a.get("legs", ())) == 2]
    lines = [(a, ckpt_bytes.parse_recovered_line(a["text"]))
             for a in cycles]

    def wrong(pred):
        # a cycle with no line is counted once, as not resumed
        return sum(1 for a, ln in lines if ln is not None and pred(a, ln))

    def last1(a):
        rows = a["legs"][0]["progress"]
        return rows[-1][0] if rows else None

    def first2(a):
        rows = a["legs"][1]["progress"]
        return rows[0][0] if rows else None

    checks.append(chk("answers_that_are_no_cycle",
                      len(answers) - len(cycles), 0))
    checks.append(chk("leg1_exit_code_not_3",
                      sum(1 for a in cycles if a["legs"][0]["rc"] != 3), 0))
    checks.append(chk(
        "leg1_names_no_resumable_frame",
        sum(1 for a in cycles
            if ckpt_bytes.RESUMABLE_TEXT not in a["legs"][0]["text"]), 0))
    checks.append(chk(
        "no_frame_file_after_leg1",
        sum(1 for a in cycles if not a.get("frame_after_leg1")), 0))
    checks.append(chk("leg2_exit_code_not_0",
                      sum(1 for a in cycles if a["legs"][1]["rc"] != 0), 0))
    checks.append(chk("not_resumed",
                      sum(1 for _a, ln in lines if ln is None), 0))
    checks.append(chk(
        f"resume_level_not_leg1's_last_or_under_{kill_at}",
        wrong(lambda a, ln: ln["level"] != last1(a)
              or ln["level"] < kill_at), 0))
    checks.append(chk(
        "resume_states_differ_from_the_reference's_at_that_level",
        wrong(lambda a, ln: ln["states"] != sum(sizes[:ln["level"]])), 0))
    checks.append(chk(
        "a_level_the_frame_closed_was_expanded_again",
        wrong(lambda a, ln: ln["levels_run"] != len(sizes) - ln["level"]
              or first2(a) != ln["level"] + 1), 0))
    traced = [a for a in cycles if all(leg["stats"] for leg in a["legs"])]
    checks.append(chk(
        "ckpt_retries_or_hbm_recovered",
        sum(1 for a in traced for leg in a["legs"]
            if leg["stats"].get("ckpt_retries")
            or leg["stats"].get("hbm_recovered")), 0))
    checks.append(chk(
        f"leg1_frames_not_one_every_{cadence}_levels_and_the_suspend_frame",
        sum(1 for a in traced
            if a["legs"][0]["stats"].get("ckpt_frames")
            != (last1(a) or 0) // cadence + 1), 0))
    checks.append(chk(
        "leg2_wrote_no_frame",
        sum(1 for a in traced
            if not a["legs"][1]["stats"].get("ckpt_frames")), 0))
    return checks
