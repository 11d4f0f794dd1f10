"""What a preempted and recovered check printed and counted, read and
reckoned for the survivability cell.

**The recovered line.**  ``cli check ... -checkpoint F -recover`` prints,
after the verdict, one line on standard output (``docs/robustness.md``
has the grammar; ``RECOVERED_LINE`` is the benchmark's own reading of
it, and imports nothing of the program):

    Recovered from the checkpoint frame of level 18 (2402570 states): 6
    levels expanded after it.

**What a frame holds.**  For ``n`` states found, a frame of the
single-chip engine (``DeviceChecker._save_frame``, ``rows_window="all"``)
holds every state's ``K`` key words and its slot in the table (the
occupied slots, packed on the host: ``utils/ckpt.py: pack_fpset``, which
keeps the slot as a 64-bit integer: two words), its ``W`` row words, its
parent and its lane:

    4 * n * (K + 2 + W + 2)

bytes before compression, and every frame holds all of it again.
``ckpt_states`` of a check's ``result`` stats is ``n`` summed over the
check's frames, ``ckpt_raw_bytes`` what the program handed the writer,
``ckpt_bytes`` what the compressed files weigh and ``ckpt_d2h_bytes``
what crossed the link for them (both table columns whole, the rows and
logs whole or in bucketed slices).
"""

from __future__ import annotations

import re

WORD_BYTES = 4

RECOVERED_LINE = re.compile(
    r"^Recovered from the checkpoint frame of level (?P<level>\d+) "
    r"\((?P<states>\d+) states\): (?P<levels_run>\d+) levels expanded "
    r"after it\.$", re.M)

# the CLI's progress line, as device_bfs._log prints it to stderr
LEVEL_LINE = re.compile(r"^\s*level (\d+): \+(\d+) \(total (\d+),", re.M)

RESUMABLE_TEXT = "continue with -recover"


def parse_recovered_line(text: str):
    """``{"level", "states", "levels_run"}`` of the one recovered line
    in a check's standard output; None where there is no such line, or
    more than one."""
    found = list(RECOVERED_LINE.finditer(text))
    if len(found) != 1:
        return None
    return {k: int(v) for k, v in found[0].groupdict().items()}


def progress_rows(text: str):
    """``[(level, added, total)]`` of a check's progress lines."""
    return [tuple(int(x) for x in m.groups())
            for m in LEVEL_LINE.finditer(text)]


def joined_level_sizes(rows1, rows2):
    """Per-level sizes of one search told by two legs' progress lines:
    every level the second leg closed from its own lines, the levels
    under its first from the first leg's (a second leg that started
    afresh tells the whole search alone).  Level 1 is the first line's
    total less what that level added.  None where the lines joined so
    do not number the levels 2, 3, ... without a gap."""
    first2 = rows2[0][0] if rows2 else None
    rows = [r for r in rows1 if first2 is None or r[0] < first2] + list(rows2)
    if not rows or [r[0] for r in rows] != list(range(2, len(rows) + 2)):
        return None
    return [rows[0][2] - rows[0][1]] + [r[1] for r in rows]


def frame_bytes(states: int, key_columns: int, state_words: int) -> int:
    """Bytes, before compression, of frames that hold ``states`` states
    between them."""
    return WORD_BYTES * states * (key_columns + 2 + state_words + 2)
