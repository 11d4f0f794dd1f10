"""Control ``in-turn``: a configuration with more than one control names
them under ``controls``, each an entry as ``control`` would hold it
alone (``kind`` and its parameters), and ``benchmark/control.py`` runs
the one that the seed picks: ``controls[seed % len(controls)]``, so
``--seeds 0,1`` runs two in turn, each handed to the comparison, and
judged, on its own line.  Standard error says which one a seed ran."""

from __future__ import annotations

import sys

from benchmark.lib import plug

NEEDS_DEVICE = True  # one of them may


def answers(ctx, seed):
    controls = ctx["control"]["controls"]
    picked = controls[seed % len(controls)]
    print(f"[control] seed {seed}: {picked['kind']}", file=sys.stderr,
          flush=True)
    mod = plug.load_file("controls", picked["kind"])
    return mod.answers(dict(ctx, control=picked), seed)
