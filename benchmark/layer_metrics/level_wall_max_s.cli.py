"""The longest stretch between two level-boundary records of any check of
the window (``level_wall_max_s`` of the engine's ``result`` event; the
first stretch of a check runs from the start of ``run()``), and the
level it ended on: a pause names its level, and the printed host phases
of that check name where it sat.  A maximum, not a median: a window's one
slow check is what this is for."""

from benchmark.lib import program_spans


def read(ctx, params):
    checks = [c for c in ctx["out"]["stats"].get("checks", [])
              if "level_wall_max_s" in c]
    if not checks:
        return None
    worst = max(checks, key=lambda c: c["level_wall_max_s"])
    program_spans.say(
        "level_wall_max_s by check (s, ending on level): " + ", ".join(
            f"{c['level_wall_max_s']:.4f}@{c.get('level_wall_max_at')}"
            for c in checks))
    program_spans.say(
        "host phases of the check with the longest stretch (s): " + ", ".join(
            f"{k[5:-2]} {v:.4f}" for k, v in sorted(
                ((k, v) for k, v in worst.items()
                 if k.startswith("host_") and k.endswith("_s")
                 and k != "host_wait_s"),
                key=lambda kv: -kv[1])[:6]))
    return worst["level_wall_max_s"]
