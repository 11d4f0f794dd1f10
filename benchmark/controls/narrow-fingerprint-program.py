"""Control ``narrow-fingerprint-program``: the program itself at the
cell's own size, deduplicating on ``bits`` bits: every fingerprint column
above the first is zeroed from outside before the checker is built, so
the device programs it compiles are the narrow ones.  Needs the chip.

The program refuses a seed that its own fingerprints merge ("seed states
are not all distinct"), so the control hands it the seed a checker that
narrow would hold: of the seed states that share a fingerprint only the
first found is kept, as a breadth-first search keeps it.  The run then
goes through the cell's own window and the cell's own comparison, and
has to fail it on counts.  The run does not depend on ``--seed`` (only
the sample drawn from it does), so it is made once and compared under
every seed.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.lib import plug

NEEDS_DEVICE = True
ONE_RUN = True


def narrow_program(bits: int):
    """Zero every fingerprint column of the program above the first
    ``bits``; returns the call that undoes it."""
    import jax.numpy as jnp

    from pulsar_tlaplus_tpu.ops import dedup

    if bits != 32:
        raise ValueError("the program's key columns are 32 bits wide")
    sound = dedup.KeySpec.make

    def make(self, packed):
        cols = sound(self, packed)
        return (cols[0],) + tuple(jnp.zeros_like(c) for c in cols[1:])

    dedup.KeySpec.make = make
    return lambda: setattr(dedup.KeySpec, "make", sound)


def seed_as_narrow_checker_holds_it(ck, seed):
    """``seed`` (rows, parents, lanes, level sizes; rows in breadth-first
    order) less every state whose fingerprint an earlier state has: its
    children hang on the state that took its place."""
    import jax.numpy as jnp

    rows, parents, lanes, lsizes = seed
    rows = np.ascontiguousarray(rows, np.uint32)
    keys = np.stack(
        [np.asarray(c) for c in ck.keys.make(jnp.asarray(rows))], axis=1)
    _, first, inverse = np.unique(
        keys, axis=0, return_index=True, return_inverse=True)
    stands_for = first[inverse.reshape(-1)]  # earliest row with that key
    keep = stands_for == np.arange(len(rows))
    new_id = np.cumsum(keep) - 1
    par = np.asarray(parents, np.int64)
    par = np.where(par >= 0, new_id[stands_for[np.maximum(par, 0)]], par)
    bounds = np.cumsum([0] + list(lsizes))
    return (
        rows[keep], par[keep].astype(np.int32), np.asarray(lanes)[keep],
        [int(keep[a:b].sum()) for a, b in zip(bounds, bounds[1:])],
    )


def answers(ctx, seed):
    os.makedirs(ctx["work_dir"], exist_ok=True)
    restore = narrow_program(ctx["control"]["bits"])
    try:
        drv = plug.load_file("drivers", ctx["traffic"]["driver"]).Driver(
            ctx["config"], ctx["traffic"], ctx["root"], ctx["work_dir"], 0, 0)
        drv.setup(ctx["seconds"])
        n = len(drv.seed_rows[0])
        drv.seed_rows = seed_as_narrow_checker_holds_it(drv.ck, drv.seed_rows)
        print(f"[control] the narrowed program holds {len(drv.seed_rows[0])} "
              f"of the {n} seed states", flush=True)
        out = drv.window(ctx["seconds"])
        drv.after_window(out)
    finally:
        restore()
    return out["answers"]
