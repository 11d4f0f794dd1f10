"""The units change no program, and a second binding is a miss (ISSUE 33).

The heavier half of tests/test_units.py (a file of its own so that the
two spread over the suite's workers): whole ``cli check`` runs in child
processes against one compile cache, and the published 253,361-state
binding followed by the 45,198-state one in one process.
"""

import inspect
import os
import subprocess
import sys

import jax
import pytest

from pulsar_tlaplus_tpu.engine import device_bfs
from tests.test_units import CFG_45K, ROOT, SPEC, VERDICT, _cli_check

CFG_253K = os.path.join(ROOT, "specs", "compaction_253k.cfg")


def test_the_45k_binding_after_the_253k_binding(tmp_path):
    # whatever this worker's earlier tests built (several run the 45k
    # binding through cli.main in process): start with no program held
    jax.clear_caches()
    big = _cli_check(tmp_path, 0, "-config", CFG_253K)
    assert VERDICT.search(big[1]).groups() == ("253361", "23")
    small = _cli_check(tmp_path, 1, "-config", CFG_45K)
    assert small[3]["jit_body_traces"] > 0
    assert VERDICT.search(small[1]).groups() == ("45198", "20")
    assert len(small[2]) == 19 and sum(small[2]) + 729 == 45198


# ---- the tiered store's fetch programs are units too ----------------------


@pytest.mark.parametrize("name", ["ptt_spill_fetch", "ptt_spill_fetch_cols"])
def test_a_fetch_program_is_a_module_level_closure_free_function(name):
    """Built once a process and keyed on ``size`` alone (``start`` is
    traced), under a name of its own: the name is part of the
    persistent cache's key."""
    jitted = getattr(device_bfs, name)
    fn = inspect.unwrap(jitted)
    assert inspect.isfunction(fn) and fn is not jitted
    assert fn.__closure__ is None
    assert fn.__qualname__ == fn.__name__ == name  # no <locals>
    assert fn.__module__ == device_bfs.__name__
    params = inspect.signature(fn).parameters
    assert list(params)[-1] == "size" and "self" not in params
    assert params["size"].kind is inspect.Parameter.KEYWORD_ONLY
    assert params["start"].kind is inspect.Parameter.POSITIONAL_OR_KEYWORD


# ---- the mechanism never changes a program -------------------------------

# the ways through the changed code (PR 31's seven paths): a cache
# directory filled with the units switched off — a program is a closure
# over its static arguments, jitted with array arguments only, as it
# was when a checker built it — then the tree as it is against it.
# Goal: no new entry.  (Against the real parent commit the
# builder runs scripts/parent_cache_check.py; a test cannot hold a
# second checkout.)
PATHS = {
    "fuse_level": (["-fuse", "level"], {}),
    "fuse_stage": (["-fuse", "stage"], {}),
    "leak_trace": (["-invariant", "CompactedLedgerLeak"], {}),
    "workers_4": (["-workers", "4"], {}),
    "hbm_budget": (["-hbm-budget", "24M"], {}),
    "termination": (["-property", "Termination"], {}),
    "shift": ([], {"PTT_COMPACT_MATERIALIZE": "shift"}),
}

_IN_PLACE = """
import sys
import jax
from pulsar_tlaplus_tpu.engine import units
from pulsar_tlaplus_tpu.obs import spans

def unit(scope=None, static=(), donate=()):
    # a unit as it was before it was one: a closure over what it reads
    # that is not an array, jitted with array arguments only
    def deco(fn):
        traced = spans.staged(scope)(fn) if scope else fn
        closures = {}
        def call(*args, **statics):
            key = tuple(sorted(statics.items(), key=lambda kv: kv[0]))
            if key not in closures:
                def program(*a):
                    return traced(*a, **statics)
                program.__name__ = fn.__name__
                closures[key] = jax.jit(program, donate_argnums=donate)
            return closures[key](*args)
        call.__name__ = fn.__name__
        call.body = fn
        return call
    return deco

units.unit = unit   # before the module that defines the units is imported
from pulsar_tlaplus_tpu import cli
sys.exit(cli.main(sys.argv[1:]))
"""

_AS_IS = """
import sys
from pulsar_tlaplus_tpu import cli
sys.exit(cli.main(sys.argv[1:]))
"""


def _entries(d):
    return {f for f in os.listdir(d) if f.endswith("-cache")}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_units_move_no_program(path, tmp_path):
    argv, extra = PATHS[path]
    cache = str(tmp_path / "cache")
    os.makedirs(cache)
    env = dict(
        os.environ, JAX_COMPILATION_CACHE_DIR=cache,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=ROOT, **extra,
    )
    cmd = ["check", SPEC, "-config", CFG_45K, *argv]
    rcs = []
    filled = None
    for code in (_IN_PLACE, _AS_IS):
        p = subprocess.run(
            [sys.executable, "-c", code, *cmd], cwd=ROOT, env=env,
            capture_output=True, text=True, timeout=600,
        )
        rcs.append(p.returncode)
        if filled is None:
            filled = _entries(cache)
    assert rcs[0] == rcs[1] and rcs[0] in (0, 1), rcs
    assert len(filled) > 20
    missed = sorted(e.rsplit("-", 2)[0] for e in _entries(cache) - filled)
    assert missed == [], f"programs whose HLO the units moved: {missed}"
