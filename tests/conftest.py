"""Test harness config: virtual 8-device CPU mesh + persistent compile cache.

Multi-chip behavior is tested without TPUs by forcing 8 host-platform
devices (SURVEY.md §4e); the real-chip path is exercised by
chip_smoke.py.  Must run before jax is imported anywhere.
"""

import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The suite keeps a compile-time threshold of its own, set the way a
# user would (from outside, so ``setup_compile_cache`` leaves it alone,
# also when ``cli.main`` calls it inside a test or a child process
# inherits it).  Not the helper's 0: tests of the compile meter assert
# that a first run compiles, which holds only while the small programs
# they build are never kept from one test, worker or run to the next.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from pulsar_tlaplus_tpu.utils.device import setup_compile_cache  # noqa: E402

# tests always run on the CPU mesh, whatever the host has
jax.config.update("jax_platforms", "cpu")
setup_compile_cache()


# ---- quick tier (VERDICT r4 #9) -------------------------------------
# `pytest -m quick`: the fast green signal — oracle pins + one engine
# per family, ~50s total on the 1-core image (full suite: ~770s).
# Central nodeid list rather than per-file decorators so the tier's
# composition is reviewable in one place.
_QUICK = (
    "test_pyeval_oracle.py",  # every oracle pin
    "test_packing.py",        # layout round-trip properties
    "test_device_bfs.py::test_device_engine_shipped_cfg_published_count",
    "test_device_bfs.py::test_device_engine_leak_counterexample",
    "test_sharded_device.py::test_sharded_device_counts_identical_across_meshes[8]",
    "test_codegen.py::test_compiled_shipped_cfg_published_count",
    "test_actions.py::test_successors_match_oracle[shipped]",
    "test_engine.py::test_engine_shipped_cfg_published_count",
    "test_frontend.py::TestOracles::test_shipped_cfg_state_count",
    "test_native_baseline.py::test_native_baseline_shipped_cfg_published_count",
)


def pytest_collection_modifyitems(items):
    for item in items:
        rel = item.nodeid.split("tests/")[-1]
        if any(
            rel == q or rel.startswith(q + "::") or rel.startswith(q)
            and q.endswith(".py")
            for q in _QUICK
        ):
            item.add_marker(pytest.mark.quick)


# ---- legacy bench artifacts (ledger / validator ingestion) ----------

# (value, distinct_states, extra keys) per round: the driver-wrapper
# artifact shapes ledger ingestion must keep reading — pre-schema
# (r1-r4: no ``bench_schema``) through schema 2 (r5).  The figures are
# made up for the test; they are not measurements of anything.
_LEGACY_ROUNDS = {
    1: (20000.0, 4_000_000, {}),
    2: (1_000_000.0, 20_000_000, {}),
    3: (1_500_000.0, 30_000_000, {"compile_warmup_s": 800.0}),
    4: (2_000_000.0, 60_000_000, {"host_wait_s": 18.0}),
    5: (
        3_150_000.0, 170_000_000,
        {
            "bench_schema": 2, "stop_reason": "max_states",
            "vs_baseline_definition": "native_8w_extrapolated",
            "compile_warmup_s": 46.0,
            "sustained_final_60s_sps": None, "host_wait_s": 43.0,
        },
    ),
}


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    """A directory of ``BENCH_r01..r05.json`` driver-wrapper artifacts
    (see ``_LEGACY_ROUNDS``)."""
    d = tmp_path_factory.mktemp("bench_artifacts")
    for n, (value, states, extra) in _LEGACY_ROUNDS.items():
        parsed = {
            "metric": "distinct states/sec on scaled compaction.tla",
            "value": value,
            "unit": "states/sec/chip",
            "vs_baseline": 0.5,
            "levels": 7,
            "distinct_states": states,
            "engine": f"device_bfs r{n}",
            **extra,
        }
        wrapper = {
            "n": n, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(parsed) + "\n", "parsed": parsed,
        }
        (d / f"BENCH_r0{n}.json").write_text(json.dumps(wrapper))
    return str(d)
