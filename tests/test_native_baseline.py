"""The native (C++) TLC-class baseline checker must agree with the
published state-space oracles — it exists to make the BASELINE.md
comparison honest (BASELINE.md round-3; /root/reference/compaction.tla:23),
so its semantics are pinned against the same counts as every engine."""

import pytest

from pulsar_tlaplus_tpu import native
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS, needs_native_binary

# every test here shells out to the baseline binary, built from source
# on first use; a host with no C++ toolchain SKIPS the whole module
pytestmark = needs_native_binary


def _run(c, budget_s=300.0):
    return native.run_baseline(
        c.message_sent_limit, c.num_keys, c.num_values,
        c.compaction_times_limit, c.max_crash_times, c.model_producer,
        c.retain_null_key, budget_s, table_log2=22,
    )


def test_native_baseline_shipped_cfg_published_count():
    r = _run(pe.SHIPPED_CFG)
    assert not r["truncated"] and not r["violated"]
    assert r["distinct_states"] == 45198  # compaction.tla:23
    assert r["levels"] == 20


def test_native_baseline_full_cfg_published_count():
    """Producer modeled, RetainNullKey=FALSE: the 253,361-state /
    diameter-23 oracle (compaction.tla:23)."""
    r = native.run_baseline(
        3, 2, 2, 3, 1, True, False, 300.0, table_log2=22
    )
    assert not r["truncated"] and not r["violated"]
    assert r["distinct_states"] == 253361
    assert r["levels"] == 23


@pytest.mark.parametrize("name", ["producer_on", "two_crashes", "no_retain"])
def test_native_baseline_matches_oracle_small(name):
    c = SMALL_CONFIGS[name]
    want = pe.check(c, invariants=())
    r = _run(c)
    assert not r["truncated"] and not r["violated"]
    assert r["distinct_states"] == want.distinct_states
    assert r["levels"] == want.diameter
