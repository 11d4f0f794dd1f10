"""Start-up and device plumbing (PR 23 bring-up): the one compile-cache
helper and what it does to a second process (PR 30), ``-workers N``
with too few devices, and the source-hash native build."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from pulsar_tlaplus_tpu import native
from pulsar_tlaplus_tpu.obs import report
from pulsar_tlaplus_tpu.utils import device

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Every ``jax.config.update`` the code under test makes."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v))
    )
    return calls


# what ``setup_compile_cache`` sets whichever way the directory comes:
# both of JAX's write thresholds are taken away
KEEPS_EVERY_PROGRAM = [
    ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ("jax_persistent_cache_min_entry_size_bytes", -1),
]
THRESHOLD_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def test_compile_cache_env_wins(monkeypatch, config_updates):
    """``JAX_COMPILATION_CACHE_DIR`` from outside is left to JAX: no
    directory is set in code, the thresholds are."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.delenv(THRESHOLD_ENV)  # the suite's own, conftest.py
    assert device.setup_compile_cache() == "/some/dir"
    assert config_updates == KEEPS_EVERY_PROGRAM


def test_compile_cache_default_is_under_checkout(
    monkeypatch, config_updates, tmp_path
):
    """Unset: ``<checkout>/.jax_cache`` whatever the working
    directory."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv(THRESHOLD_ENV)
    monkeypatch.chdir(tmp_path)
    want = os.path.join(CHECKOUT, ".jax_cache")
    assert device.setup_compile_cache() == want
    assert config_updates == KEEPS_EVERY_PROGRAM + [
        ("jax_compilation_cache_dir", want)
    ]


def test_compile_cache_threshold_from_outside_is_left_alone(
    monkeypatch, config_updates
):
    """A threshold in the environment wins as the directory does: JAX
    reads it itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setenv(THRESHOLD_ENV, "2.5")
    device.setup_compile_cache()
    assert config_updates == KEEPS_EVERY_PROGRAM[1:]


def _check_in_a_new_process(cache_dir, telemetry):
    """``cli check`` of the shipped binding with the leak invariant in
    a process of its own, as a user's shell would start it: the cache
    directory from outside, the thresholds the helper's."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir
    )
    del env[THRESHOLD_ENV]
    spec = os.path.join(CHECKOUT, "specs", "compaction.tla")
    proc = subprocess.run(
        [
            sys.executable, "-m", "pulsar_tlaplus_tpu.cli", "check", spec,
            "-config", os.path.join(CHECKOUT, "specs", "compaction.cfg"),
            "-invariant", "CompactedLedgerLeak", "-telemetry", telemetry,
        ],
        capture_output=True, text=True, timeout=180, env=env, cwd=CHECKOUT,
    )
    assert proc.returncode == 1, proc.stderr  # the counterexample
    events, errors = report.load_events(telemetry)
    assert not errors, errors
    # all but the last line, which says how long the check took
    return report.result(events), proc.stdout.rsplit("\n", 2)[0]


def test_second_process_compiles_nothing(tmp_path):
    """The effect: with every executable kept, the same check in a
    second process makes no backend compile; each program the first
    compiled is a cache hit, and verdict, counts and the printed trace
    are the first's."""
    cache_dir = str(tmp_path / "cache")
    first, out1 = _check_in_a_new_process(cache_dir, str(tmp_path / "1.jsonl"))
    second, out2 = _check_in_a_new_process(
        cache_dir, str(tmp_path / "2.jsonl")
    )
    compiled = first["stats"]["jit_backend_compiles"]
    assert compiled > 0 and first["stats"]["jit_cache_hits"] == 0
    assert second["stats"]["jit_backend_compiles"] == 0
    assert second["stats"]["jit_cache_misses"] == 0
    assert second["stats"]["jit_cache_hits"] == compiled
    assert second["stats"]["jit_traces"] == first["stats"]["jit_traces"]
    for k in ("distinct_states", "diameter", "truncated"):
        assert second[k] == first[k], k
    assert (second["distinct_states"], second["diameter"]) == (25515, 12)
    assert "CompactedLedgerLeak" in out1 and out2 == out1


def test_workers_beyond_device_count_is_an_error(capsys):
    """``-workers N`` never silently runs on fewer devices."""
    from pulsar_tlaplus_tpu import cli

    n = len(jax.devices()) + 1
    with pytest.raises(SystemExit) as ei:
        cli.main(
            [
                "check", os.path.join(CHECKOUT, "specs", "compaction.tla"),
                "-workers", str(n),
            ]
        )
    assert f"-workers {n} needs {n} devices" in str(ei.value)
    assert "distinct states" not in capsys.readouterr().out


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_native_build_is_keyed_on_source_hash(tmp_path):
    """Rebuild when the source changes, not when file times do."""
    src = tmp_path / "prog.cpp"
    out = str(tmp_path / "prog")
    cmd = ["g++", "-O0"]
    src.write_text("int main() { return 0; }\n")
    assert native._build(str(src), out, cmd, False) == out
    first = os.stat(out).st_mtime_ns
    os.utime(src)  # a newer source time alone means nothing
    native._build(str(src), out, cmd, False)
    assert os.stat(out).st_mtime_ns == first
    src.write_text("int main() { return 1; }\n")
    native._build(str(src), out, cmd, False)
    assert os.stat(out).st_mtime_ns != first


@pytest.mark.parametrize(
    "flag,value",
    [
        ("-visited", "sort"), ("-compact", "sort"),
        ("-probe-impl", "tile"), ("-expand-impl", "tile"),
        ("-sieve-impl", "tile"),
    ],
)
def test_removed_selector_flag_is_refused(flag, value, capsys):
    """The five kernel selectors are gone with their second
    implementations: a command line that still passes one exits 2 with
    argparse's message, it does not run some other path."""
    from pulsar_tlaplus_tpu import cli

    with pytest.raises(SystemExit) as ei:
        cli.main(
            [
                "check", os.path.join(CHECKOUT, "specs", "compaction.tla"),
                flag, value,
            ]
        )
    assert ei.value.code == 2
    io = capsys.readouterr()
    assert "unrecognized arguments" in io.err and flag in io.err
    assert "distinct states" not in io.out
