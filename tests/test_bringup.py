"""Start-up and device plumbing (PR 23 bring-up): the one compile-cache
helper, the Pallas interpret rule, ``-workers N`` with too few devices,
and the source-hash native build."""

import os
import shutil

import jax
import pytest

from pulsar_tlaplus_tpu import native
from pulsar_tlaplus_tpu.ops import tiles
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


def test_compile_cache_env_wins(monkeypatch, config_updates):
    """``JAX_COMPILATION_CACHE_DIR`` from outside is left to JAX: no
    directory is set in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert device.setup_compile_cache() == "/some/dir"
    assert config_updates == []


def test_compile_cache_default_is_under_checkout(
    monkeypatch, config_updates, tmp_path
):
    """Unset: ``<checkout>/.jax_cache`` whatever the working
    directory."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    want = os.path.join(CHECKOUT, ".jax_cache")
    assert device.setup_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


def test_pallas_interpret_follows_backend(monkeypatch):
    assert jax.default_backend() == "cpu" and tiles.interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not tiles.interpret()


def test_pallas_interpret_swallows_nothing(monkeypatch):
    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        tiles.interpret()


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


def test_predict_unknown_device_is_an_error():
    """Host-link figures are measured per device kind; an unknown
    accelerator never inherits another device's."""
    from pulsar_tlaplus_tpu.tune import predict

    ref = {"backend": "tpu", "device_kind": "TPU v9"}
    with pytest.raises(ValueError, match="TPU v9"):
        predict._device_link(ref, {}, "rtt_s")
    cal = {"rtt_s": 0.002}
    assert predict._device_link(ref, cal, "rtt_s") == 0.002
