"""Native runtime components, built from source on first use.

``build()`` compiles ``logstore.cpp`` (a CPython extension) and
``build_baseline()`` compiles ``compaction_bfs.cpp`` (the standalone
TLC-class baseline checker) with the system toolchain directly (g++; no
pybind11 in the image) into this package directory.  No binary is
tracked: an artifact is rebuilt whenever the hash of its source and
compile command differs from the stamp written beside it — file times
mean nothing in a copied or freshly checked-out tree.  A ``_logstore``
build failure falls back to the pure-python implementation in
``engine/statelog.py``.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sysconfig

_DIR = os.path.dirname(__file__)


def _build(src: str, out: str, cmd: list, force: bool) -> str:
    """Run ``cmd + [src, "-o", out]`` unless ``out`` was already built
    from this exact source and command; returns ``out``."""
    with open(src, "rb") as f:
        want = hashlib.sha256(
            f.read() + "\0".join(cmd).encode()
        ).hexdigest()
    stamp = out + ".srchash"
    if not force and os.path.exists(out):
        try:
            with open(stamp) as f:
                if f.read().strip() == want:
                    return out
        except OSError:
            pass
    # compile beside the target and rename: a concurrent builder or
    # loader never sees a half-written artifact
    tmp = f"{out}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            cmd + [src, "-o", tmp], check=True, capture_output=True
        )
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(stamp, "w") as f:
        f.write(want + "\n")
    return out


def _ext_path() -> str:
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_DIR, "_logstore" + suffix)


def build(force: bool = False) -> str:
    """Compile the extension if needed; returns the .so path."""
    include = sysconfig.get_paths()["include"]
    return _build(
        os.path.join(_DIR, "logstore.cpp"),
        _ext_path(),
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", f"-I{include}"],
        force,
    )


def build_baseline(force: bool = False) -> str:
    """Compile the TLC-class native baseline checker
    (``compaction_bfs.cpp``) into a standalone binary; returns its path.
    See BASELINE.md: this is the in-image stand-in for 8-worker CPU TLC
    (no JVM in the image)."""
    return _build(
        os.path.join(_DIR, "compaction_bfs.cpp"),
        os.path.join(_DIR, "compaction_bfs"),
        ["g++", "-O2", "-std=c++17", "-pthread"],
        force,
    )


def run_baseline(
    m: int, k: int, v: int, c: int, crash: int, producer: bool,
    retain: bool, budget_s: float, threads: int = 1,
    table_log2: int | None = None,
) -> dict:
    """Run the native baseline checker; returns its JSON result dict.

    ``table_log2`` sizes the fingerprint table (slots = 2^n); small
    differential-test configs should pass ~22 so each run does not
    zero-fill the 1 GB bench-sized default table."""
    import json

    binary = build_baseline()
    if table_log2 is None:
        table_log2 = 27 if producer else 22
    p = subprocess.run(
        [
            binary, str(m), str(k), str(v), str(c), str(crash),
            "1" if producer else "0", "1" if retain else "0",
            str(budget_s), str(threads), str(table_log2),
        ],
        capture_output=True, text=True,
    )
    if p.returncode not in (0, 1):
        raise RuntimeError(f"baseline checker failed: {p.stderr[:500]}")
    res = json.loads(p.stdout.strip().splitlines()[-1])
    if res.get("violated"):
        # a violated run stops BFS early — its states/sec is measured
        # against a partial exploration and must never be used as a
        # throughput baseline (ADVICE r3)
        raise RuntimeError(
            "native baseline run hit an invariant violation; its "
            f"partial-run throughput is not a valid baseline: {res}"
        )
    return res


def load_logstore():
    """Returns the native _logstore module, building it if necessary.

    Raises on toolchain/build failure — callers fall back to the python
    implementation.
    """
    import importlib

    build()
    return importlib.import_module("pulsar_tlaplus_tpu.native._logstore")
