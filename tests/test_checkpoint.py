"""Checkpoint/resume tests (SURVEY.md §2.2-E8): a truncated run must resume
to the exact published state count, and traces must span checkpoints."""

import dataclasses
import os

import pytest

from pulsar_tlaplus_tpu.engine.bfs import Checker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import assert_valid_counterexample


def test_checkpoint_resume_exact_count(tmp_path):
    m = CompactionModel(pe.SHIPPED_CFG)
    path = str(tmp_path / "ck.npz")
    r1 = Checker(
        m, visited_cap=1 << 16, checkpoint_path=path,
        checkpoint_every=3, max_states=10_000,
    ).run()
    assert r1.truncated and r1.distinct_states < 45198
    r2 = Checker(m, visited_cap=1 << 16, checkpoint_path=path).run(resume=True)
    assert r2.distinct_states == 45198
    assert r2.diameter == 20
    assert not r2.truncated


def test_checkpoint_config_mismatch_rejected(tmp_path):
    m = CompactionModel(pe.SHIPPED_CFG)
    path = str(tmp_path / "ck.npz")
    Checker(
        m, visited_cap=1 << 16, checkpoint_path=path,
        checkpoint_every=2, max_states=5_000,
    ).run()
    other = CompactionModel(
        dataclasses.replace(pe.SHIPPED_CFG, max_crash_times=2)
    )
    with pytest.raises(ValueError, match="different model configuration"):
        Checker(other, checkpoint_path=path).run(resume=True)


def test_trace_spans_checkpoint(tmp_path):
    m = CompactionModel(pe.SHIPPED_CFG)
    path = str(tmp_path / "ck.npz")
    inv = ("CompactedLedgerLeak",)
    r1 = Checker(
        m, invariants=inv, visited_cap=1 << 16, checkpoint_path=path,
        checkpoint_every=2, max_states=8_000,
    ).run()
    assert r1.truncated and r1.violation is None
    r2 = Checker(m, invariants=inv, visited_cap=1 << 16, checkpoint_path=path).run(
        resume=True
    )
    assert r2.violation == "CompactedLedgerLeak"
    assert r2.diameter == 12
    assert_valid_counterexample(
        pe.SHIPPED_CFG, r2.trace, r2.trace_actions, "CompactedLedgerLeak"
    )


# ---- concurrent frame writers (r11, checking-as-a-service) ----------
# Two run_ids sharing a checkpoint dir (the daemon's jobs/<id>/ layout
# collapses to this when paths collide) must never clobber each other's
# frames, tmp files, or stale-tmp cleanup.


def _hammer_frames(path, sig, run_id, payload, n, errors):
    from pulsar_tlaplus_tpu.utils import ckpt
    import numpy as np

    try:
        for seq in range(n):
            ckpt.save_frame(
                path, sig,
                {"payload": np.full(256, payload, np.int64)},
                meta={"run_id": run_id, "frame_seq": seq},
            )
    except Exception as e:  # noqa: BLE001 — surfaced by the test body
        errors.append(e)


def test_concurrent_writers_same_path_never_torn(tmp_path):
    """Two writers racing on ONE path: every load observes a COMPLETE
    frame from one of them (per-writer-unique tmp names make the
    os.replace publish atomic even under contention; the pre-r11 fixed
    tmp name let writer A install writer B's half-filled tmp)."""
    import threading

    import numpy as np

    from pulsar_tlaplus_tpu.utils import ckpt

    path = str(tmp_path / "frame.npz")
    sig = ckpt.config_sig(test="race")
    errors: list = []
    writers = [
        threading.Thread(
            target=_hammer_frames,
            args=(path, sig, rid, val, 30, errors),
        )
        for rid, val in (("run-a", 1), ("run-b", 2))
    ]
    for t in writers:
        t.start()
    torn = []
    while any(t.is_alive() for t in writers):
        try:
            d = ckpt.load_frame(path, sig)
        except FileNotFoundError:
            continue  # before the first publish
        except ValueError as e:
            torn.append(repr(e))
            break
        p = np.asarray(d["payload"])
        if not (p == p[0]).all() or int(p[0]) not in (1, 2):
            torn.append(f"mixed payload {set(p.tolist())}")
            break
    for t in writers:
        t.join()
    assert not errors, errors
    assert not torn, torn
    # final frame: complete, signed, from one of the two writers
    d = ckpt.load_frame(path, sig)
    assert int(np.asarray(d["payload"])[0]) in (1, 2)
    assert ckpt.frame_meta(d)["run_id"] in ("run-a", "run-b")
    # no tmp survives the writers
    assert not [
        n for n in os.listdir(tmp_path) if ".tmp." in n
    ]


def test_shared_dir_frames_and_cleanup_are_isolated(tmp_path):
    """Two run_ids with sibling frame paths in ONE dir: concurrent
    writes land in their own frames, and one path's stale-tmp cleanup
    never touches the sibling's tmp or frame."""
    import threading

    import numpy as np

    from pulsar_tlaplus_tpu.utils import ckpt

    pa = str(tmp_path / "frame.a.npz")
    pb = str(tmp_path / "frame.b.npz")
    sig = ckpt.config_sig(test="shared-dir")
    errors: list = []
    ts = [
        threading.Thread(
            target=_hammer_frames, args=(p, sig, rid, v, 20, errors)
        )
        for p, rid, v in ((pa, "run-a", 1), (pb, "run-b", 2))
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    da, db = ckpt.load_frame(pa, sig), ckpt.load_frame(pb, sig)
    assert int(np.asarray(da["payload"])[0]) == 1
    assert int(np.asarray(db["payload"])[0]) == 2
    assert ckpt.frame_meta(da)["run_id"] == "run-a"
    assert ckpt.frame_meta(db)["run_id"] == "run-b"
    # stale tmps: cleanup is scoped to ITS frame path — a crashed
    # writer's debris for A never takes B's live tmp (or frame) along
    for stale in (
        pa + ".tmp.npz",              # pre-r11 fixed name
        pa + ".tmp.999.888.npz",      # per-writer name, dead writer
    ):
        with open(stale, "wb") as f:
            f.write(b"half-written")
    live_b = pb + ".tmp.777.666.npz"
    with open(live_b, "wb") as f:
        f.write(b"in flight")
    assert ckpt.cleanup_stale_tmp(pa)
    assert not [
        n for n in os.listdir(tmp_path)
        if n.startswith("frame.a.npz.tmp.")
    ]
    assert os.path.exists(live_b)  # B's tmp untouched
    assert os.path.exists(pb)      # B's frame untouched
    assert not ckpt.cleanup_stale_tmp(pa)  # idempotent: nothing left
    os.remove(live_b)


# ---- the frame's writer: an npz deflated in blocks (ISSUE 45) -------
# Same container, same bytes in every member, same level; where the
# deflate runs goes by the frame's size.

import io  # noqa: E402
import json  # noqa: E402
import threading  # noqa: E402
import zipfile  # noqa: E402

import numpy as np  # noqa: E402

from pulsar_tlaplus_tpu.utils import ckpt, faults  # noqa: E402

BLOCK = ckpt.DEFLATE_BLOCK
NPY_HEAD = 128  # a v1.0 header of a 1-D array, padded
META = {"run_id": "r", "frame_seq": 3, "level": 7}


def _frame_like(n, seed=0):
    """A frame's kinds of arrays at ``n`` states: random key words,
    sorted slots, small-integer lanes, packed rows, scalars."""
    rng = np.random.default_rng(seed)
    return {
        "fpk0": rng.integers(0, 2**32, n, dtype=np.uint32),
        "fpk1": rng.integers(0, 2**32, n, dtype=np.uint32),
        "fp_slot": np.sort(rng.integers(0, 4 * n + 1, n)).astype(np.int64),
        "rows": rng.integers(0, 2**20, 2 * n).astype(np.uint32),
        "parent": np.sort(rng.integers(0, n + 1, n)).astype(np.int32),
        "lane": rng.integers(0, 19, n).astype(np.int32),
        "n_visited": np.int64(n),
        "level_sizes": np.arange(24, dtype=np.int64),
    }


def _ramp(nbytes, dtype):
    """``nbytes`` of ``dtype`` that neither vanish under deflate nor
    repeat block to block."""
    n = nbytes // np.dtype(dtype).itemsize
    return (np.arange(n, dtype=np.uint64) * 2654435761 % 65521).astype(dtype)


PAYLOADS = {
    **{f"tiny-{np.dtype(t).name}": {"a": _ramp(400, t)}
       for t in (np.uint32, np.int32, np.int64, np.uint8)},
    # the member (header + data) ends one byte short of a block, on it,
    # one byte past it, and one past the second
    **{f"block{k:+d}": {"a": _ramp(BLOCK - NPY_HEAD + k, np.uint8)}
       for k in (-1, 0, 1)},
    "two-blocks+1": {"a": _ramp(2 * BLOCK - NPY_HEAD + 1, np.uint8)},
    **{f"several-MB-{np.dtype(t).name}": {"a": _ramp(6 << 20, t)}
       for t in (np.uint32, np.int64)},
    "scalar-0d": {"a": np.int64(-5), "b": np.float64(0.25)},
    "empty": {"a": np.zeros((0,), np.uint32), "b": np.zeros((0, 3))},
    "strided-view": {"a": _ramp(4000, np.int32)[::3],
                     "b": _ramp(4096, np.uint32).reshape(32, 32).T},
    "frame-like": _frame_like(150_000),
}


def _member_blocks(arrays):
    """Blocks a frame's members are cut into, reckoned from the bytes
    ``np.save`` gives each (what ``ckpt_deflate_blocks`` must read)."""
    total = 0
    for a in arrays.values():
        buf = io.BytesIO()
        np.save(buf, np.asarray(a))
        total += -(-buf.getbuffer().nbytes // BLOCK)
    return total


def _frame_members(sig, wall_s, meta, arrays):
    """Every member ``save_frame`` writes, as the parent's writer was
    handed them."""
    out = dict(
        __format__=np.int64(ckpt.FORMAT_VERSION),
        sig=np.frombuffer(sig.encode(), dtype=np.uint8),
        wall_s=np.float64(wall_s),
    )
    if meta:
        out["__meta__"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)
    out.update(arrays)
    return out


@pytest.mark.parametrize("name", PAYLOADS)
def test_frame_round_trip(tmp_path, name):
    arrays = PAYLOADS[name]
    p = str(tmp_path / "f.npz")
    st = {}
    nbytes, write_s, retries = ckpt.save_frame(
        p, "sig-x", arrays, wall_s=12.5, meta=META, stats=st)
    assert nbytes == os.path.getsize(p) and retries == 0 and write_s > 0
    d = ckpt.load_frame(p, "sig-x")
    assert ckpt.frame_meta(d) == META
    assert float(d["wall_s"]) == 12.5
    assert int(d["__format__"]) == ckpt.FORMAT_VERSION == 2
    assert set(d.files) == {"__format__", "sig", "wall_s", "__meta__",
                            *arrays}
    for k, want in arrays.items():
        got, want = d[k], np.asarray(want)
        assert got.dtype == want.dtype and got.shape == want.shape, k
        assert np.array_equal(got, want), k
    assert st["deflate_blocks"] == _member_blocks(
        _frame_members("sig-x", 12.5, META, arrays))
    assert st["deflate_threads"] >= 1 and st["deflate_cpu_s"] >= 0.0
    with pytest.raises(ValueError, match="different"):
        ckpt.load_frame(p, "sig-y")
    assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]


@pytest.mark.parametrize("name", ["tiny-uint32", "block+1", "strided-view",
                                  "several-MB-uint32", "frame-like"])
def test_frame_is_the_npz_numpy_writes(tmp_path, name):
    """Plain ``np.load`` and ``zipfile`` open it; its members' names,
    order and ``.npy`` bytes are ``np.savez_compressed``'s."""
    arrays = PAYLOADS[name]
    p, ref = str(tmp_path / "f.npz"), str(tmp_path / "ref.npz")
    ckpt.save_frame(p, "s", arrays, wall_s=1.0, meta=META)
    np.savez_compressed(ref, **_frame_members("s", 1.0, META, arrays))
    with np.load(p) as d:
        assert all(np.array_equal(d[k], np.asarray(v))
                   for k, v in arrays.items())
    with zipfile.ZipFile(p) as zn, zipfile.ZipFile(ref) as zr:
        assert zn.testzip() is None
        assert zn.namelist() == zr.namelist()
        for n in zr.namelist():
            assert zn.read(n) == zr.read(n), n
            assert zn.getinfo(n).compress_type == zipfile.ZIP_DEFLATED
            assert zn.getinfo(n).CRC == zr.getinfo(n).CRC


def test_frame_size_within_one_percent_of_numpys(tmp_path):
    arrays = _frame_like(400_000)  # 12.8 MB: 50 blocks
    p, ref = str(tmp_path / "f.npz"), str(tmp_path / "ref.npz")
    nbytes, _, _ = ckpt.save_frame(p, "s", arrays)
    np.savez_compressed(ref, **_frame_members("s", 0.0, None, arrays))
    assert nbytes <= os.path.getsize(ref) * 1.01


def test_frame_the_parent_wrote_still_loads(tmp_path):
    """A frame as ``save_frame`` wrote it through ``np.savez_compressed``
    (before ISSUE 45) is read as before."""
    arrays = _frame_like(20_000, seed=3)
    p = str(tmp_path / "old.npz")
    np.savez_compressed(p, **_frame_members("old-sig", 3.5, META, arrays))
    d = ckpt.load_frame(p, "old-sig")
    assert ckpt.frame_meta(d) == META and float(d["wall_s"]) == 3.5
    for k, want in arrays.items():
        assert np.array_equal(d[k], want), k
    cols = ckpt.pack_fpset((np.r_[arrays["fpk0"], ckpt._SENTINEL],
                            np.r_[arrays["fpk1"], ckpt._SENTINEL]))
    np.savez_compressed(p, **_frame_members("old-sig", 0.0, None, cols))
    back = ckpt.unpack_fpset(ckpt.load_frame(p, "old-sig"), 2)
    assert np.array_equal(back[0][:-1], arrays["fpk0"])


@pytest.mark.parametrize("threads", [2, 5])
def test_pool_and_caller_write_the_same_file(tmp_path, monkeypatch, threads):
    """Where the deflate runs changes no byte of the frame."""
    arrays = PAYLOADS["frame-like"]
    a, b = str(tmp_path / "a.npz"), str(tmp_path / "b.npz")
    monkeypatch.setattr(ckpt, "_deflate_threads", lambda n: 1)
    sa = ckpt._write_npz(a, arrays)
    monkeypatch.setattr(ckpt, "_deflate_threads", lambda n: threads)
    sb = ckpt._write_npz(b, arrays)
    assert (sa["deflate_threads"], sb["deflate_threads"]) == (1, threads)
    assert sa["deflate_blocks"] == sb["deflate_blocks"]
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()


def test_members_past_four_gigabytes_go_by_zip64(tmp_path, monkeypatch):
    """The zip64 fields, met at a size a test can write: every size and
    offset from 1,000 on is handed to them."""
    monkeypatch.setattr(ckpt, "_ZIP64_FROM", 1000)
    arrays = PAYLOADS["frame-like"]
    p = str(tmp_path / "f.npz")
    ckpt.save_frame(p, "s", arrays)
    d = ckpt.load_frame(p, "s")
    assert all(np.array_equal(d[k], v) for k, v in arrays.items())
    with zipfile.ZipFile(p) as z:
        assert z.testzip() is None
        big = [i for i in z.infolist() if i.file_size >= 1000]
        assert big and all(i.extract_version == 45 for i in big)


def test_deflate_threads_go_by_the_frames_bytes(monkeypatch):
    """``min(8, usable cores - 1)`` from two blocks a worker on, the
    caller's own thread under that."""
    for cores, want in ((1, 1), (2, 1), (4, 3), (9, 8), (13, 8), (64, 8)):
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda _pid, n=cores: set(range(n)))
        edge = 2 * BLOCK * want
        assert ckpt._deflate_threads(edge) == want
        assert ckpt._deflate_threads(edge - 1) == 1
        assert ckpt._deflate_threads(0) == 1
    monkeypatch.delattr(os, "sched_getaffinity")
    monkeypatch.setattr(os, "cpu_count", lambda: 5)
    assert ckpt._deflate_threads(1 << 30) == 4


def test_small_frame_inline_large_frame_pooled(tmp_path):
    small, large = {}, {}
    ckpt.save_frame(str(tmp_path / "s.npz"), "s", _frame_like(10_928),
                    stats=small)
    assert small["deflate_threads"] == 1  # level 5's frame: 350 KB
    if ckpt._usable_cores() <= 2:
        pytest.skip("no core to spare: every frame is deflated inline")
    arrays = _frame_like(300_000)  # 9.6 MB: over two blocks a worker
    ckpt.save_frame(str(tmp_path / "l.npz"), "s", arrays, stats=large)
    assert large["deflate_threads"] == min(8, ckpt._usable_cores() - 1) > 1
    assert large["deflate_blocks"] == _member_blocks(
        _frame_members("s", 0.0, None, arrays))
    assert large["deflate_cpu_s"] > 0.0


def test_injected_write_fault_retries_once_with_the_pool(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PTT_FAULT", "ckpt_fail@frame:3")
    faults.reset()
    monkeypatch.setattr(ckpt, "WRITE_BACKOFF_S", 0.001)
    monkeypatch.setattr(ckpt, "_deflate_threads", lambda n: 4)
    arrays = PAYLOADS["frame-like"]
    p = str(tmp_path / "f.npz")
    st = {}
    try:
        nbytes, _, retries = ckpt.save_frame(p, "s", arrays, meta=META,
                                             stats=st)
    finally:
        monkeypatch.delenv("PTT_FAULT")
        faults.reset()
    assert retries == 1 and nbytes == os.path.getsize(p)
    assert st["deflate_threads"] == 4
    d = ckpt.load_frame(p, "s")
    assert all(np.array_equal(d[k], v) for k, v in arrays.items())
    assert os.listdir(tmp_path) == ["f.npz"]


@pytest.mark.parametrize("threads", [1, 4])
def test_worker_oserror_surfaces_in_the_retry_loop(
        tmp_path, monkeypatch, threads):
    """A block's ``OSError`` reaches the caller inside the retry loop:
    the attempts are counted, the half-written tmp goes each time, the
    frame that was there stays."""
    calls, real = [], ckpt._deflate_block

    def failing(block):
        calls.append(len(block))
        if len(calls) % 7 == 0:
            raise OSError(5, "Input/output error (a worker's)")
        return real(block)

    monkeypatch.setattr(ckpt, "_deflate_block", failing)
    monkeypatch.setattr(ckpt, "_deflate_threads", lambda n: threads)
    monkeypatch.setattr(ckpt, "WRITE_BACKOFF_S", 0.001)
    p = str(tmp_path / "f.npz")
    with open(p, "wb") as f:
        f.write(b"the frame before")
    with pytest.raises(OSError, match="a worker's"):
        ckpt.save_frame(p, "s", PAYLOADS["frame-like"])
    assert len(calls) >= 7 * (ckpt.MAX_WRITE_RETRIES + 1)
    assert os.listdir(tmp_path) == ["f.npz"]
    with open(p, "rb") as f:
        assert f.read() == b"the frame before"
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ckpt-deflate")]


def test_engine_counts_where_the_deflate_ran(tmp_path):
    """``DeviceChecker`` folds the writer's counters into ``last_stats``
    and every ``ckpt_frame`` event; a small model's frames are inline."""
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
    from tests.helpers import SMALL_CONFIGS

    tel = str(tmp_path / "t.jsonl")
    ck = DeviceChecker(
        CompactionModel(SMALL_CONFIGS["producer_on"]), invariants=(),
        check_deadlock=False, sub_batch=64, visited_cap=1 << 9,
        frontier_cap=1 << 9, checkpoint_path=str(tmp_path / "f.npz"),
        checkpoint_every=4, telemetry=tel,
    )
    assert ck.run().distinct_states == 1654
    st = ck.last_stats
    with open(tel) as f:
        evs = [json.loads(x) for x in f]
    frames = [e for e in evs if e["event"] == "ckpt_frame"]
    assert len(frames) == st["ckpt_frames"] >= 3
    assert st["ckpt_deflate_threads"] == 1
    assert all(e["deflate_threads"] == 1 for e in frames)
    assert st["ckpt_deflate_blocks"] == sum(
        e["deflate_blocks"] for e in frames) >= 10 * len(frames)
    assert 0 < st["ckpt_deflate_cpu_s"] <= st["ckpt_npz_s"]
    assert st["ckpt_deflate_speedup"] == pytest.approx(
        st["ckpt_deflate_cpu_s"] / st["ckpt_npz_s"])
    res = [e for e in evs if e["event"] == "result"][-1]["stats"]
    for k in ("ckpt_deflate_threads", "ckpt_deflate_blocks",
              "ckpt_deflate_cpu_s", "ckpt_deflate_speedup"):
        assert res[k] == pytest.approx(st[k], abs=1e-3), k
