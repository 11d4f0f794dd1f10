"""Shared checkpoint-frame layer — the run-survivability substrate.

TLC's killer production feature is that a week-long run survives
crashes via its ``states/`` checkpoint directory.  This module is the
engine-agnostic half of that story for the JAX engines: an atomic
``tmp + os.replace`` npz frame with a config signature, a format
version, a compacted-occupancy codec for hash-table (fpset) visited
sets, and a preemption watcher that turns SIGTERM/SIGINT into a
"checkpoint at the next level boundary" request (the TPU-VM
preemption contract).

Design rules every engine follows:

- **Atomicity**: a frame is written to a per-writer-unique
  ``<path>.tmp.<pid>.<tid>.npz`` and ``os.replace``d over the target,
  so a crash mid-write can never leave a half-frame where a resumable
  one used to be — and two writers racing on one path (a job handed
  between daemon scheduling slices) each publish a complete frame,
  never each other's half-filled tmp.
- **Signature**: every frame embeds a config signature (model hash,
  invariant set, key geometry, visited impl, engine format revision).
  ``load_frame`` refuses a frame written under a different
  configuration with a clean error — two specs can never silently
  resume each other's state.
- **Format version**: frames carry ``__format__``; readers accept
  every version up to :data:`FORMAT_VERSION` (v1 frames predate the
  field and the compacted fpset codec; they still load).
- **Compacted fpset occupancy** (:func:`pack_fpset` /
  :func:`unpack_fpset`): hash-table occupancy is scattered across the
  table, so full-column snapshots carry mostly SENTINEL runs.  The
  compacted codec stores only the occupied slots (keys + slot index)
  — frame size scales with the *state count*, not the table tier.
- **Hardened writer**: a transient ``OSError`` (disk full, EIO, an
  NFS hiccup) retries with bounded exponential backoff instead of
  killing an hours-long run over one bad write; the retry count comes
  back to the caller (the ``ckpt_retries`` telemetry breadcrumb).
  Stale ``<path>.tmp.*.npz`` left by a crash mid-write is removed at
  run start (:func:`cleanup_stale_tmp`, scoped to the one frame path
  so sibling jobs sharing a checkpoint dir are never touched) — the
  atomic ``os.replace`` already guarantees it never shadows a valid
  frame, but a dead multi-GB temp file must not squat the checkpoint
  volume either.
- **Block-parallel deflate**: a frame is the ``.npz`` ``np.load``
  opens (one ZIP_DEFLATED ``<name>.npy`` member an array), and where
  its deflate runs adapts to its size alone.  A member's stream is
  :data:`DEFLATE_BLOCK`-byte blocks, each raw-deflated on its own at
  zlib's default level and ended on a sync flush, concatenated in
  order (pigz's scheme: one valid stream).  A frame of
  :func:`_deflate_threads` blocks or more hands them to a pool of
  threads (zlib releases the GIL), the blocks of all its arrays in
  flight together; a smaller one is deflated on the caller's thread.
  Same bytes, same level, same container: readers never know.
"""

from __future__ import annotations

import collections
import io
import json
import os
import signal
import struct
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pulsar_tlaplus_tpu.utils import faults

# v1: full-column fpset snapshots, no version field (round-4/6 sharded
# frames).  v2: ``__format__`` field + compacted-occupancy fpset codec
# + the device_bfs frame layout.  Readers accept <= FORMAT_VERSION.
FORMAT_VERSION = 2

_SENTINEL = np.uint32(0xFFFFFFFF)


def model_sig(model) -> str:
    """Model identity, the first field of a device engine's frame
    signature: hand models carry their Constants in ``.c``; compiled
    specs are identified by module name + constant bindings + lane
    structure (so two different .tla specs can never resume each
    other's frames).  The string is held byte for byte by
    ``tests/test_knobs.py``: a frame written before a change to it
    would no longer restore."""
    c = getattr(model, "c", None)
    if c is not None:
        return repr(c)
    spec = getattr(model, "spec", None)
    if spec is not None:
        return repr(
            (
                getattr(spec.module, "name", "?"),
                sorted(
                    (k, repr(v)) for k, v in spec.constants.items()
                ),
                tuple(getattr(model, "lane_labels", ())),
            )
        )
    return type(model).__name__


def config_sig(**fields) -> str:
    """Canonical signature string from keyword fields (sorted, so two
    call sites building the same logical config always agree)."""
    return repr(tuple(sorted((k, repr(v)) for k, v in fields.items())))


# bounded retry-with-backoff for transient frame-write failures: a
# week-long run must not die because one write hit a full/flaky disk.
# MAX_WRITE_RETRIES retries (so MAX+1 attempts) with exponential
# backoff starting at WRITE_BACKOFF_S; a persistent error still raises.
MAX_WRITE_RETRIES = 3
WRITE_BACKOFF_S = 0.05

# ------------------------------------------- the frame's npz writer

# A member's deflate stream is cut into blocks of this many bytes.  At
# 256 KB the file is 0.04% over np.savez_compressed's (a block starts
# with an empty window) and a 10 MB column is 40 tasks; at 4 MB the
# same pool gave x2.2 where this gives x3.4-8 (ISSUE 45's planning-host
# runs, ``scripts/profile.py deflate``): an array was two or three tasks.
DEFLATE_BLOCK = 256 << 10
# the pool: a thread a usable core, one left to the caller, at most 8
MAX_DEFLATE_THREADS = 8
# blocks handed to the pool and not yet in the file, a worker: enough
# that no worker waits on the caller's CRC and writes, few enough that
# the compressed blocks held in memory stay under a few MB
_BLOCKS_IN_FLIGHT = 4
# an empty final block: what closes a stream of sync-flushed blocks
_DEFLATE_END = b"\x03\x00"
# a size, an offset or a count from here on goes into a zip64 field,
# and its plain field reads all ones
_ZIP64_FROM = _ZIP32_MAX = 0xFFFFFFFF
_ZIP_DATE = (1 << 5) | 1  # 1980-01-01: equal arrays give equal files


def _usable_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # not on this platform
        return os.cpu_count() or 1


def _deflate_threads(nbytes: int) -> int:
    """Threads that deflate a frame of ``nbytes``: ``min(8, usable
    cores - 1)``, and 1 (the caller's own, no pool) for a frame under
    two blocks a worker."""
    workers = min(MAX_DEFLATE_THREADS, max(1, _usable_cores() - 1))
    return workers if nbytes >= 2 * DEFLATE_BLOCK * workers else 1


def _deflate_block(block) -> Tuple[bytes, float]:
    """One block as a raw-deflate stream of its own at the level
    ``np.savez_compressed`` uses, ended on a byte boundary and not
    closed; and the thread's own seconds in it."""
    t0 = time.thread_time()
    c = zlib.compressobj(zlib.Z_DEFAULT_COMPRESSION, zlib.DEFLATED, -15)
    out = c.compress(block) + c.flush(zlib.Z_SYNC_FLUSH)
    return out, time.thread_time() - t0


def _npy_parts(value) -> Tuple[bytes, np.ndarray]:
    """What ``np.save`` writes for ``value``, byte for byte, as the
    ``.npy`` header (v1.0) and a flat byte view of the data in the
    order the header names (a copy only where ``value`` is contiguous
    in neither)."""
    a = np.asanyarray(value)
    d = np.lib.format.header_data_from_array_1_0(a)
    head = io.BytesIO()
    np.lib.format.write_array_header_1_0(head, d)
    body = np.ascontiguousarray(a.T if d["fortran_order"] else a)
    return head.getvalue(), body.reshape(-1).view(np.uint8)


def _blocks(head: bytes, body: np.ndarray):
    """The member ``head + body`` in blocks of :data:`DEFLATE_BLOCK`
    bytes (the last one shorter); only the header's block is a copy."""
    lead = body[: -len(head) % DEFLATE_BLOCK]
    first = memoryview(head + lead.tobytes())
    for off in range(0, len(first), DEFLATE_BLOCK):
        yield first[off: off + DEFLATE_BLOCK]
    for off in range(lead.size, body.size, DEFLATE_BLOCK):
        yield body[off: off + DEFLATE_BLOCK]


class _Member:
    """One zip member while its blocks are in flight."""

    __slots__ = ("name", "usize", "left", "crc", "csize", "offset")

    def __init__(self, name: str, usize: int):
        self.name = name.encode()
        self.usize = usize
        self.left = -(-usize // DEFLATE_BLOCK)  # blocks not yet written
        self.crc = 0
        self.csize = 0
        self.offset: Optional[int] = None

    def local_header(self) -> bytes:
        # sizes in a zip64 field whatever they are, as numpy's writer
        # has them (``force_zip64``): one header shape, patched in place
        return struct.pack(
            "<4s5H3L2H", b"PK\x03\x04", 45, 0, 8, 0, _ZIP_DATE,
            self.crc, _ZIP32_MAX, _ZIP32_MAX, len(self.name), 20,
        ) + self.name + struct.pack("<2H2Q", 1, 16, self.usize, self.csize)

    def central_header(self) -> bytes:
        big = [v for v in (self.usize, self.csize, self.offset)
               if v >= _ZIP64_FROM]
        extra = struct.pack(f"<2H{len(big)}Q", 1, 8 * len(big), *big) \
            if big else b""
        return struct.pack(
            "<4s4B4H3L5H2L", b"PK\x01\x02", 45, 3, 45, 0, 0, 8, 0,
            _ZIP_DATE, self.crc, _zip32(self.csize), _zip32(self.usize),
            len(self.name), len(extra), 0, 0, 0, 0o600 << 16,
            _zip32(self.offset),
        ) + self.name + extra


def _zip32(v: int) -> int:
    return v if v < _ZIP64_FROM else _ZIP32_MAX


def _zip_end(count: int, size: int, offset: int) -> bytes:
    """The end-of-central-directory record, behind a zip64 one where a
    number passes what the plain one holds."""
    out = b""
    if count >= 0xFFFF or max(size, offset) >= _ZIP64_FROM:
        out = struct.pack(
            "<4sQ2H2L4Q", b"PK\x06\x06", 44, 45, 45, 0, 0, count, count,
            size, offset,
        ) + struct.pack("<4sLQL", b"PK\x06\x07", 0, offset + size, 1)
    return out + struct.pack(
        "<4s4H2LH", b"PK\x05\x06", 0, 0, min(count, 0xFFFF),
        min(count, 0xFFFF), _zip32(size), _zip32(offset), 0,
    )


def _write_npz(path: str, arrays: Dict[str, object]) -> Dict[str, object]:
    """``np.savez_compressed(path, **arrays)`` with the deflate in
    blocks (the module's design rule): the blocks of ALL the arrays go
    to the pool in order, at most :data:`_BLOCKS_IN_FLIGHT` a worker
    ahead of the file, and land in it in order; the caller's thread
    takes the CRCs, writes, and patches a member's header when its last
    block has landed.  Returns the frame's ``deflate_threads`` (1: no
    pool, every block deflated here), ``deflate_blocks`` and
    ``deflate_cpu_s`` (the threads' own seconds in zlib, summed)."""
    parts = [(k,) + _npy_parts(v) for k, v in arrays.items()]
    threads = _deflate_threads(sum(len(h) + b.size for _k, h, b in parts))
    pool = ThreadPoolExecutor(
        threads, thread_name_prefix="ckpt-deflate"
    ) if threads > 1 else None
    ahead = threads * _BLOCKS_IN_FLIGHT if pool else 0
    in_flight: collections.deque = collections.deque()
    members: List[_Member] = []
    stats = {"deflate_threads": threads, "deflate_blocks": 0,
             "deflate_cpu_s": 0.0}
    try:
        with open(path, "wb") as f:

            def land():
                m, got = in_flight.popleft()
                data, cpu_s = got.result() if pool else got
                stats["deflate_blocks"] += 1
                stats["deflate_cpu_s"] += cpu_s
                if m.offset is None:
                    m.offset = f.tell()
                    f.write(m.local_header())
                f.write(data)
                m.csize += len(data)
                m.left -= 1
                if not m.left:
                    f.write(_DEFLATE_END)
                    m.csize += len(_DEFLATE_END)
                    f.seek(m.offset)
                    f.write(m.local_header())
                    f.seek(0, os.SEEK_END)

            for name, head, body in parts:
                m = _Member(name + ".npy", len(head) + body.size)
                members.append(m)
                for block in _blocks(head, body):
                    # the member's CRC is whole before its last block
                    # lands: a block is fed before it is written
                    m.crc = zlib.crc32(block, m.crc)
                    in_flight.append((m, pool.submit(
                        _deflate_block, block
                    ) if pool else _deflate_block(block)))
                    while len(in_flight) > ahead:
                        land()
            while in_flight:
                land()
            start = f.tell()
            f.writelines(m.central_header() for m in members)
            f.write(_zip_end(len(members), f.tell() - start, start))
    finally:
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)
    return stats


def save_frame(
    path: str, sig: str, arrays: Dict[str, np.ndarray],
    wall_s: float = 0.0,
    meta: Optional[Dict[str, object]] = None,
    stats: Optional[Dict[str, object]] = None,
) -> Tuple[int, float, int]:
    """Write one checkpoint frame atomically; returns ``(nbytes,
    write_s, retries)`` — size, the seconds the caller was blocked
    HERE (compression + fsync-adjacent filesystem time and a retry's
    backoff, NOT the D2H gather or the pack, which engines time on
    their side: a ``ckpt_frame`` event's ``write_s``, and summed the
    single-chip engine's ``ckpt_npz_s``; the engines' ``ckpt_write_s``
    counter is the frames' whole stall, gather and pack included), and
    how many transient-failure retries the write needed (0 on the happy
    path; the ``ckpt_retries`` breadcrumb).  ``sig`` is the writer's config signature (verified by
    :func:`load_frame`); ``wall_s`` the cumulative run wall time so a
    resumed run's states/sec stays meaningful end to end.  ``meta`` is
    an optional small JSON-able dict (writer run_id, frame_seq, level)
    stored under ``__meta__`` — read back with :func:`frame_meta`; v2
    frames without it still load.  ``stats``, where given, is updated
    with the published write's ``deflate_threads``, ``deflate_blocks``
    and ``deflate_cpu_s`` (:func:`_write_npz`).

    Transient ``OSError`` (disk full, EIO) retries with bounded
    exponential backoff; only a persistent failure propagates.  The
    ``PTT_FAULT=ckpt_fail@frame:N`` injection raises a synthetic
    ENOSPC on frame N's first attempt, exercising exactly this path.

    The tmp name is unique per writer (pid + thread id): two writers
    racing on one path — a job handed between daemon slices, a
    split-brain daemon pair — each publish a COMPLETE frame through
    their own tmp, so ``os.replace`` can never install a half-written
    file another writer was still filling (last complete write wins)."""
    t0 = time.perf_counter()
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}.npz"
    extra = {}
    if meta:
        extra["__meta__"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8
        )
    inject = meta is not None and meta.get(
        "frame_seq"
    ) is not None and "ckpt_fail" in faults.poll(
        "frame", int(meta["frame_seq"])
    )
    retries = 0
    while True:
        try:
            if inject:
                inject = False  # transient: only the first attempt
                raise OSError(
                    28,
                    "No space left on device "
                    "(injected fault ckpt_fail, PTT_FAULT)",
                )
            deflate = _write_npz(tmp, dict(
                __format__=np.int64(FORMAT_VERSION),
                sig=np.frombuffer(sig.encode(), dtype=np.uint8),
                wall_s=np.float64(wall_s),
                **extra,
                **arrays,
            ))
            nbytes = os.path.getsize(tmp)
            os.replace(tmp, path)  # atomic vs crashes + readers
            if stats is not None:
                stats.update(deflate)
            return nbytes, time.perf_counter() - t0, retries
        except OSError:
            # a half-written tmp from the failed attempt must not
            # linger (and on ENOSPC, freeing it is what lets the
            # retry succeed)
            try:
                os.remove(tmp)
            except OSError:
                pass
            if retries >= MAX_WRITE_RETRIES:
                raise
            time.sleep(WRITE_BACKOFF_S * (1 << retries))
            retries += 1


def cleanup_stale_tmp(path: Optional[str]) -> bool:
    """Remove stale ``<path>.tmp.*.npz`` temps (and the pre-r11 fixed
    ``<path>.tmp.npz`` name) left by a crash mid-write — engines call
    this at run start.  The atomic ``os.replace`` already guarantees a
    tmp never shadows a valid frame; this is disk hygiene — a dead
    multi-GB temp must not squat the checkpoint volume.  Scoped to
    THIS frame path only: sibling frames sharing the directory (other
    jobs' run_ids in a service checkpoint dir) are never touched.
    Returns True when something was removed."""
    if not path:
        return False
    d, base = os.path.split(path)
    prefix = base + ".tmp."
    removed = False
    try:
        names = os.listdir(d or ".")
    except OSError:
        return False
    for name in names:
        if not (name.startswith(prefix) and name.endswith(".npz")):
            continue
        try:
            os.remove(os.path.join(d, name))
            removed = True
        except OSError:
            pass
    return removed


def frame_meta(d) -> Dict[str, object]:
    """Writer metadata of a loaded frame (``{}`` for frames that
    predate the field or carry none)."""
    if "__meta__" not in d:
        return {}
    try:
        return json.loads(d["__meta__"].tobytes().decode())
    except (ValueError, AttributeError):
        return {}


def load_frame(path: str, sig: str, what: str = "configuration"):
    """Open a frame, verify format + signature, return the npz dict.

    A file that isn't a frame (arbitrary npz, truncated write,
    pre-frame formats) fails with one clean "unrecognized checkpoint
    format" error rather than a raw KeyError/zipfile error; a missing
    file raises FileNotFoundError untouched (callers distinguish
    "nothing to resume" from "corrupt").
    """
    try:
        d = np.load(path)
        frame_sig = d["sig"].tobytes().decode()
        version = int(d["__format__"]) if "__format__" in d else 1
    except FileNotFoundError:
        raise  # a missing file is not a format problem
    except Exception as e:  # noqa: BLE001
        raise ValueError(
            f"unrecognized checkpoint format at {path!r} — not written "
            f"by this engine ({type(e).__name__}: {e})"
        ) from e
    if version > FORMAT_VERSION:
        raise ValueError(
            f"checkpoint frame format v{version} is newer than this "
            f"build supports (v{FORMAT_VERSION}); upgrade to resume it"
        )
    if frame_sig != sig:
        raise ValueError(f"checkpoint was written by a different {what}")
    return d


# ------------------------------------------------- fpset frame codec


def pack_fpset(
    cols: Sequence[np.ndarray], prefix: str = "fp"
) -> Dict[str, np.ndarray]:
    """Compacted-occupancy snapshot of fpset key columns.

    ``cols`` are K uint32 columns of ``cap + 1`` slots (the trailing
    trash row is dropped), either 1-D (single device) or 2-D
    ``[N, cap + 1]`` (one row per shard).  Only occupied (non-all-
    SENTINEL) slots are stored: their keys per column plus the slot
    index, with per-shard counts so ragged occupancy round-trips.
    """
    cs = [np.asarray(c, np.uint32) for c in cols]
    ndim = cs[0].ndim
    if ndim == 1:
        cs = [c[None, :] for c in cs]
    cap = cs[0].shape[1] - 1
    body = [c[:, :cap] for c in cs]
    empty = body[0] == _SENTINEL
    for b in body[1:]:
        empty &= b == _SENTINEL
    occ = ~empty
    out: Dict[str, np.ndarray] = {
        f"{prefix}_tcap": np.int64(cap),
        f"{prefix}_ndim": np.int64(ndim),
    }
    keys = [[] for _ in cs]
    slots = []
    cnts = []
    for s in range(cs[0].shape[0]):
        idx = np.flatnonzero(occ[s])
        cnts.append(len(idx))
        slots.append(idx.astype(np.int64))
        for i, b in enumerate(body):
            keys[i].append(b[s][idx])
    out[f"{prefix}_cnt"] = np.asarray(cnts, np.int64)
    out[f"{prefix}_slot"] = (
        np.concatenate(slots) if slots else np.zeros((0,), np.int64)
    )
    for i, k in enumerate(keys):
        out[f"{prefix}k{i}"] = (
            np.concatenate(k) if k else np.zeros((0,), np.uint32)
        )
    return out


def unpack_fpset(
    d, ncols: int, prefix: str = "fp"
) -> Tuple[np.ndarray, ...]:
    """Rebuild full fpset columns (SENTINEL-filled, occupied slots
    scattered back, trash row restored) from a :func:`pack_fpset`
    frame.  Returns numpy arrays shaped exactly as saved (1-D or
    ``[N, cap + 1]``); callers device_put them."""
    cap = int(d[f"{prefix}_tcap"])
    ndim = int(d[f"{prefix}_ndim"])
    cnts = np.asarray(d[f"{prefix}_cnt"], np.int64)
    slots = np.asarray(d[f"{prefix}_slot"], np.int64)
    n_shards = len(cnts)
    cols = tuple(
        np.full((n_shards, cap + 1), _SENTINEL, np.uint32)
        for _ in range(ncols)
    )
    off = 0
    for s in range(n_shards):
        n = int(cnts[s])
        sl = slots[off: off + n]
        for i in range(ncols):
            cols[i][s, sl] = np.asarray(
                d[f"{prefix}k{i}"][off: off + n], np.uint32
            )
        off += n
    if ndim == 1:
        cols = tuple(c[0] for c in cols)
    return cols


# --------------------------------------------- preemption-safe stops


class PreemptionWatcher:
    """SIGTERM/SIGINT -> "checkpoint at the next level boundary".

    The TPU-VM preemption contract delivers SIGTERM with a short grace
    window; an operator Ctrl-C deserves the same survivable exit.  The
    first signal only sets :attr:`requested` — the engine finishes the
    level it is on, writes a resumable frame, and returns a truncated
    result with ``stop_reason="preempted"``.  A second SIGINT raises
    KeyboardInterrupt immediately (the operator insists).

    Usable as a context manager; installs handlers only when
    ``enabled`` and on the main thread (signal handlers cannot be set
    elsewhere — a checker driven from a worker thread simply runs
    without preemption capture).
    """

    def __init__(self, enabled: bool = True, log=None):
        self.enabled = enabled
        self.requested = False
        self._log = log
        self._prev: Dict[int, object] = {}
        self._installed = False

    def _handle(self, signum, frame):
        if self.requested and signum == signal.SIGINT:
            raise KeyboardInterrupt
        self.requested = True
        name = signal.Signals(signum).name
        msg = (
            f"{name} received: checkpointing at the next level "
            "boundary, then exiting resumably"
        )
        if self._log is not None:
            self._log(msg)
        else:
            import sys

            print(f"  {msg}", file=sys.stderr, flush=True)

    def __enter__(self):
        if (
            self.enabled
            and threading.current_thread() is threading.main_thread()
        ):
            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    self._prev[sig] = signal.signal(sig, self._handle)
                except (ValueError, OSError):  # non-main thread/races
                    break
            else:
                self._installed = True
        return self

    def __exit__(self, *exc):
        if self._installed:
            for sig, prev in self._prev.items():
                try:
                    signal.signal(sig, prev)
                except (ValueError, OSError):
                    pass
            self._installed = False
        return False
