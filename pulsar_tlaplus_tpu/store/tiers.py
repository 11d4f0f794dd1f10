"""Host-side tiers: cold key runs, row/log segments, spill manifest.

The :class:`TieredStore` is the engine's "slower memory": evicted
fpset key runs and aged row/log ranges live here — in host RAM always,
and (for checkpointed runs) as compressed files under the run's spill
directory so crash/preempt/daemon-suspend resume restores the WHOLE
tiered store, not just the device-resident window.

Design rules:

- **Synchronous availability, asynchronous durability.**  An evicted
  run is queryable the moment :meth:`evict_keys` returns (the very
  next flush may probe a just-evicted key); the encode + disk write
  runs on a background worker, overlapped with the next level's
  compute, and :meth:`flush` joins it at the next boundary.  The
  overlap is measured: ``blocked_s`` (time boundaries actually waited)
  over ``transfer_s`` (total encode/write work) is the
  ``spill_overlap_ratio`` the bench artifact carries.
- **Batched miss resolution, one index.**  Every evicted key is also
  kept once in ONE sorted lookup index (:func:`_merge_sorted`, a merge
  an eviction, on the evicting thread); :meth:`lookup_keys` sorts a
  whole sieved batch and answers it with one binary search a key —
  O(batch * log(cold keys)) however many runs were evicted, no
  per-key host loops.  The RUNS stay the unit of everything durable:
  one record, one encode job, one file, one manifest entry an
  eviction.
- **Crash hygiene** (the round-16 bugfix satellite): spill files are
  written to a per-writer-unique ``<name>.tmp.<pid>.<tid>`` and
  ``os.replace``d into place (the utils/ckpt.py frame discipline), so
  a killed run can never publish a torn file; stale temps are swept at
  startup (:func:`cleanup_stale_spill`), and a FRESH (non-resume) run
  wipes its spill dir outright so dead runs cannot leak unbounded
  host/disk bytes across restarts.
- **Manifest-anchored resume.**  :meth:`manifest` describes every run
  and segment (counts, byte sizes, file names, content digests);
  checkpoint frames embed it, and :meth:`restore` refuses digest
  mismatches — a torn or swapped spill file can never feed a resumed
  run silently-wrong cold verdicts.
- **ENOSPC degrades, never crashes** (r17).  A disk-full on the
  background durable write — real, or the ``enospc@spill:N`` drill —
  latches :attr:`degraded`: the in-RAM tiers stay fully queryable (so
  everything already evicted keeps deduplicating exactly), further
  durable writes stop, and the ENGINE finishes or truncates honestly
  with ``stop_reason="spill_enospc"`` instead of surfacing a raw
  worker crash.  A degraded store refuses :meth:`manifest` — a frame
  must never anchor a resume on spill files that were not written.
"""

from __future__ import annotations

import errno
import hashlib
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from pulsar_tlaplus_tpu.store import compress as codec
from pulsar_tlaplus_tpu.utils import faults

_TMP_MARK = ".tmp."
# the lookup index of a store that has evicted nothing: (hi, lo) planes
_NO_KEYS = (np.zeros((0,), np.uint64), np.zeros((0,), np.uint32))


def _digest(blob: bytes) -> str:
    return hashlib.sha256(blob).hexdigest()[:16]


def _atomic_write(path: str, blob: bytes) -> None:
    tmp = f"{path}{_TMP_MARK}{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


def _merge_sorted(ahi, alo, bhi, blo):
    """The union of two ``(hi, lo)``-sorted key planes, sorted, each
    key once.  A stable merge on ``hi`` (``b``'s keys placed by one
    search each, in order); an equal-``hi`` block — a 3-column key's,
    or a key both sides hold — is then put in ``lo`` order and its
    repeats dropped."""
    at = np.searchsorted(ahi, bhi, "right") + np.arange(len(bhi))
    n = len(ahi) + len(bhi)
    old = np.ones(n, bool)
    old[at] = False
    hi, lo = np.empty(n, np.uint64), np.empty(n, np.uint32)
    hi[at], hi[old] = bhi, ahi
    lo[at], lo[old] = blo, alo
    tie = hi[1:] == hi[:-1]
    if tie.any():
        blk = np.zeros(n, bool)
        blk[1:] = tie
        blk[:-1] |= tie
        idx = np.nonzero(blk)[0]
        lo[idx] = lo[idx][np.lexsort((lo[idx], hi[idx]))]
        keep = np.ones(n, bool)
        keep[1:] = ~tie | (lo[1:] != lo[:-1])
        hi, lo = hi[keep], lo[keep]
    return hi, lo


def _find_sorted(hi, lo, qh, ql):
    """Which of the ``hi``-sorted queries ``(qh, ql)`` are keys of the
    ``(hi, lo)``-sorted planes: one search a query."""
    left = np.searchsorted(hi, qh, "left")
    same = hi.take(left, mode="clip") == qh
    hit = same & (lo.take(left, mode="clip") == ql)
    # a 3-column key whose hi is there under another lo (~never): its
    # equal-hi block may go on, and hold the key further in
    for t in np.nonzero(same & ~hit)[0]:
        seg = lo[left[t]: np.searchsorted(hi, qh[t], "right")]
        p = np.searchsorted(seg, ql[t])
        hit[t] = p < len(seg) and seg[p] == ql[t]
    return hit


def cleanup_stale_spill(spill_dir: Optional[str]) -> int:
    """Remove stale ``*.tmp.<pid>.<tid>`` spill temps left by a crash
    mid-write (same contract as ``ckpt.cleanup_stale_tmp``).  Returns
    the number of files removed; a missing dir is a no-op."""
    if not spill_dir:
        return 0
    try:
        names = os.listdir(spill_dir)
    except OSError:
        return 0
    removed = 0
    for name in names:
        if _TMP_MARK not in name:
            continue
        try:
            os.remove(os.path.join(spill_dir, name))
            removed += 1
        except OSError:
            pass
    return removed


class SpillStats:
    """Cumulative spill counters (the ``spill`` telemetry payload)."""

    FIELDS = (
        "evictions", "keys_evicted", "rows_evicted", "logs_evicted",
        "bytes_raw", "bytes_comp", "transfer_s", "blocked_s",
        "misses_resolved", "miss_hits", "miss_batches", "lookup_s",
        "joins",
        # the lookup index (PR 51): seconds merging evicted runs into
        # it, merges done, keys it holds (keys_evicted less the keys
        # evicted twice)
        "merge_s", "merges", "index_keys",
    )

    def __init__(self):
        for f in self.FIELDS:
            setattr(self, f, 0.0 if f.endswith("_s") else 0)

    def as_dict(self) -> Dict[str, object]:
        return {
            f: (
                round(getattr(self, f), 4)
                if f.endswith("_s")
                else int(getattr(self, f))
            )
            for f in self.FIELDS
        }

    @property
    def overlap_ratio(self) -> Optional[float]:
        """Fraction of spill transfer work that overlapped compute
        (1.0 = boundaries never waited on a transfer)."""
        if self.transfer_s <= 0:
            return None
        return round(
            max(0.0, 1.0 - self.blocked_s / self.transfer_s), 4
        )


class TieredStore:
    """Cold tiers for one run: key runs + row/log segments.

    ``durable`` runs (anything with a checkpoint path) persist every
    run/segment to ``spill_dir`` as it is created, so a checkpoint
    frame only needs to embed the manifest.  Non-durable runs keep
    the cold tiers in host RAM only.
    """

    def __init__(
        self,
        ncols: int,
        spill_dir: Optional[str] = None,
        compress: bool = True,
        durable: bool = False,
    ):
        if durable and not spill_dir:
            raise ValueError("durable spill needs a spill_dir")
        self.ncols = int(ncols)
        self.spill_dir = spill_dir
        self.compress = bool(compress)
        self.durable = bool(durable)
        self.stats = SpillStats()
        # cold key runs: [{n, file, digest, raw, comp}]; their keys
        # live in the lookup index alone once the encode job has run
        self._runs: List[Dict] = []
        self._set_index(*_NO_KEYS)
        # row/log segments: [{lo, hi, arr(s), file(s), digest(s)}]
        self._rows: List[Dict] = []
        self._logs: List[Dict] = []
        self._seq = 0
        self._spill_write_n = 0  # enospc@spill fault-site counter
        # ENOSPC degradation latch (r17): once set, durable writes
        # stop (the in-RAM tiers stay queryable) and manifest() — the
        # resume anchor — refuses to describe the incomplete dir
        self.degraded = False
        self.degraded_error: Optional[str] = None
        self._pending: List[Future] = []
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ptt-spill"
        )
        self._lock = threading.Lock()
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
            cleanup_stale_spill(spill_dir)

    # ------------------------------------------------------------ keys

    @property
    def has_cold_keys(self) -> bool:
        return bool(self._runs)

    @property
    def cold_keys(self) -> int:
        return sum(r["n"] for r in self._runs)

    @property
    def cold_runs(self) -> int:
        """Sorted runs evicted: one record, one durable file and one
        manifest entry each.  A lookup walks none of them: it reads
        the one index they were merged into."""
        return len(self._runs)

    def _set_index(self, hi, lo) -> None:
        self._index = (hi, lo)
        self.stats.index_keys = len(hi)

    def evict_keys(self, kcols_np) -> int:
        """Ingest one SORTED evicted key run (dense numpy columns from
        the device's ``extract_cold``).  Queryable immediately: the
        run is merged into the lookup index before this returns;
        encode + durable write happen on the background worker, which
        is the last holder of the run's own planes."""
        hi, lo = codec.pack_keys(kcols_np)
        n = len(hi)
        if n == 0:
            return 0
        rec: Dict = {
            "kind": "keys", "n": n, "file": None, "digest": None,
            "raw": hi.nbytes + lo.nbytes, "comp": None,
        }
        self._runs.append(rec)
        self.stats.evictions += 1
        self.stats.keys_evicted += n
        t0 = time.perf_counter()
        self._set_index(*_merge_sorted(*self._index, hi, lo))
        self.stats.merges += 1
        self.stats.merge_s += time.perf_counter() - t0
        self._submit_encode(
            rec, lambda: codec.encode_key_run(hi, lo, self.compress),
            f"keys_{self._next_seq()}.ptsk",
        )
        return n

    def lookup_keys(self, kcols_np) -> np.ndarray:
        """bool mask over the query batch, in its order: True = the
        key was evicted (a false-new verdict the engine must merge
        back).  The batch is sorted, searched ONCE in the index, and
        the hits scattered back."""
        t0 = time.perf_counter()
        qhi, qlo = codec.pack_keys(kcols_np)
        member = np.zeros(qhi.shape, bool)
        if len(qhi) and len(self._index[0]):
            order = np.argsort(qhi)
            member[order] = _find_sorted(
                *self._index, qhi[order], qlo[order]
            )
        self.stats.misses_resolved += int(len(qhi))
        self.stats.miss_hits += int(member.sum())
        self.stats.miss_batches += 1
        self.stats.lookup_s += time.perf_counter() - t0
        return member

    # ------------------------------------------------- rows / logs

    def spill_rows(self, gid_lo: int, gid_hi: int, flat_u32) -> None:
        """Store the packed rows of gid range [gid_lo, gid_hi) (flat
        uint32, ``(gid_hi - gid_lo) * W`` words)."""
        if gid_hi <= gid_lo:
            return
        arr = np.ascontiguousarray(flat_u32, np.uint32)
        rec: Dict = {
            "kind": "rows", "lo": int(gid_lo), "hi": int(gid_hi),
            "arr": arr, "file": None, "digest": None,
            "raw": arr.nbytes, "comp": None,
        }
        self._rows.append(rec)
        self.stats.rows_evicted += int(gid_hi - gid_lo)
        self._submit_encode(
            rec, lambda: codec.encode_plane(arr, self.compress),
            f"rows_{gid_lo}_{gid_hi}.ptsr",
        )

    def spill_logs(
        self, gid_lo: int, gid_hi: int, parent, lane
    ) -> None:
        """Store the parent/lane trace-log range [gid_lo, gid_hi)."""
        if gid_hi <= gid_lo:
            return
        par = np.ascontiguousarray(parent, np.int32)
        lan = np.ascontiguousarray(lane, np.int32)
        rec: Dict = {
            "kind": "logs", "lo": int(gid_lo), "hi": int(gid_hi),
            "arrs": (par, lan), "files": None, "digests": None,
            "raw": par.nbytes + lan.nbytes, "comp": None,
        }
        self._logs.append(rec)
        self.stats.logs_evicted += int(gid_hi - gid_lo)
        seq = self._next_seq()

        def encode():
            bp, rp, cp = codec.encode_plane(par, self.compress)
            bl, rl, cl = codec.encode_plane(lan, self.compress)
            return (bp, bl), rp + rl, cp + cl

        self._submit_encode(
            rec, encode,
            (f"parent_{gid_lo}_{gid_hi}.{seq}.ptsr",
             f"lane_{gid_lo}_{gid_hi}.{seq}.ptsr"),
        )

    def _gather(self, segs: List[Dict], lo: int, hi: int, width: int,
                pick) -> np.ndarray:
        """Concatenate segment slices covering [lo, hi) — tier by
        tier, in gid order; raises on gaps (a spilled range the store
        never saw would silently corrupt a sweep/trace)."""
        out = []
        cur = lo
        for rec in sorted(segs, key=lambda r: r["lo"]):
            if rec["hi"] <= cur or rec["lo"] >= hi:
                continue
            if rec["lo"] > cur:
                raise ValueError(
                    f"cold tier gap: [{cur}, {rec['lo']}) missing"
                )
            a, b = cur, min(rec["hi"], hi)
            arr = pick(rec)
            out.append(
                arr[(a - rec["lo"]) * width: (b - rec["lo"]) * width]
            )
            cur = b
            if cur >= hi:
                break
        if cur < hi:
            raise ValueError(f"cold tier gap: [{cur}, {hi}) missing")
        if not out:
            return np.zeros((0,), np.int32)
        return np.concatenate(out)

    def fetch_rows(self, gid_lo: int, gid_hi: int, W: int) -> np.ndarray:
        """Flat uint32 rows for gid range [gid_lo, gid_hi) streamed
        back from the cold segments."""
        if gid_hi <= gid_lo:
            return np.zeros((0,), np.uint32)
        return self._gather(
            self._rows, gid_lo, gid_hi, W, lambda r: r["arr"]
        )

    def fetch_logs(
        self, gid_lo: int, gid_hi: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        if gid_hi <= gid_lo:
            z = np.zeros((0,), np.int32)
            return z, z
        par = self._gather(
            self._logs, gid_lo, gid_hi, 1, lambda r: r["arrs"][0]
        )
        lan = self._gather(
            self._logs, gid_lo, gid_hi, 1, lambda r: r["arrs"][1]
        )
        return par, lan

    @property
    def rows_spilled_hi(self) -> int:
        """One past the highest spilled row gid (0 = nothing spilled);
        spilled row ranges are contiguous from 0 by construction."""
        return max((r["hi"] for r in self._rows), default=0)

    # ------------------------------------------------------ async tier

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def note_transfer(self, seconds: float) -> None:
        """Account engine-side D2H gather time for the spilled data
        (the other half of the transfer beside encode/write).  Under
        the lock: the background encode worker increments the same
        counter, and an unlocked read-modify-write would lose one of
        the two updates."""
        with self._lock:
            self.stats.transfer_s += float(seconds)

    def _submit_encode(self, rec: Dict, encode, names) -> None:
        # the enospc@spill:N drill arms on the SUBMITTING (engine)
        # thread so the firing write is deterministic; the synthetic
        # OSError is raised at the worker's write, where a real
        # disk-full lands
        self._spill_write_n += 1
        inject = "enospc" in faults.poll("spill", self._spill_write_n)
        inject_n = self._spill_write_n

        def job():
            t0 = time.perf_counter()
            blob, raw, comp = encode()
            files = digests = None
            try:
                if self.durable and not self.degraded:
                    if inject:
                        raise faults.enospc_error("spill", inject_n)
                    blobs = (
                        blob if isinstance(blob, tuple) else (blob,)
                    )
                    fnames = (
                        names if isinstance(names, tuple) else (names,)
                    )
                    files, digests = [], []
                    for b, nm in zip(blobs, fnames):
                        _atomic_write(
                            os.path.join(self.spill_dir, nm), b
                        )
                        files.append(nm)
                        digests.append(_digest(b))
            except OSError as e:
                if e.errno != errno.ENOSPC:
                    raise  # only disk-full degrades; the rest is real
                # ENOSPC: keep the run alive — the in-RAM copy stays
                # queryable, durability is gone, the engine finishes
                # honestly (stop_reason="spill_enospc")
                files = digests = None
                with self._lock:
                    self.degraded = True
                    self.degraded_error = f"{e}"
            with self._lock:
                rec["comp"] = comp
                if rec["kind"] == "logs":
                    rec["files"] = files
                    rec["digests"] = digests
                else:
                    rec["file"] = files[0] if files else None
                    rec["digest"] = digests[0] if digests else None
                self.stats.bytes_raw += raw
                self.stats.bytes_comp += comp
                self.stats.transfer_s += time.perf_counter() - t0

        self._pending.append(self._pool.submit(job))

    def flush(self) -> None:
        """Join pending encode/write work (boundary barrier).  Time
        actually spent waiting here is the NON-overlapped share of the
        transfer work — the ``spill_overlap_ratio`` denominator's
        counterpart."""
        if not self._pending:
            return
        t0 = time.perf_counter()
        pending, self._pending = self._pending, []
        for f in pending:
            f.result()  # re-raises a worker failure loudly
        self.stats.blocked_s += time.perf_counter() - t0
        self.stats.joins += 1

    def quiesce(self) -> None:
        """Join + shut down the spill worker while keeping the in-RAM
        tiers fully readable (trace walks and the liveness sweep read
        cold data after the run ends).  Engines call this at run end
        so finished checkers never hold an idle worker thread; a later
        run rebuilds the store."""
        self.flush()
        self._pool.shutdown(wait=True)

    def close(self) -> None:
        try:
            self.flush()
        finally:
            self._pool.shutdown(wait=True)

    # ------------------------------------------------ manifest / resume

    def manifest(self) -> Dict[str, object]:
        """JSON-able description of every cold run/segment — embedded
        in checkpoint frames (requires :meth:`flush` first so every
        durable file + digest is final)."""
        self.flush()
        if self.degraded:
            raise ValueError(
                "spill tier degraded (ENOSPC): the spill dir is "
                "incomplete, so no frame may anchor a resume on it "
                f"({self.degraded_error})"
            )
        with self._lock:
            return {
                "spill_v": 1,
                "ncols": self.ncols,
                "compress": self.compress,
                "durable": self.durable,
                "stats": self.stats.as_dict(),
                "key_runs": [
                    {
                        "n": r["n"], "file": r["file"],
                        "digest": r["digest"], "raw": r["raw"],
                        "comp": r["comp"],
                    }
                    for r in self._runs
                ],
                "rows": [
                    {
                        "lo": r["lo"], "hi": r["hi"], "file": r["file"],
                        "digest": r["digest"], "raw": r["raw"],
                        "comp": r["comp"],
                    }
                    for r in self._rows
                ],
                "logs": [
                    {
                        "lo": r["lo"], "hi": r["hi"],
                        "files": r["files"], "digests": r["digests"],
                        "raw": r["raw"], "comp": r["comp"],
                    }
                    for r in self._logs
                ],
            }

    def _read_verified(self, name: str, want_digest: str) -> bytes:
        path = os.path.join(self.spill_dir, name)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise ValueError(
                f"spill file missing/unreadable on resume: {path} ({e})"
            ) from e
        if _digest(blob) != want_digest:
            raise ValueError(
                f"spill file digest mismatch on resume: {path} — "
                "torn or foreign file; the run cannot resume from it"
            )
        return blob

    def restore(self, manifest: Dict) -> None:
        """Rebuild the cold tiers from a frame-embedded manifest (the
        durable files must be under ``spill_dir``).  Digest mismatches
        and gaps raise — never a silently partial cold tier."""
        if not self.spill_dir:
            raise ValueError("restore needs a spill_dir")
        if int(manifest.get("spill_v", 0)) > 1:
            raise ValueError("spill manifest newer than supported")
        self._runs, self._rows, self._logs = [], [], []
        self._set_index(*_NO_KEYS)
        for e in manifest.get("key_runs", []):
            blob = self._read_verified(e["file"], e["digest"])
            hi, lo = codec.decode_key_run(blob)
            self._runs.append(
                {
                    "kind": "keys", "n": int(e["n"]),
                    "file": e["file"], "digest": e["digest"],
                    "raw": int(e["raw"]), "comp": int(e["comp"]),
                }
            )
            if len(hi) != int(e["n"]):
                raise ValueError(
                    f"spill run {e['file']}: decoded {len(hi)} keys, "
                    f"manifest says {e['n']}"
                )
            self._set_index(*_merge_sorted(*self._index, hi, lo))
        for e in manifest.get("rows", []):
            blob = self._read_verified(e["file"], e["digest"])
            self._rows.append(
                {
                    "kind": "rows", "lo": int(e["lo"]),
                    "hi": int(e["hi"]), "arr": codec.decode_plane(blob),
                    "file": e["file"], "digest": e["digest"],
                    "raw": int(e["raw"]), "comp": int(e["comp"]),
                }
            )
        for e in manifest.get("logs", []):
            bp = self._read_verified(e["files"][0], e["digests"][0])
            bl = self._read_verified(e["files"][1], e["digests"][1])
            self._logs.append(
                {
                    "kind": "logs", "lo": int(e["lo"]),
                    "hi": int(e["hi"]),
                    "arrs": (codec.decode_plane(bp), codec.decode_plane(bl)),
                    "files": e["files"], "digests": e["digests"],
                    "raw": int(e["raw"]), "comp": int(e["comp"]),
                }
            )
        # cumulative stats continue from the frame (the monotone-
        # cumulative telemetry contract survives resume); rebuilding
        # the index above is no eviction's merge and counts as none,
        # and a frame from before the index says nothing of its keys
        st = manifest.get("stats") or {}
        for f in SpillStats.FIELDS:
            if f in st:
                setattr(
                    self.stats, f,
                    float(st[f]) if f.endswith("_s") else int(st[f]),
                )
        self._seq = len(self._runs) + len(self._rows) + len(self._logs)

    def wipe(self) -> None:
        """Fresh-run hygiene: drop every spill file in the dir (this
        run owns it — a dead prior run must not leak disk bytes) and
        reset the in-memory tiers."""
        self._runs, self._rows, self._logs = [], [], []
        self._set_index(*_NO_KEYS)
        self.stats = SpillStats()
        if not self.spill_dir:
            return
        try:
            names = os.listdir(self.spill_dir)
        except OSError:
            return
        for name in names:
            if name.endswith((".ptsk", ".ptsr")) or _TMP_MARK in name:
                try:
                    os.remove(os.path.join(self.spill_dir, name))
                except OSError:
                    pass
