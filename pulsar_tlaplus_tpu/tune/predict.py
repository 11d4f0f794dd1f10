"""Candidate cost prediction — the prune stage of the tuner.

The r14 cost model (``obs/attribution.py``) prices a run's measured
work units with calibrated per-backend unit costs.  Prediction runs
the same pricing over *predicted* work counts: one reference run at
default knobs measures the workload's per-stage work units once, and
each candidate's counts are derived from how its knobs reshape the
schedule — never the state space (tuning changes batching, not
semantics, so state-determined work is invariant):

- ``expand_rows`` / ``append_rows`` / ``compact_elems``: invariant
  across candidates (one row per live frontier state / appended state
  / compacted element, fixed by the spec + constants).
- ``probe_lanes``: presented lanes per candidate lane scale with the
  fpset probe schedule — ``dense`` full-width rounds, then staged
  1/div widths up to each stage limit (:func:`schedule_lane_factor`;
  the same stated approximation as the sweep's shared unit cost).
- dispatch/fetch overhead: the fused engine pays ~1 dispatch + 1
  stats fetch per steady-state level and 1 per ramp *batch*, so the
  level structure of the reference run + the candidate's
  ``fuse_group``/``sub_batch`` predict the dispatch count; each
  dispatch is priced at the calibration's measured ``rtt_s`` (or the
  measured per-device default) — the term ``fuse_group`` amortizes
  across the ramp.
- **padded-capacity compute**: shapes are static, so an expand
  window processes its full ``sub_batch`` rows and a flush its full
  ``sub_batch * A * flush_factor`` lanes — padding included — and
  every level ends with at least one window and one flush.  Lanes
  and rows BEYOND the live work counters are priced at the same
  unit costs, which is what stops the model from blindly preferring
  the biggest batch: on a workload whose levels are smaller than
  the window, doubling ``sub_batch`` doubles real compute for zero
  extra states (the capacity-proportional term the mapper papers
  model).

Absolute seconds inherit the calibration's ~±25% cross-shape
tolerance; the tuner only needs the RANKING to prune, and the top-K
survivors are measured for real (docs/tuning.md).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from pulsar_tlaplus_tpu.obs import attribution

# Host<->device figures used when the calibration did not measure
# them, keyed by ``jax.devices()[0].device_kind`` and then by the
# ``calibration.json`` key they stand in for: ``rtt_s``, seconds per
# dispatch plus stats fetch, and ``link_bytes_per_s`` for the
# tiered-store spill term.  A
# device that is not in the table is an error, not a default — run
# ``scripts/profile.py calibrate`` there.
DEVICE_LINK = {
    # local dispatch on the CPU mesh; host RAM moves at memcpy speed
    "cpu": {"rtt_s": 2e-4, "link_bytes_per_s": 2e9},
    # one v5e chip (my chip run, PR 23): dispatch + scalar fetch,
    # median of 50 (``obs.telemetry.measure_rtt``, the minimum of 20,
    # read 3.7e-4 s); D2H of 256 MiB, median of 5 (H2D read 4.67e9)
    "TPU v5 lite": {"rtt_s": 9.3e-4, "link_bytes_per_s": 3.55e9},
}


def _device_link(ref: Dict, cal: dict, key: str) -> float:
    """The calibration's measured figure, else the table's for the
    reference run's device."""
    if cal.get(key):
        return float(cal[key])
    kind = ref.get("device_kind") or ref.get("backend", "cpu")
    if kind not in DEVICE_LINK:
        raise ValueError(
            f"no measured {key} for device {kind!r} and none in the "
            "calibration — run scripts/profile.py calibrate on it"
        )
    return DEVICE_LINK[kind][key]

# nominal delta+zlib ratio when the reference ran uncompressed (the
# measured producer_on ratio is ~0.35; used only to price a
# spill_compress=True candidate against an uncompressed reference)
_NOMINAL_SPILL_RATIO = 0.4

# default probe schedule constants mirrored from ops/fpset.py (not
# imported: predict must stay importable without jax)
_DENSE_DEFAULT = 4
_STAGES_DEFAULT = (
    (4, 16), (8, 24), (16, 32), (32, 40), (64, 48), (256, 64),
)

def schedule_lane_factor(
    dense: int, stages: Tuple[Tuple[int, int], ...], avg_rounds: float
) -> float:
    """Expected presented-lane rounds per candidate lane under a probe
    schedule: full width for ``dense`` rounds, then 1/div width per
    stage up to its round limit, truncated at the run's measured
    average probe depth (``fpset_avg_probe_rounds``) — lanes that
    settled stop presenting."""
    depth = max(float(avg_rounds), 1.0)
    f = min(depth, float(dense))
    prev = float(dense)
    for div, limit in stages:
        if depth <= prev:
            break
        f += (min(depth, float(limit)) - prev) / float(div)
        prev = float(limit)
    return max(f, 1.0)


def ramp_dispatches(
    level_sizes: List[int], sub_batch: int, fuse_group: int
) -> Tuple[int, int]:
    """(ramp_levels, dispatches) for the fused engine: consecutive
    levels whose frontier fits one expand window batch up to
    ``fuse_group`` per dispatch; every other level is one dispatch."""
    fg = max(int(fuse_group), 1)
    ramp = 0
    for sz in level_sizes:
        if sz > sub_batch:
            break
        ramp += 1
    steady = len(level_sizes) - ramp
    return ramp, -(-ramp // fg) + steady


def predict_candidate(
    cand: Dict,
    ref: Dict,
    cal: Optional[dict] = None,
) -> Dict[str, object]:
    """Predicted cost of one sparse candidate against a reference
    measurement (:func:`reference_of`).  Returns ``{est_s, est_work,
    dispatches, overhead_s}``."""
    backend = ref.get("backend", "cpu")
    if cal is None:
        cal = attribution.default_calibration(backend)
    units = cal.get("units", {})
    work = dict(ref.get("work", {}))
    # probe-schedule scaling (stated approximation — see module doc)
    d_ref = int(ref.get("dense_rounds") or _DENSE_DEFAULT)
    stages_ref = tuple(
        tuple(s) for s in (ref.get("stages") or _STAGES_DEFAULT)
    )
    d_new = int(cand.get("fpset_dense_rounds") or d_ref)
    avg = float(ref.get("avg_probe_rounds") or 1.0)
    if "probe_lanes" in work and d_new != d_ref:
        f_ref = schedule_lane_factor(d_ref, stages_ref, avg)
        f_new = schedule_lane_factor(d_new, stages_ref, avg)
        work["probe_lanes"] = int(work["probe_lanes"] * f_new / f_ref)
    est = 0.0
    for _stage, wkey, ukey, _lbl in attribution.STAGE_WORK:
        w = work.get(wkey[len("work_"):])
        u = units.get(ukey)
        if w and u is not None:
            est += w * u * 1e-9
    g = int(cand.get("sub_batch") or ref.get("sub_batch") or 8192)
    fg = int(cand.get("fuse_group") or ref.get("fuse_group") or 8)
    levels = list(ref.get("level_sizes", ()))
    _ramp, disp = ramp_dispatches(levels, g, fg)
    # bigger flush groups / group-ahead amortize mid-level syncs; model
    # them as extra fetches per level beyond the fused 1-per-dispatch
    ff = int(cand.get("flush_factor") or ref.get("flush_factor") or 1)
    grp = int(cand.get("group") or ref.get("group") or 4)
    lanes = float(work.get("probe_lanes") or 0)
    a = float(ref.get("A") or 1)
    acap = g * a * ff
    extra_syncs = 0.0
    if acap > 0:
        extra_syncs = lanes / acap / max(grp, 1)
    # padded-capacity compute (see module doc): every level pays at
    # least one full expand window (g rows) and one full flush (acap
    # lanes) regardless of how few states are live — the term that
    # penalizes oversizing the batch for the workload
    n_levels = max(len(levels), 1)
    rows_live = float(work.get("expand_rows") or 0)
    cand_lanes = rows_live * a
    windows = max(-(-rows_live // g) if g else 0, n_levels)
    flushes = max(-(-cand_lanes // acap) if acap else 0, n_levels)
    pad_rows = max(windows * g - rows_live, 0.0)
    pad_lanes = max(flushes * acap - cand_lanes, 0.0)
    u_row = units.get("expand_row_ns")
    u_lane = units.get("probe_lane_ns")
    if u_row is not None:
        est += pad_rows * u_row * 1e-9
    if u_lane is not None:
        est += pad_lanes * u_lane * 1e-9
    per_disp = _device_link(ref, cal, "rtt_s")
    # tiered-store link term (r16): a budgeted workload's spilled
    # bytes cross the slow link — price them at the measured byte
    # rate, and the batched miss resolutions at one sync each.  The
    # reference run's spill traffic is knob-invariant (evictions are
    # state-determined at a fixed budget); only the encoding and the
    # batch width move across candidates.
    spill_s = 0.0
    raw = float(ref.get("spill_bytes_raw") or 0)
    if raw > 0:
        rate = _device_link(ref, cal, "link_bytes_per_s")
        comp_ref = float(ref.get("spill_bytes_comp") or raw)
        ratio = comp_ref / raw if comp_ref < raw else _NOMINAL_SPILL_RATIO
        compress = cand.get("spill_compress")
        if compress is None:
            compress = bool(ref.get("spill_compress", True))
        bytes_cross = raw * ratio if compress else raw
        spill_s = bytes_cross / max(rate, 1.0)
        mb = int(
            cand.get("miss_batch") or ref.get("miss_batch") or (1 << 15)
        )
        misses = float(ref.get("spill_misses_resolved") or 0)
        spill_s += (misses / max(mb, 1)) * per_disp
    overhead = (disp + extra_syncs) * per_disp + spill_s
    return {
        "est_s": round(est + overhead, 6),
        "est_work": work,
        "dispatches": int(disp),
        "overhead_s": round(overhead, 6),
        "spill_s": round(spill_s, 6),
    }


def reference_of(ck, result) -> Dict[str, object]:
    """The reference measurement the predictor scales from: one
    default-knob run's engine state + result."""
    stats = getattr(ck, "last_stats", {}) or {}
    work = {
        k[len("work_"):]: int(v)
        for k, v in stats.items()
        if k.startswith("work_") and isinstance(v, (int, float))
    }
    import jax

    backend = jax.default_backend()
    return {
        "backend": "cpu" if backend == "cpu" else "tpu",
        "device_kind": jax.devices()[0].device_kind,
        "work": work,
        "level_sizes": [int(x) for x in result.level_sizes],
        "distinct_states": int(result.distinct_states),
        "wall_s": float(result.wall_s),
        "sub_batch": int(ck.G),
        "fuse_group": int(ck.RMAX),
        "flush_factor": int(ck.FLUSH),
        "group": int(ck.group),
        "A": int(ck.A),
        "dense_rounds": int(ck.fps_dense),
        "stages": tuple(tuple(s) for s in ck.fps_stages),
        "avg_probe_rounds": float(
            stats.get("fpset_avg_probe_rounds") or 1.0
        ),
        # tiered-store reference signals (r16): zero/absent untiered
        "spill_bytes_raw": int(stats.get("spill_bytes_raw") or 0),
        "spill_bytes_comp": int(stats.get("spill_bytes_comp") or 0),
        "spill_misses_resolved": int(
            stats.get("spill_misses_resolved") or 0
        ),
        "spill_compress": bool(getattr(ck, "spill_compress", True)),
        "miss_batch": int(getattr(ck, "miss_batch", 1 << 15)),
    }


def rank(
    cands: List[Dict], ref: Dict, cal: Optional[dict] = None
) -> List[Tuple[Dict, Dict]]:
    """Every candidate priced and sorted cheapest-first:
    ``[(candidate, prediction), ...]``."""
    priced = [(c, predict_candidate(c, ref, cal)) for c in cands]
    priced.sort(key=lambda cp: cp[1]["est_s"])
    return priced


# ------------------------------------------------------- simulation


def predict_sim_candidate(
    cand: Dict,
    ref: Dict,
    cal: Optional[dict] = None,
) -> Dict[str, object]:
    """Predicted wall of one simulation candidate for a FIXED step
    budget (``ref["total_steps"]``), priced with the r14 calibration:

    - per-step compute: every walker-step evaluates all ``A``
      successor lanes of one state through the same vmapped model
      kernels the expand stage runs, priced at ``expand_row_ns``
      per lane-row, plus ``n_inv`` invariant evaluations priced at
      ``probe_lane_ns`` (both per-unit approximations shared with
      the explorer's model — stated tolerance applies);
    - per-dispatch overhead: one dispatch + one stats fetch per
      segment, priced at the calibration's measured ``rtt_s`` (or
      the measured per-device default) — the term ``segment_len``
      amortizes;
    - swarm-width efficiency: widths below the reference's measured
      occupancy knee pay the same dispatch for fewer steps — modeled
      simply as the dispatch count scaling with ``total_steps /
      (n_walkers * segment_len)``.

    ``ref``: {"backend", "device_kind", "A", "n_inv", "depth",
    "total_steps", "n_walkers", "segment_len"} (defaults for unset
    knobs)."""
    backend = ref.get("backend", "cpu")
    if cal is None:
        cal = attribution.default_calibration(backend)
    units = cal.get("units", {})
    b = int(cand.get("n_walkers") or ref.get("n_walkers") or 1024)
    depth = int(ref.get("depth") or 64)
    seg = int(cand.get("segment_len") or ref.get("segment_len") or 32)
    seg = max(1, min(seg, depth))
    while depth % seg:  # the engine's divisor clamp
        seg -= 1
    total = int(ref.get("total_steps") or b * depth)
    a = float(ref.get("A") or 1)
    n_inv = float(ref.get("n_inv") or 0)
    u_row = float(units.get("expand_row_ns") or 0.0)
    u_lane = float(units.get("probe_lane_ns") or 0.0)
    # steps are swarm-total, so per-step compute is width-invariant;
    # what the width changes is the dispatch COUNT for the budget
    est = total * (a * u_row + n_inv * u_lane) * 1e-9
    per_disp = _device_link(ref, cal, "rtt_s")
    segments = max(-(-total // (b * seg)), 1)
    overhead = segments * per_disp
    return {
        "est_s": round(est + overhead, 6),
        "est_work": {"steps": total},
        "dispatches": int(segments),
        "overhead_s": round(overhead, 6),
    }


def rank_sim(
    cands: List[Dict], ref: Dict, cal: Optional[dict] = None
) -> List[Tuple[Dict, Dict]]:
    """Simulation candidates priced and sorted cheapest-first."""
    priced = [(c, predict_sim_candidate(c, ref, cal)) for c in cands]
    priced.sort(key=lambda cp: cp[1]["est_s"])
    return priced
