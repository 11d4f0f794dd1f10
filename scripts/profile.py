#!/usr/bin/env python
"""One front-end for the real-chip profiling probes (round 13).

The nine one-off ``scripts/profile_*.py`` probes accreted one per
design round; this consolidates them into subcommands so the bench
playbook has a single entry point and the probe idioms (chained
dispatch timing, completion barriers) live in one place:

    python scripts/profile.py expand  [--mode timed|chained]
    python scripts/profile.py prims   [--set v1|sorts|big|gather|all]
    python scripts/profile.py stages  [--sub-batch-log2 19] [--run S]
    python scripts/profile.py lsm     [--section sort|sort4|gather|scatter]
    python scripts/profile.py bucket
    python scripts/profile.py calibrate [--out calibration.json]  # r14:
        # unit costs for the work-unit cost-attribution model
    python scripts/profile.py ladder    [--schedules ...]  # PR 37
    python scripts/profile.py arbitrate [--widths ...] [--caps-log2 ...]
    python scripts/profile.py scatter   [--updates ...] [--caps-log2 ...]
        [--variants two_as_is,compact8,...] [--win 0,0.02,0.1,0.5,1]
    python scripts/profile.py deflate   [--states N] [--blocks-kb ...]
        [--threads ...]  # PR 45: the frame writer, no device touched

Mapping from the retired scripts:

- ``profile_expand.py``   -> ``expand --mode timed`` (per-stage expand
  breakdown, block_until_ready timing)
- ``profile_expand2.py``  -> ``expand --mode chained`` (chained
  dispatches cancel the per-call host round trip)
- ``profile_prims.py``    -> ``prims --set v1`` (dedup primitive
  candidates: sorts, gathers, scatter variants, searchsorted)
- ``profile_prims2.py``   -> ``prims --set sorts|big|gather`` (the
  round-4 sort/gather/scatter cost curves)
- ``profile_stages.py``   -> ``stages`` (per-dispatch stage costs on
  the CURRENT device engine — updated to the r10 compact split and the
  r13 fused level megakernel; the old script predated both and called
  retired jit signatures)
- ``profile_stages5.py``  -> ``stages --run BUDGET_S`` (a budgeted
  bench-shape run under PTT_STAGE_TIMING with the per-stage totals +
  RTT-corrected estimates printed)
- ``profile_lsm.py``      -> ``lsm`` (sort/gather/scatter/DUS at
  round-3 LSM shapes; one section per process — the buffer sets are
  mutually incompatible in HBM)
- ``profile_bucket.py``   -> ``bucket`` (bucketized-hash row gathers,
  unique scatter, segmented rank)
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from pulsar_tlaplus_tpu.utils.device import setup_compile_cache  # noqa: E402

setup_compile_cache()


# ------------------------------------------------------ timing idioms


barrier = jax.block_until_ready


def timed(name, fn, *args, reps=5):
    """Simple block_until_ready timing: first call = compile, then the
    median of ``reps`` runs.  Each rep includes one host round trip
    (use chain_time for per-call costs without it)."""
    t0 = time.time()
    out = fn(*args)
    barrier(out)
    compile_s = time.time() - t0
    times = []
    for _ in range(reps):
        t0 = time.time()
        out = fn(*args)
        barrier(out)
        times.append(time.time() - t0)
    med = sorted(times)[len(times) // 2]
    print(f"{name:44s} compile {compile_s:7.2f}s   run {med*1e3:9.2f} ms",
          flush=True)
    return out, med


def chain_time(name, f, args, thread, k=8, settle=2):
    """True per-call device cost by chaining: dispatch ``k`` calls with
    a data dependency (``thread(out, args) -> next args``) and fetch
    once; per-call ~= (t_k - t_1) / (k - 1) — the host round trip
    cancels."""
    out = f(*args)
    barrier(out)  # compile + settle

    def run(n):
        t0 = time.time()
        a = args
        o = f(*a)
        for _ in range(n - 1):
            a = thread(o, a)
            o = f(*a)
        barrier(o)
        return time.time() - t0

    t1 = min(run(1) for _ in range(settle))
    tk = min(run(k) for _ in range(settle))
    per = (tk - t1) / (k - 1)
    print(f"{name:44s} 1x {t1*1e3:8.1f} ms   per-call {per*1e3:8.2f} ms",
          flush=True)
    return per


def rng_cols(n, k, seed=0):
    key = jax.random.PRNGKey(seed)
    cols = []
    for _ in range(k):
        key, sub = jax.random.split(key)
        cols.append(jax.random.bits(sub, (n,), jnp.uint32))
    return cols


# ------------------------------------------------------------- expand


def cmd_expand(args):
    """Per-stage cost of the round-1 expand pipeline (unpack ->
    successors -> pack -> keys -> hashtable -> partition ->
    invariants), with a visited table at a realistic load factor."""
    from bench import scaled_config
    from pulsar_tlaplus_tpu.engine.bfs import Checker
    from pulsar_tlaplus_tpu.engine.core import partition_perm
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ops import dedup, hashtable

    c = scaled_config()
    model = CompactionModel(c)
    layout = model.layout
    F, A, W = args.chunk, model.A, layout.W
    FA = F * A
    cap = 1 << args.cap
    print(f"device: {jax.devices()[0]}")
    print(f"F={F} A={A} W={W} FA={FA} cap={cap} fill={args.fill}")

    # realistic frontier: run BFS a few levels, take logged states
    ck = Checker(model, frontier_chunk=4096, visited_cap=1 << 16,
                 max_states=30_000, keep_log=True)
    r = ck.run()
    log_mat = ck.last_run_state.log.packed_matrix()
    n_log = len(log_mat)
    print(f"BFS seed run: {r.distinct_states} states, {r.diameter} levels")
    frontier = jnp.asarray(log_mat[np.arange(FA) % n_log][:F])
    nc = jnp.int32(F)

    # visited table at a realistic load factor: random fill
    rng = np.random.default_rng(0)
    t1_, t2_, t3_, occ = hashtable.empty_table(cap)
    ins = jax.jit(hashtable.lookup_insert)
    fill_chunk = 1 << 19
    for _start in range(0, args.fill, fill_chunk):
        ks = [jnp.asarray(rng.integers(0, 2**32, fill_chunk, np.uint32))
              for _ in range(3)]
        _, t1_, t2_, t3_, occ, nf = ins(t1_, t2_, t3_, occ, *ks,
                                        jnp.ones((fill_chunk,), bool))
        assert int(nf) == 0
    barrier(occ)
    print(f"table load: {args.fill / cap:.2f}")

    def stage_a(frontier, n):
        f = frontier.shape[0]
        row_live = jnp.arange(f, dtype=jnp.int32) < n
        states = jax.vmap(layout.unpack)(frontier)
        succ, valid = jax.vmap(model.successors)(states)
        valid = valid & row_live[:, None]
        packed = jax.vmap(jax.vmap(layout.pack))(succ)
        return packed.reshape(f * A, W), valid.reshape(f * A)

    fa = jax.jit(stage_a)
    fb = jax.jit(lambda p: dedup.make_keys(p, layout.total_bits))

    def stage_d(is_new, packed):
        return packed[partition_perm(is_new)]

    def stage_e(out_packed):
        states = jax.vmap(layout.unpack)(out_packed)
        oks = [jax.vmap(model.invariants[n])(states)
               for n in model.default_invariants]
        return jnp.stack([jnp.min(jnp.where(~ok, jnp.arange(FA), FA))
                          for ok in oks]), out_packed

    if args.mode == "timed":
        (packed, valid), _ = timed("A unpack+successors+pack", fa,
                                   frontier, nc)
        (k1, k2, k3), _ = timed("B make_keys", fb, packed)
        (is_new, *_rest), _ = timed(
            "C hashtable lookup_insert", ins,
            t1_, t2_, t3_, occ, k1, k2, k3, valid,
        )
        out_packed, _ = timed("D partition+gather", jax.jit(stage_d),
                              is_new, packed)
        timed("E invariants(all lanes)", jax.jit(stage_e), out_packed)

        def stage_e2(frontier):
            states = jax.vmap(layout.unpack)(frontier)
            return jax.vmap(model.stutter_enabled)(states)

        timed("E2 stutter check", jax.jit(stage_e2), frontier)
        ck2 = Checker(model, frontier_chunk=F, visited_cap=cap)
        step = ck2._get_step("expand")
        out, med = timed("F full expand step", step, frontier, nc,
                         t1_, t2_, t3_, occ, jnp.int32(args.fill))
        n_new = int(out[3])
        print(f"full step: n_new={n_new}, {FA/med:,.0f} lanes/s, "
              f"{n_new/med:,.0f} new states/s")
        return

    # chained mode (RTT-free per-call costs)
    chain_time("A unpack+succ+pack", fa, (frontier, nc),
               lambda o, a: (o[0][:F] ^ jnp.uint32(0), a[1]))
    packed, valid = fa(frontier, nc)
    barrier(packed)
    chain_time("B make_keys", fb, (packed,),
               lambda o, a: (a[0] ^ (o[0][:, None] & jnp.uint32(0)),))
    k1, k2, k3 = fb(packed)
    barrier(k1)

    def ins_thread(o, a):
        return (o[1], o[2], o[3], o[4],
                a[4] ^ (o[0][0].astype(jnp.uint32) & 0), a[5], a[6], a[7])

    chain_time("C hashtable lookup_insert", ins,
               (t1_, t2_, t3_, occ, k1, k2, k3, valid), ins_thread)
    is_new = ins(t1_, t2_, t3_, occ, k1, k2, k3, valid)[0]
    barrier(is_new)
    chain_time("D partition+gather", jax.jit(stage_d), (is_new, packed),
               lambda o, a: (a[0], o))
    fe = jax.jit(stage_e)
    chain_time("E invariants(all lanes)", fe, (packed,),
               lambda o, a: (o[1] ^ (o[0][0].astype(jnp.uint32) & 0),))
    step = Checker(model, frontier_chunk=F,
                   visited_cap=cap)._get_step("expand")

    def step_thread(o, a):
        return (a[0] ^ (o[0][:F] & jnp.uint32(0)), a[1], o[4], o[5],
                o[6], o[7], a[6])

    chain_time("F full expand step", step,
               (frontier, nc, t1_, t2_, t3_, occ, jnp.int32(args.fill)),
               step_thread, k=6)


# -------------------------------------------------------------- prims


def _prims_v1():
    rng = np.random.default_rng(0)
    for n in (1 << 18, 1 << 21, 1 << 24):
        cols = tuple(jnp.asarray(rng.integers(0, 2**32, n, np.uint32))
                     for _ in range(4))
        f = jax.jit(lambda a, b, c, d: lax.sort((a, b, c, d), num_keys=3))
        chain_time(f"sort3+1payload n={n}", f, cols,
                   lambda o, a: (o[0], o[1], o[2], o[3]), k=4)
    for nq, cap in ((1 << 18, 1 << 23), (1 << 21, 1 << 23),
                    (1 << 24, 1 << 25)):
        tbl = jnp.asarray(rng.integers(0, 2**32, cap, np.uint32))
        idx = jnp.asarray(rng.integers(0, cap, nq, np.int32))
        f = jax.jit(lambda t, i: t[i])
        chain_time(f"gather nq={nq} cap={cap}", f, (tbl, idx),
                   lambda o, a: (a[0], (a[1] ^ (o & 0)).astype(jnp.int32)))
    nq, nb = 1 << 18, 1 << 20
    tbl = jnp.asarray(rng.integers(0, 2**32, (nb, 32), np.uint32))
    idx = jnp.asarray(rng.integers(0, nb, nq, np.int32))
    f = jax.jit(lambda t, i: t[i])
    chain_time(f"gather-rows nq={nq} [1M,32]", f, (tbl, idx),
               lambda o, a: (a[0],
                             (a[1] ^ (o[:, 0] & 0)).astype(jnp.int32)))
    nq, cap = 1 << 18, 1 << 23
    tbl = jnp.zeros((cap,), jnp.uint32)
    dup_idx = jnp.asarray(rng.integers(0, cap, nq, np.int32))
    uni_idx = jnp.asarray(
        rng.choice(cap, nq, replace=False).astype(np.int32))
    uni_sorted = jnp.sort(uni_idx)
    vals = jnp.asarray(rng.integers(0, 2**32, nq, np.uint32))
    f = jax.jit(lambda t, i, v: t.at[i].min(v))
    chain_time("scatter-min dup idx", f, (tbl, dup_idx, vals),
               lambda o, a: (o, a[1], a[2]))
    f = jax.jit(lambda t, i, v: t.at[i].set(v, unique_indices=True))
    chain_time("scatter-set unique", f, (tbl, uni_idx, vals),
               lambda o, a: (o, a[1], a[2]))
    f = jax.jit(lambda t, i, v: t.at[i].set(
        v, unique_indices=True, indices_are_sorted=True))
    chain_time("scatter-set unique+sorted", f, (tbl, uni_sorted, vals),
               lambda o, a: (o, a[1], a[2]))
    f = jax.jit(lambda t, i, v: t.at[i].set(v))
    chain_time("scatter-set dup-possible", f, (tbl, dup_idx, vals),
               lambda o, a: (o, a[1], a[2]))
    nq, cap = 1 << 21, 1 << 24
    vis = jnp.sort(jnp.asarray(rng.integers(0, 2**32, cap, np.uint32)))
    q = jnp.asarray(rng.integers(0, 2**32, nq, np.uint32))
    f = jax.jit(lambda v, q: jnp.searchsorted(v, q))
    chain_time(f"searchsorted nq={nq} cap={cap}", f, (vis, q),
               lambda o, a: (a[0], a[1] ^ (o.astype(jnp.uint32) & 0)))


def _prims_sorts():
    n = 1 << 23  # 8.4M ~ accumulator width
    for ops, stable in [(2, False), (3, False), (6, False), (11, False),
                        (21, False), (21, True), (22, True)]:
        cols = rng_cols(n, ops)
        jf = jax.jit(
            lambda *cs, _s=stable: lax.sort(cs, num_keys=1, is_stable=_s)
        )
        timed(f"sort n=2^23 ops={ops} stable={int(stable)}", jf, *cols)


def _prims_big():
    for logn in (25, 26):
        n = 1 << logn
        for ops, nk in [(3, 3), (3, 1), (4, 4)]:
            cols = rng_cols(n, ops)
            jf = jax.jit(
                lambda *cs, _k=nk: lax.sort(cs, num_keys=_k,
                                            is_stable=False)
            )
            timed(f"sort n=2^{logn} ops={ops} keys={nk}", jf, *cols)


def _prims_gather():
    t = 1 << 27
    n = 1 << 23
    tab = jax.random.bits(jax.random.PRNGKey(1), (t,), jnp.uint32)
    idx = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, t, jnp.int32)
    sidx = jnp.sort(idx)
    g = jax.jit(lambda tb, ix: tb[ix])
    timed("gather 2^23 random from 2^27", g, tab, idx)
    timed("gather 2^23 sorted-idx from 2^27", g, tab, sidx)
    sc = jax.jit(
        lambda tb, ix, v: tb.at[ix].set(v, mode="drop",
                                        unique_indices=True)
    )
    vals = jax.random.bits(jax.random.PRNGKey(3), (n,), jnp.uint32)
    timed("scatter 2^23 random into 2^27", sc, tab, idx, vals)
    timed("scatter 2^23 sorted into 2^27", sc, tab, sidx, vals)
    tab2 = jax.random.bits(jax.random.PRNGKey(4), (2, t), jnp.uint32)
    g2 = jax.jit(lambda tb, ix: (tb[0, ix], tb[1, ix]))
    timed("gather 2x 2^23 random from 2^27", g2, tab2, idx)


def cmd_prims(args):
    print(f"device: {jax.devices()[0]}", flush=True)
    cases = {"v1": _prims_v1, "sorts": _prims_sorts, "big": _prims_big,
             "gather": _prims_gather}
    for name, fn in cases.items():
        if args.set in ("all", name):
            fn()


# ------------------------------------------------------------- stages


def cmd_stages(args):
    """Per-dispatch stage costs of the CURRENT device engine at bench
    shapes: expand / flush (fpset probe) / compact / append as the
    stage chain dispatches them, plus ONE fused level megakernel
    dispatch over the same frontier — the r13 before/after in a single
    probe.  ``--run S`` instead runs a budgeted bench-shape check under
    PTT_STAGE_TIMING and prints the per-stage totals (the old
    profile_stages5 mode)."""
    from pulsar_tlaplus_tpu.engine.device_bfs import BIG, DeviceChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ops import fpset
    from pulsar_tlaplus_tpu.ops.dedup import SENTINEL
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    if args.run is not None:
        os.environ["PTT_STAGE_TIMING"] = "1"
        from bench import BENCH_CHECKER_KW, scaled_config

        c = scaled_config()
        model = CompactionModel(c)
        ck = DeviceChecker(model, time_budget_s=args.run, progress=True,
                           fuse=args.fuse, **BENCH_CHECKER_KW)
        t0 = time.time()
        w = ck.warmup(seed=True)
        print(f"warmup: {w:.1f}s  {ck.last_stats}", file=sys.stderr)
        seed = model.host_seed(max_level_states=800_000,
                               max_total=1_000_000)
        print(f"seed: {len(seed[0])} states", file=sys.stderr)
        r = ck.run(seed=seed)
        print(f"run: {r.distinct_states} states / {r.diameter} levels "
              f"in {r.wall_s:.1f}s ({r.states_per_sec:.0f} st/s) "
              f"truncated={r.truncated}")
        stages = {k: v for k, v in ck.last_stats.items()
                  if k.startswith("stage_")}
        print(f"stage totals: {stages}")
        rtt = ck.last_stats["rtt_s"]  # measured by warmup()
        for name in ("fused", "expand", "flush", "compact", "append"):
            s = stages.get(f"stage_{name}_s")
            n = stages.get(f"stage_{name}_n")
            if s is not None and n:
                print(f"  {name}: {s:.1f}s / {n} dispatches "
                      f"(~{s - rtt * n:.1f}s est device time)")
        print(f"dispatches/level: "
              f"{ck.last_stats.get('dispatches_per_level')}")
        print(f"total: {time.time() - t0:.1f}s")
        return

    c = Constants(
        message_sent_limit=64, compaction_times_limit=3, num_keys=8,
        num_values=2, retain_null_key=True, max_crash_times=3,
        model_producer=True, model_consumer=False,
    )
    model = CompactionModel(c)
    ck = DeviceChecker(
        model,
        sub_batch=1 << args.sub_batch_log2,
        expand_chunk=min(1 << 13, 1 << args.sub_batch_log2),
        visited_cap=1 << 25,
        frontier_cap=24_000_000
        + (1 << args.sub_batch_log2) * model.A * args.flush_factor,
        max_states=24_000_000,
        flush_factor=args.flush_factor,
        fuse="stage",  # the per-stage jits are what this probe times
    )
    print(f"device {jax.devices()[0]}; G={ck.G} A={ck.A} NCs={ck.NCs} "
          f"ACAP={ck.ACAP} APAD={ck.APAD} K={ck.K} TCAP={ck.TCAP} "
          f"LCAP={ck.LCAP} W={ck.W} SL={ck.SLc} C={ck.C}", flush=True)
    t0 = time.time()
    warm_s = ck.warmup(tiers=False)
    print(f"warmup compile: {warm_s:.1f}s (wall {time.time()-t0:.1f}s)",
          flush=True)

    K = ck.K
    z = jnp.zeros
    ak = tuple(jnp.full((ck.ACAP,), SENTINEL, jnp.uint32)
               for _ in range(K))
    arows = z((ck.W, ck.ACAP), jnp.uint32)
    rows_store = z((ck._rows_len(),), jnp.uint32)
    vk = fpset.empty_cols(ck.TCAP, K)
    fpm = z((fpset.FPM_WIDE_N,), jnp.int32)
    n_inv = len(ck.invariant_names)
    viol0 = jnp.full((n_inv,), int(BIG), jnp.int32)

    def bench(name, dispatch, iters=6):
        t0 = time.time()
        last = None
        for _ in range(iters):
            last = dispatch()
        barrier(last)
        dt = (time.time() - t0) / iters
        print(f"{name:44s} {dt*1e3:9.1f} ms", flush=True)
        return dt

    # real initial states at rows 0..G
    window = jax.jit(
        jax.vmap(lambda i: model.layout.pack(model.gen_initial(i)))
    )(jnp.arange(ck.G, dtype=jnp.int32) % model.n_initial).reshape(
        ck.G * ck.W
    )
    barrier(window)

    def do_expand():
        nonlocal ak, arows
        out = ck._expand_jit()(
            ak, arows, window, jnp.int32(0), jnp.int32(ck.G), BIG,
            jnp.int32(0), jnp.int32(0),
        )
        ak, arows = out[:K], out[K]
        return out[K + 1]

    t_expand = bench("expand window (G states)", do_expand)

    def do_flush():
        nonlocal vk, fpm
        out = ck._fpflush_jit()(vk, ak, jnp.int32(ck.ACAP), fpm)
        vk, fpm = out[:K], out[K + 2]
        return out[K]

    t_flush = bench("flush (fpset probe-or-insert)", do_flush)

    out = ck._fpflush_jit()(vk, ak, jnp.int32(ck.ACAP), fpm)
    vk, n_new, flag, fpm = out[:K], out[K], out[K + 1], out[K + 2]
    barrier(n_new)
    print(f"  (n_new in flush probe: {int(np.asarray(n_new))})",
          flush=True)

    def do_compact():
        nonlocal arows
        crows, idx = ck._compact_jit()(arows, flag)
        arows = crows
        return idx

    t_compact = bench("compact (log-shift stream)", do_compact)
    crows, idx = ck._compact_jit()(arows, flag)
    arows = crows
    barrier(idx)

    par_log = z((ck.PCAP,), jnp.int32)
    lane_log = z((ck.PCAP,), jnp.int32)

    def do_append():
        nonlocal rows_store, par_log, lane_log
        rows_store, par_log, lane_log, nv2, _v = ck._append_jit()(
            rows_store, par_log, lane_log, crows, idx, n_new,
            jnp.int32(0), viol0, jnp.int32(0), jnp.bool_(False),
            jnp.int32(0), jnp.bool_(True),
        )
        return nv2

    t_append = bench("append (invariants+DUS)", do_append)

    per_flush = (t_expand * args.flush_factor + t_flush + t_compact
                 + t_append)
    print(f"total per flush-group (stage chain): {per_flush*1e3:.1f} ms "
          f"for {ck.ACAP} candidate lanes", flush=True)
    print(f"  -> ceiling at 100%/30%/10% new-rate: "
          f"{ck.ACAP/per_flush/1e6:.2f} / "
          f"{0.3*ck.ACAP/per_flush/1e6:.2f} / "
          f"{0.1*ck.ACAP/per_flush/1e6:.2f} M st/s", flush=True)

    # r13 comparison point: the same work as ONE fused megakernel
    # dispatch (expand+flush+compact+append, zero intermediate
    # dispatch boundaries) over a G-state frontier at row 0
    ck2 = DeviceChecker(
        model,
        sub_batch=1 << args.sub_batch_log2,
        expand_chunk=min(1 << 13, 1 << args.sub_batch_log2),
        visited_cap=1 << 25,
        frontier_cap=24_000_000
        + (1 << args.sub_batch_log2) * model.A * args.flush_factor,
        max_states=24_000_000,
        flush_factor=args.flush_factor,
        fuse="level",
    )
    fstate = {
        "vk": fpset.empty_cols(ck2.TCAP, K),
        "ak": tuple(jnp.full((ck2.ACAP,), SENTINEL, jnp.uint32)
                    for _ in range(K)),
        "arows": z((ck2.W, ck2.ACAP), jnp.uint32),
        "rows": z((ck2._rows_len(),), jnp.uint32),
        "parent": z((ck2.PCAP,), jnp.int32),
        "lane": z((ck2.PCAP,), jnp.int32),
        "nv": jnp.int32(0),
        "fpm": z((fpset.FPM_WIDE_N,), jnp.int32),
    }

    def do_fused():
        out = ck2._fused_jit()(
            fstate["vk"], fstate["ak"], fstate["arows"],
            fstate["rows"], fstate["parent"], fstate["lane"],
            fstate["nv"], BIG, viol0, fstate["fpm"],
            jnp.int32(0), jnp.int32(ck2.G), jnp.int32(0),
            jnp.int32(1), jnp.int32(1),
            jnp.int32(0), jnp.bool_(True),
        )
        fstate["vk"] = out[:K]
        fstate["ak"] = out[K: 2 * K]
        (fstate["arows"], fstate["rows"], fstate["parent"],
         fstate["lane"]) = out[2 * K: 2 * K + 4]
        fstate["fpm"] = out[2 * K + 7]
        return out[2 * K + 8]

    barrier(do_fused())  # compile outside the timed iterations
    bench("FUSED level megakernel (1 group)", do_fused, iters=4)


# ---------------------------------------------------------------- lsm


def cmd_lsm(args):
    W = 20
    N_ACC = 1 << 25
    T = N_ACC + (1 << 25)
    LIVE_FRAC = 0.03
    print(f"device: {jax.devices()[0]}", flush=True)
    key = jax.random.PRNGKey(0)
    which = args.section

    def bench(name, fn, a, k=8):
        t0 = time.time()
        out = fn(*a)
        barrier(out)
        compile_s = time.time() - t0
        t0 = time.time()
        outs = [fn(*a) for _ in range(k)]
        barrier(outs[-1])
        dt = (time.time() - t0) / k
        print(f"{name:44s} {dt*1e3:9.1f} ms/iter   "
              f"(compile {compile_s:.1f}s)", flush=True)
        return dt

    rows = jax.random.randint(
        key, (N_ACC, W), 0, 1 << 30, dtype=jnp.int32
    ).astype(jnp.uint32)
    n_new = int(N_ACC * LIVE_FRAC)
    idx_host = np.zeros((N_ACC,), np.int32)
    idx_host[:n_new] = np.random.permutation(N_ACC)[:n_new]
    gidx = jnp.asarray(idx_host)
    sidx_host = np.full((N_ACC,), N_ACC + 5, np.int64)
    sidx_host[:n_new] = np.arange(n_new)
    sidx = jnp.asarray(sidx_host, jnp.int32)
    store = jnp.zeros((N_ACC + 8, W), jnp.uint32)

    if which == "sort":
        k1 = jax.random.bits(key, (T,), jnp.uint32)
        k2 = jax.random.bits(jax.random.PRNGKey(1), (T,), jnp.uint32)
        pay = jax.random.bits(jax.random.PRNGKey(3), (T,), jnp.uint32)
        del rows, store
        s3 = jax.jit(lambda a, b, c: lax.sort((a, b, c), num_keys=3,
                                              is_stable=False))
        bench(f"sort 3-operand T={T>>20}M", s3, (k1, k2, pay))
        s2 = jax.jit(lambda a, b: lax.sort((a, b), num_keys=1,
                                           is_stable=True))
        bench(f"sort 2-operand stable T={T>>20}M", s2, (k1, pay))
        nn = N_ACC
        s3n = jax.jit(lambda a, b, c: lax.sort(
            (a[:nn], b[:nn], c[:nn]), num_keys=3, is_stable=False))
        bench(f"sort 3-operand T={nn>>20}M", s3n, (k1, k2, pay))
    elif which == "sort4":
        t2 = (1 << 25) + (1 << 23)
        del rows, store
        ks = [jax.random.bits(jax.random.PRNGKey(i), (t2,), jnp.uint32)
              for i in range(4)]
        s4 = jax.jit(lambda a, b, c, d: lax.sort(
            (a, b, c, d), num_keys=4, is_stable=False))
        bench(f"sort 4-operand T={t2>>20}M (r2 shape)", s4, tuple(ks))
    elif which == "gather":
        g = jax.jit(lambda r, i: r[i])
        bench("gather 33.5M rows[20] (3% random live)", g, (rows, gidx))
        ridx = jnp.asarray(np.random.permutation(N_ACC).astype(np.int32))
        bench("gather 33.5M rows[20] (100% random)", g, (rows, ridx))
    elif which == "scatter":
        sc = jax.jit(
            lambda st, r, i: st.at[i].set(r, mode="drop",
                                          unique_indices=True,
                                          indices_are_sorted=True))
        bench("scatter 33.5M rows[20] contig (3% live)", sc,
              (store, rows, sidx))
        sidx_all = jnp.arange(N_ACC, dtype=jnp.int32)
        bench("scatter 33.5M rows[20] contig (all live)", sc,
              (store, rows, sidx_all))
        d = jax.jit(lambda st, r: lax.dynamic_update_slice(st, r, (5, 0)))
        bench("DUS 33.5M rows[20] window", d, (store, rows))
        st1 = jnp.zeros((N_ACC + 8,), jnp.uint32)
        sc1 = jax.jit(
            lambda st, v, i: st.at[i].set(v, mode="drop",
                                          unique_indices=True,
                                          indices_are_sorted=True))
        bench("scatter 33.5M u32 contig (3% live)", sc1,
              (st1, jax.random.bits(key, (N_ACC,), jnp.uint32), sidx))


# ------------------------------------------------------------- bucket


def cmd_bucket(_args):
    rng = np.random.default_rng(0)
    print(f"device: {jax.devices()[0]}")
    ROW = 32
    for nq, nb in ((1 << 20, 1 << 21), (1 << 23, 1 << 22)):
        flat = jnp.asarray(rng.integers(0, 2**32, nb * ROW, np.uint32))
        idx = jnp.asarray(rng.integers(0, nb, nq, np.int32))

        def rowgather(flat, idx):
            g = jax.vmap(
                lambda i: lax.dynamic_slice(flat, (i * ROW,), (ROW,)))
            return g(idx)

        chain_time(f"flat-row-gather nq={nq} nb={nb} row{ROW}",
                   jax.jit(rowgather), (flat, idx),
                   lambda o, a: (a[0],
                                 (a[1] ^ (o[:, 0] & 0)).astype(jnp.int32)))
        tbl2d = flat.reshape(nb, ROW)
        chain_time(f"2d-row-gather   nq={nq} nb={nb} row{ROW}",
                   jax.jit(lambda t, i: t[i]), (tbl2d, idx),
                   lambda o, a: (a[0],
                                 (a[1] ^ (o[:, 0] & 0)).astype(jnp.int32)))
    nq, cap = 1 << 22, 1 << 27
    tbl = jnp.zeros((cap,), jnp.uint32)
    uni = jnp.asarray(
        (rng.permutation(cap >> 5)[:nq].astype(np.int64) << 5)
        .astype(np.int32))
    vals = jnp.asarray(rng.integers(0, 2**32, nq, np.uint32))
    chain_time("scatter-set unique 4M into 128M",
               jax.jit(lambda t, i, v: t.at[i].set(
                   v, unique_indices=True)),
               (tbl, uni, vals), lambda o, a: (o, a[1], a[2]))
    n = 8_700_000
    cols = tuple(jnp.asarray(rng.integers(0, 2**32, n, np.uint32))
                 for _ in range(5))
    chain_time("sort4+1 n=8.7M",
               jax.jit(lambda *c: lax.sort(c, num_keys=4)), cols,
               lambda o, a: tuple(o), k=4)
    starts = jnp.asarray(rng.integers(0, 2, n, np.int32))

    def segrank(starts):
        i = jnp.arange(n, dtype=jnp.int32)
        run_start = jnp.where(starts == 1, i, 0)
        seg = lax.cummax(run_start)
        return i - seg

    chain_time("segmented-rank cummax 8.7M", jax.jit(segrank), (starts,),
               lambda o, a: ((a[0] ^ (o & 0)).astype(jnp.int32),), k=4)


# ---------------------------------------------------------- calibrate


def cmd_calibrate(args):
    """Write ``calibration.json`` for the fused-era cost-attribution
    model (obs/attribution.py, round 14): run the ``-fuse stage``
    dispatch chain under ``PTT_STAGE_TIMING=1`` on a reference config,
    divide each stage's RTT-corrected measured seconds by the run's
    own work-unit counts, and persist the per-backend ns/unit costs.
    ``telemetry_report.py --attribution --calibration FILE`` then
    prices any single fused run's work counters — no stage rerun.

        python scripts/profile.py calibrate                 # 45k oracle
        python scripts/profile.py calibrate --config small  # 1.7k smoke
        python scripts/profile.py calibrate --sweep         # + liveness

    The stage-timing barrier serializes the pipeline, so this is a
    measurement run, not a benchmark — expect it to be slower than a
    normal check of the same config.
    """
    import tempfile

    # the barrier flag is read at CHECKER CONSTRUCTION, so it must be
    # in the environment before the import-side ctor below
    os.environ["PTT_STAGE_TIMING"] = "1"

    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.obs import attribution, report
    from pulsar_tlaplus_tpu.ref import pyeval as pe

    if args.config == "small":
        c = pe.Constants(
            message_sent_limit=2, compaction_times_limit=2,
            num_keys=1, num_values=1, max_crash_times=1,
            model_producer=True,
        )
        kw = dict(sub_batch=256, visited_cap=1 << 12,
                  frontier_cap=1 << 12)
    else:  # the shipped 45,198-state reference binding
        c = pe.SHIPPED_CFG
        kw = dict(sub_batch=2048, visited_cap=1 << 16,
                  frontier_cap=1 << 15)
    stream = os.path.join(
        tempfile.gettempdir(), f"calibrate_{os.getpid()}.jsonl"
    )
    try:
        os.remove(stream)
    except OSError:
        pass
    print(f"calibration run: -fuse stage + PTT_STAGE_TIMING on "
          f"{'small' if args.config == 'small' else 'shipped'} config",
          file=sys.stderr)
    ck = DeviceChecker(
        CompactionModel(c), invariants=(), fuse="stage",
        telemetry=stream, **kw,
    )
    ck.warmup(tiers=False)
    r = ck.run()
    print(f"  {r.distinct_states} states in {r.wall_s:.1f}s "
          "(barrier-serialized)", file=sys.stderr)
    events, _errs = report.load_events(stream)
    cal = attribution.calibrate_from_events(
        events, label=f"profile.py calibrate ({args.config})"
    )
    if args.sweep:
        from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

        sweep_stream = stream + ".sweep"
        lck = LivenessChecker(
            CompactionModel(c), goal="Termination",
            fairness="wf_next", telemetry=sweep_stream,
            frontier_chunk=kw["sub_batch"],
            visited_cap=kw["visited_cap"],
        )
        lres = lck.run()
        print(f"  sweep calibration: {lres.distinct_states} states "
              f"({lres.reason[:60]})", file=sys.stderr)
        sweep_events, _e = report.load_events(sweep_stream)
        cal = attribution.sweep_calibrate_from_events(
            sweep_events, cal
        )
        try:
            os.remove(sweep_stream)
        except OSError:
            pass
    attribution.save_calibration(args.out, cal)
    try:
        os.remove(stream)
    except OSError:
        pass
    print(f"wrote {args.out}:")
    for k, v in sorted(cal["units"].items()):
        print(f"  {k:20s} {v:10.2f}")
    print(f"  (measured stages: {cal.get('measured_stages')}; "
          f"defaults kept for: {cal.get('defaulted_stages')})")
    return 0


# ------------------------------------------------------------- ladder


def _prefilled(cap, K, load):
    """A table of ``cap`` slots holding ``load * cap`` keys, and the
    key function (column 0 is a bijection of the index)."""
    from pulsar_tlaplus_tpu.ops import fpset
    from pulsar_tlaplus_tpu.ops.dedup import _fmix

    u = jnp.uint32
    n_pre = int(load * cap)
    chunk = min(1 << 18, cap // 2)

    def keys_of(idx):
        return tuple(
            _fmix(idx ^ u((0x9E3779B9 * (c + 1)) & 0xFFFFFFFF))
            for c in range(K)
        )

    @jax.jit
    def prefill(tcols):
        def body(i, tc):
            idx = i.astype(u) * u(chunk) + jnp.arange(chunk, dtype=u)
            _, tc, _, _, _, _, _ = fpset.lookup_or_insert(
                tc, keys_of(idx), idx < u(n_pre),
                stages=fpset.STAGES_TWO_STEP,
            )
            return tc
        return lax.fori_loop(0, -(-n_pre // chunk), body, tcols)

    return barrier(prefill(fpset.empty_cols(cap, K))), keys_of, n_pre


def cmd_ladder(args):
    """Seconds a flush for each probe schedule at ONE flush shape: the
    chip's answer to which steps of ``fpset.lookup_or_insert``'s
    ladder pay for themselves (PR 37).  The table is pre-filled to
    ``--load``; every flush presents ``--lanes`` lanes of which a
    ``--valid`` share is valid, a ``--dup`` share of those keys the
    table holds already and the rest new; ``--reps`` flushes run in
    one dispatch, each on the table the one before left."""
    import json

    from pulsar_tlaplus_tpu.ops import fpset
    from pulsar_tlaplus_tpu.ops.dedup import _fmix

    nq, cap, K = args.lanes, 1 << args.cap_log2, args.cols
    u = jnp.uint32
    table, keys_of, n_pre = _prefilled(cap, K, args.load)
    lane = jnp.arange(nq, dtype=u)
    share = lambda x: u(int(x * 65536))  # noqa: E731

    def flushes(dense, stages, materialize):
        def body(i, carry):
            tc, lanes, valid_n, failed = carry
            h = _fmix(lane ^ _fmix(i.astype(u) + u(0x51ED27)))
            valid = (h & u(0xFFFF)) < share(args.valid)
            dup = ((h >> 16) & u(0xFFFF)) < share(args.dup)
            old = _fmix(h) % u(max(n_pre, 1))
            new = u(n_pre) + i.astype(u) * u(nq) + lane
            idx = jnp.where(dup & (n_pre > 0), old, new)
            _, tc, nf, _, lr, _, _ = fpset.lookup_or_insert(
                tc, keys_of(idx), valid, dense_rounds=dense,
                stages=stages, materialize=materialize,
            )
            return (tc, lanes + lr.astype(jnp.float32),
                    valid_n + jnp.sum(valid.astype(jnp.int32)),
                    failed + nf)
        return jax.jit(lambda tc: lax.fori_loop(
            0, args.reps, body,
            (tc, jnp.float32(0), jnp.int32(0), jnp.int32(0)),
        )[1:])

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for spec in args.schedules.split(";"):
            mat = None
            if "@" in spec:
                spec, mat = spec.split("@")
            dense, *steps = spec.split(",")
            dense = int(dense)
            stages = tuple(
                tuple(int(x) for x in step.split(":")) for step in steps
            )
            (lanes, valid_n, failed), med = timed(
                f"{spec} {mat or ''}", flushes(dense, stages, mat), table,
                reps=args.timed_reps,
            )
            row = {
                "schedule": spec, "materialize": mat, "lanes": nq,
                "cap_log2": args.cap_log2, "load": args.load,
                "valid": args.valid, "dup": args.dup, "reps": args.reps,
                "ms_a_flush": round(med * 1e3 / args.reps, 4),
                "lanes_presented_per_valid": round(
                    float(lanes) / max(int(valid_n), 1), 4),
                "failed": int(failed),
                "device": jax.devices()[0].device_kind,
            }
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


# ---------------------------------------------------------- arbitrate


def _win_by_sort(bid, s, lane_ids, cap):
    """The sort form of the lane arbitration (ISSUE 40's other
    candidate, kept here for the measurement): one sort by (slot, lane
    id) carrying the position, the head of each slot's run wins, and
    the flags go back by position."""
    nq = s.shape[0]
    bid_slot = jnp.where(bid, s, cap)
    pos = jnp.arange(nq, dtype=jnp.int32)
    ss, _, sp = lax.sort((bid_slot, lane_ids, pos), num_keys=2)
    head = jnp.concatenate([jnp.ones((1,), jnp.bool_), ss[1:] != ss[:-1]])
    won = jnp.zeros((nq,), jnp.bool_).at[sp].set(head, unique_indices=True)
    return bid & won


def _win_by_lane_or(bid, s, lane_ids, cap):
    """The pairwise form as an or-reduce (two compares, an and and an
    or a pair, against ``fpset.win_among_lanes``' compare, select and
    min): a lane wins unless another bidder has its slot and a smaller
    id."""
    bid_slot = jnp.where(bid, s, cap)
    beaten = jnp.any(
        (bid_slot[None, :] == bid_slot[:, None])
        & (lane_ids[None, :] < lane_ids[:, None]),
        axis=1,
    )
    return bid & ~beaten


def cmd_arbitrate(args):
    """Microseconds a round for the probe's arbitrations (PR 40): the
    ``claims`` array at the table's size against the pairwise compare
    among the lanes (and the sort form), bare in a ``fori_loop`` and
    inside ``fpset.probe_insert`` on a pre-filled table with the rule
    held to each side.  It is the measurement behind
    ``fpset.arbitrates_among_lanes``."""
    import json

    from pulsar_tlaplus_tpu.ops import fpset
    from pulsar_tlaplus_tpu.ops.dedup import _fmix

    u = jnp.uint32
    widths = [int(x) for x in args.widths.split(",")]
    caps = [1 << int(x) for x in args.caps_log2.split(",")]
    forms = {
        "claims": fpset.win_by_claims,
        "lanes": fpset.win_among_lanes,
        "lanes_or": _win_by_lane_or,
        "sort": _win_by_sort,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def emit(row):
        row["device"] = jax.devices()[0].device_kind
        print(json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()

    def bare(form, nq, cap, reps):
        lane = jnp.arange(nq, dtype=u)
        ids = lane.astype(jnp.int32) * 3 + 7  # a compacted buffer's ids

        def body(i, acc):
            h = _fmix(lane ^ _fmix(i.astype(u) + acc.astype(u)))
            s = (h & u(cap - 1)).astype(jnp.int32)
            bid = ((h >> 24) & u(0xFF)) < u(77)  # 30% bid
            if form is None:
                return acc + jnp.sum((bid & (s > 3)).astype(jnp.int32))
            win = form(bid, jnp.where(bid, s, cap), ids, cap)
            return acc + jnp.sum(win.astype(jnp.int32))
        return jax.jit(lambda: lax.fori_loop(0, reps, body, jnp.int32(0)))

    if "bare" in args.sections:
        for nq in widths:
            _, base = timed(f"bare none {nq}", bare(None, nq, caps[0],
                                                    args.reps))
            for name, form in forms.items():
                for cap in (caps if name == "claims" else caps[-1:]):
                    t0 = time.time()
                    fn = bare(form, nq, cap, args.reps)
                    _, med = timed(f"bare {name} {nq} 2^{cap.bit_length()-1}",
                                   fn)
                    emit({
                        "section": "bare", "form": name, "lanes": nq,
                        "cap_log2": cap.bit_length() - 1,
                        "us_a_round": round(
                            (med - base) * 1e6 / args.reps, 3),
                        "first_call_s": round(time.time() - t0, 2),
                    })

    if "probe" in args.sections:
        K = 2
        real_rule = fpset.arbitrates_among_lanes
        for cap in caps:
            table, keys_of, n_pre = _prefilled(cap, K, args.load)
            for nq in widths:
                # what an iteration adds stays under a tenth of the table
                reps = max(2, min(args.reps, int(0.1 * cap / (0.15 * nq))))
                lane = jnp.arange(nq, dtype=u)

                def loop(tc):
                    def body(i, carry):
                        tc, rounds = carry
                        h = _fmix(lane ^ _fmix(i.astype(u) + u(0x51ED27)))
                        valid = (h & u(0xFF)) < u(128)
                        dup = ((h >> 8) & u(0xFF)) < u(179)
                        old = _fmix(h) % u(max(n_pre, 1))
                        new = u(n_pre) + i.astype(u) * u(nq) + lane
                        idx = jnp.where(dup, old, new)
                        _, tc, _, _, r, _ = fpset.probe_insert(
                            tc, keys_of(idx), valid)
                        return tc, rounds + r
                    return lax.fori_loop(
                        0, reps, body, (tc, jnp.int32(0)))[1]

                for name, rule in (
                    ("claims", lambda nq, cap: False),
                    ("lanes", lambda nq, cap: True),
                ):
                    fpset.arbitrates_among_lanes = rule
                    try:
                        # a new function a side: one traced under the
                        # other rule must not be found again
                        rounds, med = timed(
                            f"probe {name} {nq} 2^{cap.bit_length()-1}",
                            jax.jit(lambda tc, _f=loop: _f(tc)), table)
                    finally:
                        fpset.arbitrates_among_lanes = real_rule
                    emit({
                        "section": "probe", "form": name, "lanes": nq,
                        "cap_log2": cap.bit_length() - 1, "reps": reps,
                        "rounds": int(rounds),
                        "us_a_round": round(
                            med * 1e6 / max(int(rounds), 1), 3),
                        "rule": bool(real_rule(nq, cap)),
                    })
            del table
    return 0


# ------------------------------------------------------------ scatter


def cmd_scatter(args):
    """Microseconds a round for the probe's column write (the part
    ``write``; PR 40 and PR 42): ``--updates`` lanes into ``u32[cap +
    1]`` columns carried by a ``fori_loop``, as ``probe_insert`` writes
    them on a wide round (non-winners parked on the trash row) and with
    the candidates: non-winners dropped out of bounds,
    ``unique_indices`` (a hint that the parked lanes belie: a timing,
    not a result), every lane a winner with and without
    ``indices_are_sorted``, two columns in one loop; and the narrow
    round's write on two columns, the winners packed and handed over in
    chunks of 1/D of the lanes (``directD``) or of N lanes
    (``directcN``): ``fpset.write_winners`` itself (a prefix sum, the
    slots and key words scattered to their ranks, the chunked table
    scatters), and the packings not taken: the lane indices scattered
    and the rest gathered through them (``compactD``, ``compactcN``),
    one sort on the flag (``sortedD``, ``sortedcN``).  ``--win`` is the
    share of the lanes that win, a list: one compile serves every
    share.  Whether the seconds go by the slot, by the lane handed over
    or by the round shows in the rows of one width across table sizes
    and shares."""
    import json
    import re

    from pulsar_tlaplus_tpu.ops import fpset
    from pulsar_tlaplus_tpu.ops.dedup import _fmix

    u = jnp.uint32
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def in_chunks(cols, ws, ks, n_win, chunk):
        # the packed winners to the table, a chunk a trip
        def trip(i, cols):
            ws_i = lax.dynamic_slice(ws, (i * chunk,), (chunk,))
            return tuple(
                c.at[ws_i].set(lax.dynamic_slice(k, (i * chunk,), (chunk,)))
                for c, k in zip(cols, ks)
            )

        return lax.fori_loop(0, (n_win + chunk - 1) // chunk, trip, cols)

    def by_index(cols, win, s, ks, chunk):
        # the packing not taken: the winners' lane indices scattered to
        # their ranks, the slots and key words gathered through them
        nq, cap = win.shape[0], cols[0].shape[0] - 1
        rank = jnp.cumsum(win.astype(jnp.int32))
        n_win = rank[nq - 1]
        lane = jnp.arange(nq, dtype=jnp.int32)
        idx = jnp.zeros((nq + 1,), jnp.int32).at[
            jnp.where(win, rank - 1, nq)
        ].set(lane)[:nq]
        ws = jnp.where(lane < n_win, s[idx], cap)
        return in_chunks(cols, ws, tuple(k[idx] for k in ks), n_win, chunk)

    def by_sort(cols, win, s, ks, chunk):
        # the packing not taken: one sort on the flag
        cap = cols[0].shape[0] - 1
        _, ws, *ks = lax.sort(
            ((~win).astype(jnp.int32), jnp.where(win, s, cap), *ks),
            num_keys=1, is_stable=False,
        )
        return in_chunks(
            cols, ws, ks, jnp.sum(win.astype(jnp.int32)), chunk
        )

    def variant(name, nq, cap, reps):
        lane = jnp.arange(nq, dtype=u)
        packed = re.fullmatch(r"(compact|direct|sorted)(c?)(\d+)", name)
        chunk = 0
        if packed and packed.group(2):
            chunk = min(nq, int(packed.group(3)))
        elif packed:
            chunk = min(nq, max(nq // int(packed.group(3)), fpset.WRITE_CHUNK))

        def body(i, carry):
            cols, win_of_1024 = carry
            h = _fmix(lane ^ _fmix(i.astype(u) + cols[0][cap]))
            if "allwin" in name:
                win = lane >= u(0)
            else:
                win = ((h >> 22) & u(0x3FF)) < win_of_1024
            if "sorted" in name:
                # distinct ascending slots, a random start a round
                s = (h[0] & u(cap // 2 - 1)) + lane * u(cap // (2 * nq))
            else:
                s = h & u(cap - 1)
            s = s.astype(jnp.int32)
            ks = tuple(h ^ u(j + 1) for j in range(len(cols)))
            if packed and packed.group(1) == "direct":
                cols, _, _ = fpset.write_winners(
                    cols, None, win, s, ks, chunk
                )
                return cols, win_of_1024
            if packed:
                pack = by_index if packed.group(1) == "compact" else by_sort
                return pack(cols, win, s, ks, chunk), win_of_1024
            kw = {}
            if "drop" in name:
                ws, kw["mode"] = jnp.where(win, s, cap + 1), "drop"
            else:
                ws = jnp.where(win, s, cap)
            if "unique" in name:
                kw["unique_indices"] = True
            if "sorted" in name:
                kw["indices_are_sorted"] = True
            return tuple(
                c.at[ws].set(k, **kw) for c, k in zip(cols, ks)
            ), win_of_1024
        ncols = 2 if packed or "two" in name else 1
        return jax.jit(
            lambda cols, w: lax.fori_loop(0, reps, body, (cols, w))[0],
            donate_argnums=0,
        ), ncols, chunk

    shares = [float(x) for x in str(args.win).split(",")]
    for cap_log2 in (int(x) for x in args.caps_log2.split(",")):
        cap = 1 << cap_log2
        for nq in (int(x) for x in args.updates.split(",")):
            for name in args.variants.split(","):
                fn, ncols, chunk = variant(name, nq, cap, args.reps)
                cols = tuple(
                    jnp.full((cap + 1,), 0xFFFFFFFF, u) for _ in range(ncols)
                )
                t0 = time.time()
                cols = barrier(fn(cols, u(0)))  # compile
                compile_s = round(time.time() - t0, 2)
                for share in shares:
                    win = u(round(share * 1024))
                    times = []
                    for _ in range(3):
                        t0 = time.time()
                        cols = barrier(fn(cols, win))
                        times.append(time.time() - t0)
                    us = sorted(times)[1] * 1e6 / args.reps
                    row = {
                        "variant": name, "updates": nq,
                        "cap_log2": cap_log2, "win": share,
                        "columns": ncols, "chunk": chunk,
                        "compile_s": compile_s,
                        "us_a_scatter": round(us / ncols, 3),
                        "us_a_round": round(us, 3),
                        "device": jax.devices()[0].device_kind,
                    }
                    print(json.dumps(row), flush=True)
                    out.write(json.dumps(row) + "\n")
                    out.flush()
                del cols
    return 0


def cmd_deflate(args):
    """Seconds, bytes and the threads' own seconds in zlib for one
    frame-like set of arrays at ``--states`` (random key words, sorted
    slots, packed rows, a sorted parent log, small-integer lanes: 32 B
    a state) through ``utils/ckpt._write_npz`` at each ``--blocks-kb``
    x ``--threads`` (PR 45: the measurement behind ``DEFLATE_BLOCK`` and
    the worker rule), beside ``np.savez_compressed`` on the same arrays.
    Host code only: no device is touched."""
    import json

    from pulsar_tlaplus_tpu.utils import ckpt

    rng = np.random.default_rng(0)
    n = args.states
    arrays = {
        "fpk0": rng.integers(0, 2**32, n, dtype=np.uint32),
        "fpk1": rng.integers(0, 2**32, n, dtype=np.uint32),
        "fp_slot": np.sort(rng.integers(0, 4 * n, n)).astype(np.int64),
        "rows": rng.integers(0, 2**20, 2 * n).astype(np.uint32),
        "parent": np.sort(rng.integers(0, n, n)).astype(np.int32),
        "lane": rng.integers(0, 19, n).astype(np.int32),
    }
    raw = sum(a.nbytes for a in arrays.values())
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    tmp = args.out + ".npz"
    cores = ckpt._usable_cores()

    def best_of(fn):
        rows = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            st = fn()
            rows.append((time.perf_counter() - t0, st))
        return min(rows, key=lambda r: r[0])

    with open(args.out, "a") as out:
        def say(row):
            row.update(states=n, raw_bytes=raw, usable_cores=cores)
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")

        s, _ = best_of(lambda: np.savez_compressed(tmp, **arrays))
        ref_bytes = os.path.getsize(tmp)
        say({"writer": "np.savez_compressed", "s": round(s, 4),
             "bytes": ref_bytes, "mb_s": round(raw / s / 1e6, 2)})
        for kb in (int(x) for x in args.blocks_kb.split(",")):
            for thr in (int(x) for x in args.threads.split(",")):
                ckpt.DEFLATE_BLOCK = kb << 10
                ckpt._deflate_threads = lambda _n, t=thr: t
                s, st = best_of(lambda: ckpt._write_npz(tmp, arrays))
                nbytes = os.path.getsize(tmp)
                say({"writer": "blocks", "block_kb": kb, "threads": thr,
                     "s": round(s, 4), "bytes": nbytes,
                     "over_numpy_pct": round(
                         100.0 * (nbytes - ref_bytes) / ref_bytes, 4),
                     "mb_s": round(raw / s / 1e6, 2),
                     "blocks": st["deflate_blocks"],
                     "cpu_s": round(st["deflate_cpu_s"], 4),
                     "speedup": round(st["deflate_cpu_s"] / s, 3)})
    os.remove(tmp)
    return 0


# --------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="real-chip profiling probes (see module docstring "
        "for the retired-script mapping)"
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    pe = sub.add_parser("expand", help="expand-pipeline stage breakdown")
    pe.add_argument("--mode", choices=["timed", "chained"],
                    default="chained")
    pe.add_argument("--chunk", type=int, default=8192)
    pe.add_argument("--cap", type=int, default=23, help="log2 visited cap")
    pe.add_argument("--fill", type=int, default=3_000_000,
                    help="pre-inserted random keys (sets load factor)")
    pe.set_defaults(fn=cmd_expand)

    pp = sub.add_parser("prims", help="primitive cost curves")
    pp.add_argument("--set", choices=["v1", "sorts", "big", "gather",
                                      "all"], default="all")
    pp.set_defaults(fn=cmd_prims)

    ps = sub.add_parser(
        "stages", help="device-engine per-dispatch stage costs "
        "(+ fused megakernel comparison)")
    ps.add_argument("--sub-batch-log2", type=int, default=19)
    ps.add_argument("--flush-factor", type=int, default=1)
    ps.add_argument("--run", type=float, default=None, metavar="S",
                    help="instead: budgeted bench-shape run under "
                    "PTT_STAGE_TIMING (old profile_stages5)")
    ps.add_argument("--fuse", choices=["level", "stage"],
                    default="level", help="fusion mode for --run")
    ps.set_defaults(fn=cmd_stages)

    pl = sub.add_parser("lsm", help="round-3 LSM primitive shapes")
    pl.add_argument("--section", choices=["sort", "sort4", "gather",
                                          "scatter"], default="sort",
                    help="one section per process (incompatible "
                    "buffer sets)")
    pl.set_defaults(fn=cmd_lsm)

    pb = sub.add_parser("bucket", help="bucketized-hash primitives")
    pb.set_defaults(fn=cmd_bucket)

    pc = sub.add_parser(
        "calibrate",
        help="write calibration.json for the fused-era cost-"
        "attribution model: a -fuse stage + PTT_STAGE_TIMING "
        "reference run divided by its own work-unit counts "
        "(docs/observability.md \"Attribution\")")
    pc.add_argument("--out", default="calibration.json",
                    help="output file (default ./calibration.json)")
    pc.add_argument("--config", choices=["shipped", "small"],
                    default="shipped",
                    help="reference config: shipped 45,198-state "
                    "binding (default) or the small 1,654-state smoke")
    pc.add_argument("--sweep", action="store_true",
                    help="also run a liveness check and calibrate the "
                    "sweep unit cost from its measured sweep wall")
    pc.set_defaults(fn=cmd_calibrate)

    pd = sub.add_parser(
        "ladder", help="seconds a flush for each fpset probe schedule "
        "at one flush shape (which ladder steps pay for themselves)")
    pd.add_argument("--lanes", type=int, default=65536,
                    help="lanes a flush (cli check: sub_batch 4096 x "
                    "16 actions)")
    pd.add_argument("--cap-log2", type=int, default=25)
    pd.add_argument("--cols", type=int, default=2)
    pd.add_argument("--load", type=float, default=0.3)
    pd.add_argument("--valid", type=float, default=0.11)
    pd.add_argument("--dup", type=float, default=0.45)
    pd.add_argument("--reps", type=int, default=200)
    pd.add_argument("--timed-reps", type=int, default=3)
    pd.add_argument(
        "--schedules",
        default="4,4:16,64:64;4,4:16,16:32,64:64;"
        "4,4:16,8:24,16:32,32:48,64:64",
        help="';'-separated ladders 'DENSE[,DIV:LIMIT]*' (full-width "
        "round ceiling, then shrink divisor and round ceiling a step), "
        "each optionally '@shift' / '@roll' / '@gather' for its "
        "compactions")
    pd.add_argument("--out", default="chiprun_out/ladder.jsonl")
    pd.set_defaults(fn=cmd_ladder)

    pa = sub.add_parser(
        "arbitrate", help="microseconds a probe round by arbitration: "
        "the claims array against the compare among the lanes")
    pa.add_argument("--widths", default="1024,2048,4096,8192,16384")
    pa.add_argument("--caps-log2", default="18,20,22,24,25")
    pa.add_argument("--sections", default="bare,probe")
    pa.add_argument("--load", type=float, default=0.3)
    pa.add_argument("--reps", type=int, default=200)
    pa.add_argument("--out", default="chiprun_out/arbitrate.jsonl")
    pa.set_defaults(fn=cmd_arbitrate)

    pw = sub.add_parser(
        "scatter", help="microseconds a scatter for the probe's column "
        "write, as it is and by the candidates of the next PR")
    pw.add_argument("--updates", default="1024,4096")
    pw.add_argument("--caps-log2", default="20,24,25")
    pw.add_argument("--variants",
                    default="as_is,drop,unique,drop_unique,allwin,"
                    "allwin_unique,allwin_sorted_unique,two_as_is",
                    help="names made of: drop (non-winners out of "
                    "bounds, not on the trash row), unique, sorted "
                    "(the scatter's hints; sorted wants allwin), "
                    "allwin (every lane writes), two (two columns); "
                    "or directD / directcN (compact, sorted: the "
                    "packings not taken): the narrow round's write by "
                    "the winners, chunks of 1/D of the lanes or of N")
    pw.add_argument("--win", default="0.1",
                    help="shares of the lanes that win a slot, a list")
    pw.add_argument("--reps", type=int, default=500)
    pw.add_argument("--out", default="chiprun_out/scatter.jsonl")
    pw.set_defaults(fn=cmd_scatter)

    pz = sub.add_parser(
        "deflate", help="the frame writer by block size and threads, "
        "beside np.savez_compressed (host only)")
    pz.add_argument("--states", type=int, default=1_500_000)
    pz.add_argument("--blocks-kb", default="64,256,1024,4096")
    pz.add_argument("--threads", default="1,2,4,8,12")
    pz.add_argument("--reps", type=int, default=2)
    pz.add_argument("--out", default="chiprun_out/deflate.jsonl")
    pz.set_defaults(fn=cmd_deflate)

    args = ap.parse_args(argv)
    return args.fn(args) or 0


if __name__ == "__main__":
    sys.exit(main())
