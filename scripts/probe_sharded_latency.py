"""Per-dispatch latency of the sharded engine's level loop at n=1 on
the real chip — why do tiny early levels cost ~20 s each when deep
levels run cycles at 60 ms? (bench_sharded_n1 observation, round 4).

Uses the small liveness-scale config (54-bit state, W=2) so compiles
are cheap; timings isolate device_put-with-sharding, round dispatch,
flush dispatch, append dispatch, and the stats fetch.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.utils.device import setup_compile_cache  # noqa: E402

setup_compile_cache()


def t(tag, fn):
    t0 = time.time()
    out = fn()
    if out is not None:
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(jnp.ravel(leaf)[0])
    print(f"{tag:38s} {time.time()-t0:7.2f} s", flush=True)
    return out


def main():
    from pulsar_tlaplus_tpu.engine.sharded_device import (
        ShardedDeviceChecker,
    )
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    import sys as _sys
    big = "--big" in _sys.argv
    if big:
        c = Constants(
            message_sent_limit=64, compaction_times_limit=3, num_keys=8,
            num_values=2, retain_null_key=True, max_crash_times=3,
            model_producer=True, model_consumer=False,
        )
    else:
        c = Constants(
            message_sent_limit=4, compaction_times_limit=3, num_keys=2,
            num_values=2, retain_null_key=True, max_crash_times=2,
            model_producer=True, model_consumer=False,
        )
    print(f"device {jax.devices()[0]}", flush=True)
    ck = ShardedDeviceChecker(
        CompactionModel(c), n_devices=1,
        sub_batch=(1 << 18) if big else (1 << 16),
        expand_chunk=(1 << 13) if big else None,
        visited_cap=(1 << 26) if big else (1 << 22),
        max_states=24_000_000 if big else 4_000_000, group=2,
        flush_factor=2 if big else 1,
        append_chunk=(1 << 17) if big else None,
    )
    sh = ck._shard()
    N, K = ck.N, ck.K

    bufs = {}
    t("alloc vk+acc (device)", lambda: None)
    bufs["vk"] = tuple(
        jnp.full((N, ck.VCAP), 0xFFFFFFFF, jnp.uint32, device=sh)
        for _ in range(K)
    )
    ck._alloc_acc(bufs)
    bufs["rows"] = jnp.zeros((N, ck.LCAP * ck.W), jnp.uint32, device=sh)
    bufs["parent"] = jnp.zeros((N, ck.LCAP), jnp.int32, device=sh)
    bufs["lane"] = jnp.zeros((N, ck.LCAP), jnp.int32, device=sh)
    st = {
        "n_visited": jnp.zeros((N,), jnp.int32, device=sh),
        "dead": jnp.full((N,), 2**31 - 1, jnp.int32, device=sh),
        "viol": jnp.full(
            (N, len(ck.invariant_names)), 2**31 - 1, jnp.int32,
            device=sh,
        ),
        "ovf": jnp.zeros((N,), jnp.bool_, device=sh),
    }
    t("barrier persistent allocs", lambda: bufs["rows"])

    # compile everything once (rebinding donated buffers each time)
    o = t("compile initround", lambda: ck._init_round_jit()(
        bufs["ak"], bufs["arows"], bufs["apar"], bufs["alane"],
        st["ovf"], jnp.int32(0), jnp.int32(0),
    ))
    bufs["ak"] = tuple(o[0])
    bufs["arows"], bufs["apar"], bufs["alane"], st["ovf"] = o[1:]
    lb = t("device_put lb (sharded)", lambda: jax.device_put(
        np.zeros((N,), np.int32), sh))
    nf = t("device_put nf (sharded)", lambda: jax.device_put(
        np.ones((N,), np.int32), sh))
    o = t("compile round", lambda: ck._round_jit()(
        bufs["ak"], bufs["arows"], bufs["apar"], bufs["alane"],
        bufs["rows"], lb, nf, st["dead"], st["ovf"], jnp.int32(0),
        jnp.int32(0),
    ))
    bufs["ak"] = tuple(o[0])
    bufs["arows"], bufs["apar"], bufs["alane"] = o[1], o[2], o[3]
    st["dead"], st["ovf"] = o[4], o[5]
    out = t("compile flush", lambda: ck._flush_jit()(
        bufs["vk"], bufs["ak"], jnp.int32(0)))
    bufs["vk"] = tuple(out[0])
    ao = t("compile append", lambda: ck._append_jit()(
        bufs["rows"], bufs["parent"], bufs["lane"], bufs["arows"],
        bufs["apar"], bufs["alane"], out[2], out[1], st["n_visited"],
        st["viol"],
    ))
    (
        bufs["rows"], bufs["parent"], bufs["lane"],
        st["n_visited"], st["viol"],
    ) = ao
    t("compile stats", lambda: ck._stats_jit()(
        st["n_visited"], st["dead"], st["viol"], st["ovf"]))

    # steady-state per-dispatch costs
    for i in range(3):
        lb = t(f"[{i}] device_put lb", lambda: jax.device_put(
            np.zeros((N,), np.int32), sh))
        nf = t(f"[{i}] device_put nf", lambda: jax.device_put(
            np.ones((N,), np.int32), sh))
        o = t(f"[{i}] round dispatch+drain", lambda: ck._round_jit()(
            bufs["ak"], bufs["arows"], bufs["apar"], bufs["alane"],
            bufs["rows"], lb, nf, st["dead"], st["ovf"], jnp.int32(0),
            jnp.int32(0),
        ))
        bufs["ak"] = tuple(o[0])
        bufs["arows"], bufs["apar"], bufs["alane"] = o[1], o[2], o[3]
        st["dead"], st["ovf"] = o[4], o[5]
        fo = t(f"[{i}] flush dispatch+drain", lambda: ck._flush_jit()(
            bufs["vk"], bufs["ak"], jnp.int32(100)))
        bufs["vk"] = tuple(fo[0])
        ao = t(f"[{i}] append dispatch+drain", lambda: ck._append_jit()(
            bufs["rows"], bufs["parent"], bufs["lane"], bufs["arows"],
            bufs["apar"], bufs["alane"], fo[2], fo[1],
            st["n_visited"], st["viol"],
        ))
        (
            bufs["rows"], bufs["parent"], bufs["lane"],
            st["n_visited"], st["viol"],
        ) = ao
        t(f"[{i}] stats fetch", lambda: np.asarray(ck._stats_jit()(
            st["n_visited"], st["dead"], st["viol"], st["ovf"])) is None
          or None)


if __name__ == "__main__":
    main()
