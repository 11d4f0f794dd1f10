"""Bisect the bench-shape init-phase OOM: dispatch each stage with a
hard barrier and print progress, so the failing computation is named
instead of surfacing at the next async fetch."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pulsar_tlaplus_tpu.utils.device import setup_compile_cache  # noqa: E402

setup_compile_cache()


def barrier(o, tag):
    leaf = jax.tree_util.tree_leaves(o)[0]
    np.asarray(jnp.ravel(leaf)[0])
    print(f"  {tag}: ok", flush=True)


def main():
    from pulsar_tlaplus_tpu.engine.device_bfs import BIG, DeviceChecker
    from pulsar_tlaplus_tpu.models.compaction import CompactionModel
    from pulsar_tlaplus_tpu.ops.dedup import SENTINEL
    from pulsar_tlaplus_tpu.ref.pyeval import Constants

    c = Constants(
        message_sent_limit=64, compaction_times_limit=3, num_keys=8,
        num_values=2, retain_null_key=True, max_crash_times=3,
        model_producer=True, model_consumer=False,
    )
    model = CompactionModel(c)
    ck = DeviceChecker(
        model, sub_batch=1 << 18, expand_chunk=1 << 13,
        visited_cap=1 << 26, frontier_cap=32_000_000,
        max_states=32_000_000, group=2,
    )
    print(
        f"G={ck.G} ACAP={ck.ACAP} APAD={ck.APAD} VCAP={ck.VCAP} "
        f"LCAP={ck.LCAP} K={ck.K}", flush=True,
    )
    print(f"warmup: {ck.warmup():.1f}s", flush=True)
    K = ck.K
    bufs = {
        "vk": tuple(
            jnp.full((ck.VCAP,), SENTINEL, jnp.uint32) for _ in range(K)
        ),
        "ak": tuple(
            jnp.full((ck.ACAP,), SENTINEL, jnp.uint32) for _ in range(K)
        ),
        "arows": jnp.zeros((ck.ACAP * ck.W,), jnp.uint32),
        "rows": jnp.zeros((ck.LCAP * ck.W,), jnp.uint32),
        "parent": jnp.zeros((ck.LCAP,), jnp.int32),
        "lane": jnp.zeros((ck.LCAP,), jnp.int32),
    }
    barrier(bufs["rows"], "alloc persistent")
    out = ck._init_jit()(
        *bufs["ak"], bufs["arows"], jnp.int32(0), jnp.int32(0)
    )
    bufs["ak"], bufs["arows"] = out[:K], out[K]
    barrier(out[0], "init window")
    fl = ck._flush_jit()(*bufs["vk"], *bufs["ak"], jnp.int32(ck.NCs))
    bufs["vk"] = fl[:K]
    barrier(fl[K], "flush")
    n_new, new_pay = fl[K], fl[K + 1]
    viol0 = jnp.full((len(ck.invariant_names),), int(BIG), jnp.int32)
    wr = ck._append_jit()(
        bufs["rows"], bufs["parent"], bufs["lane"],
        bufs["arows"], new_pay, n_new, jnp.int32(0), viol0,
        jnp.int32(0), jnp.bool_(True),
    )
    barrier(wr[3], "append")
    print("init phase complete", flush=True)
    # one expand round on the (single) frontier row
    out = ck._expand_jit()(
        *bufs["ak"], bufs["arows"],
        ck._slice_jit()(wr[0], jnp.int32(0)),
        jnp.int32(0), jnp.int32(1), BIG, jnp.int32(0), jnp.int32(0),
    )
    barrier(out[0], "expand round")
    fl2 = ck._flush_jit()(*bufs["vk"], *out[:K], jnp.int32(ck.NCs))
    barrier(fl2[K], "flush 2")
    print(f"n_new level2 = {int(np.asarray(fl2[K]))}", flush=True)


if __name__ == "__main__":
    main()
