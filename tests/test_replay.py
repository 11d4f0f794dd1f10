"""The device engines' discovery order, held to the reference.

The row store and the parent and lane logs are what counterexample
traces, the liveness sweep and the benchmark's sample replay read.
Every state an engine found is rebuilt from its logs and held to
``ref/pyeval``: each gid's state is the reference's successor of its
parent's state under the action its lane names, the levels are
contiguous and in breadth-first order, and the states are exactly the
reference's reachable set.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.engine.sharded_device import ShardedDeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from tests.helpers import SMALL_CONFIGS

ENGINES = ("device-all", "device-frontier", "sharded-2")


@functools.lru_cache(maxsize=None)
def _reference(name):
    """The reference's own search: ``(depth of each reachable state,
    successors of each)``; depth 1 = initial, as the engines count."""
    c = SMALL_CONFIGS[name]
    depth, succ = {}, {}
    frontier = []
    for s in pe.initial_states(c):
        if s not in depth:
            depth[s] = 1
            frontier.append(s)
    while frontier:
        nxt = []
        for s in frontier:
            succ[s] = tuple(pe.successors(c, s))
            for _a, t in succ[s]:
                if t not in depth:
                    depth[t] = depth[s] + 1
                    nxt.append(t)
        frontier = nxt
    return depth, succ


def _run(name, engine):
    """Run one engine; return ``(model, result, logs, rows, shard_of)``
    with ``logs`` a list of ``(gid, parent, lane)``, ``rows`` a dict
    gid -> packed row for the engines that keep every row, and
    ``shard_of`` the gid's shard (None on one device)."""
    m = CompactionModel(SMALL_CONFIGS[name])
    if engine == "sharded-2":
        ck = ShardedDeviceChecker(
            m, n_devices=2, invariants=(), sub_batch=256,
            visited_cap=1 << 10,
        )
        r = ck.run()
        counts = np.asarray(ck.last_stats_matrix[:, 0])
        logs, rows = [], {}
        for s in range(ck.N):
            n = int(counts[s])
            par = np.asarray(ck.last_bufs["parent"][s, :n])
            lan = np.asarray(ck.last_bufs["lane"][s, :n])
            rws = np.asarray(
                ck.last_bufs["rows"][s, : n * ck.W]
            ).reshape(n, ck.W)
            for i in range(n):
                g = (s << ck.SB) | i
                logs.append((g, int(par[i]), int(lan[i])))
                rows[g] = rws[i]
        return m, r, logs, rows, lambda g: g >> ck.SB
    kw = dict(
        invariants=(), sub_batch=512, visited_cap=1 << 10,
        frontier_cap=1 << 10,
    )
    if engine == "device-frontier":
        # a window that holds the widest frontier and the level built
        # from it; the rows of older levels are dropped as the run goes
        kw.update(rows_window="frontier", row_cap_states=1 << 15)
    ck = DeviceChecker(m, **kw)
    r = ck.run()
    n = r.distinct_states
    par = np.asarray(ck.last_bufs["parent"][:n])
    lan = np.asarray(ck.last_bufs["lane"][:n])
    logs = [(g, int(par[g]), int(lan[g])) for g in range(n)]
    rows = {}
    if engine == "device-all":
        rws = np.asarray(ck.last_bufs["rows"][: n * ck.W]).reshape(
            n, ck.W
        )
        rows = {g: rws[g] for g in range(n)}
    return m, r, logs, rows, None


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", sorted(SMALL_CONFIGS))
def test_logs_replay_through_reference(name, engine):
    c = SMALL_CONFIGS[name]
    depth, succ = _reference(name)
    m, r, logs, rows, shard_of = _run(name, engine)
    assert not r.truncated and r.violation is None and not r.deadlock
    assert r.distinct_states == len(logs) == len(depth)
    by_gid = {g: (p, lane) for g, p, lane in logs}
    inits = set(pe.initial_states(c))
    gen_initial = jax.jit(m.gen_initial)
    state, level = {}, {}

    def rebuild(g):
        # parents lie a level up, so the recursion is diameter-deep
        if g in state:
            return state[g]
        p, lane = by_gid[g]
        if p < 0:
            s = m.to_pystate(
                jax.device_get(gen_initial(jnp.int32(-1 - p)))
            )
            assert s in inits, (g, p)
            level[g] = 1
        else:
            ps = rebuild(p)
            aid = int(m.action_ids[lane])
            under = [t for a, t in succ[ps] if a == aid]
            # the producer's lanes share one action and differ in the
            # message they send: the model's host replay says which
            s = under[0] if len(under) == 1 else m._apply_lane_py(ps, lane)
            assert s in under, (g, p, lane, pe.ACTION_NAMES[aid])
            level[g] = level[p] + 1
        state[g] = s
        return s

    for g, _p, _lane in logs:
        rebuild(g)
    # exactly the reference's reachable set, each state once
    assert len(set(state.values())) == len(state)
    assert set(state.values()) == set(depth)
    # breadth-first: a state's level is its depth in the reference's
    # search, and the level sizes are the reference's
    for g, s in state.items():
        assert level[g] == depth[s], g
    want_sizes = np.bincount(list(depth.values()))[1:].tolist()
    assert list(r.level_sizes) == want_sizes
    assert r.diameter == len(want_sizes)
    if shard_of is not None:
        # levels are contiguous within each shard's local gid order
        for sh in {shard_of(g) for g in by_gid}:
            lv = [level[g] for g in sorted(by_gid) if shard_of(g) == sh]
            assert lv == sorted(lv), sh
    else:
        # levels are contiguous in gid order, and within a level the
        # states come in the order their parents were expanded: the
        # first parent (lowest gid) that reaches a state is the one
        # logged for it
        lv = [level[g] for g in range(len(logs))]
        assert lv == sorted(lv)
        first = {}
        for g in range(len(logs)):
            for _a, t in succ[state[g]]:
                first.setdefault(t, g)
        for g, (p, _lane) in by_gid.items():
            if p >= 0:
                assert p == first[state[g]], (g, p)
        pars = [p for _g, p, _l in logs if p >= 0]
        assert pars == sorted(pars)
    # the row store holds each state's packing, where rows are kept
    if rows:
        gids = sorted(rows)
        want = m._pack_pystates([state[g] for g in gids])
        got = np.stack([rows[g] for g in gids])
        assert np.array_equal(got, np.asarray(want))
