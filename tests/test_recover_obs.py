"""A check that is preempted and recovered names, counts and prints what
it did (ISSUE 44): through ``cli.main``, SIGTERM at a pinned level and
``-recover`` give the reference's count, diameter and level sizes; the
recovered line against the reference and the engine's own counters; the
frame's stall in three parts that add up to the ``ckpt`` phase; the
bucketed fetch and restore programs, their scopes, and a second cycle
that builds none; an uncheckpointed check untouched.
"""

import contextlib
import io
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import ckpt_bytes, plug, tlafmt
from benchmark.lib import reference as bench_reference
from pulsar_tlaplus_tpu import cli
from pulsar_tlaplus_tpu.engine import bodies, device_bfs
from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.utils import ckpt
from tests.helpers import SMALL_CONFIGS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "specs", "compaction.tla")
CFG = os.path.join(ROOT, "specs", "compaction.cfg")
CFG_253K = os.path.join(ROOT, "specs", "compaction_253k.cfg")
CADENCE = 5  # DeviceChecker's checkpoint_every default, the CLI's

# the benchmark's cluster manager: standard error that sends SIGTERM at
# a progress line
KillAtLevel = plug.load_file("drivers", "repeat-cli-recover").KillAtLevel

PARTS = ("ckpt_gather_s", "ckpt_pack_s", "ckpt_npz_s")
FRAME_KEYS = PARTS + (
    "ckpt_frames", "ckpt_bytes", "ckpt_write_s", "ckpt_raw_bytes",
    "ckpt_d2h_bytes", "ckpt_states", "ckpt_last_level", "ckpt_retries",
)
RESUME_KEYS = (
    "restore_s", "restore_load_s", "restore_unpack_s", "restore_upload_s",
    "restore_h2d_bytes", "resume_level", "resume_states",
    "resume_levels_run",
)


def _leg(cfg, frame, tel, *, kill_at=None, recover=False):
    """One ``cli.main`` of a cycle: ``(rc, stdout, progress rows, the
    result event's stats, the level the signal went out at)``."""
    if os.path.exists(tel):
        os.remove(tel)
    out = io.StringIO()
    err = (KillAtLevel(kill_at, signal.SIGTERM) if kill_at
           else io.StringIO())
    argv = ["check", SPEC, "-config", cfg, "-checkpoint", frame,
            "-telemetry", tel] + (["-recover"] if recover else [])
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.main(argv)
    with open(tel) as f:
        st = [json.loads(x) for x in f if '"result"' in x][-1]["stats"]
    return (rc, out.getvalue(), ckpt_bytes.progress_rows(err.getvalue()), st,
            getattr(err, "sent_at", None))


def _cycle(cfg, tmp_path, kill_at):
    frame, tel = str(tmp_path / "f.npz"), str(tmp_path / "t.jsonl")
    leg1 = _leg(cfg, frame, tel, kill_at=kill_at)
    there = os.path.exists(frame)
    leg2 = _leg(cfg, frame, tel, recover=True)
    return leg1, there, leg2


@pytest.fixture(scope="module")
def levels_253k():
    """The reference's own search of the 253,361-state binding."""
    sizes, seen = bench_reference.bfs_levels(
        tlafmt.constants_from_cfg(CFG_253K))
    assert (len(seen), len(sizes)) == (253361, 23)
    return sizes


# ---- the cycle, against the reference ------------------------------------


@pytest.mark.parametrize("kill_at, frames_leg2", [
    (9, 3),    # a level before a periodic frame's: 10, 15 and 20 left
    (10, 2),   # on it: the periodic frame and the suspend frame
    (11, 2),   # after it
    (21, 0),   # past the last periodic frame: the suspend frame is the last
])
def test_a_check_killed_and_recovered_is_the_references(
    tmp_path, levels_253k, kill_at, frames_leg2
):
    """Levels wider than a sub-batch close one a dispatch, so the
    frame is at the level of the kill."""
    want = levels_253k
    (rc1, text1, rows1, st1, sent), there, (rc2, text2, rows2, st2, _) = \
        _cycle(CFG_253K, tmp_path, kill_at)
    assert sent == kill_at and rc1 == 3 and there
    assert ckpt_bytes.RESUMABLE_TEXT in text1
    assert rows1[-1][0] == kill_at  # no level closed after the signal
    assert st1["ckpt_frames"] == kill_at // CADENCE + 1
    assert st1["ckpt_last_level"] == kill_at
    assert rc2 == 0
    assert tlafmt.parse_counts(text2) == (sum(want), len(want))
    assert ckpt_bytes.joined_level_sizes(rows1, rows2) == want
    # no level the frame closed is expanded again
    assert rows2[0][0] == kill_at + 1
    line = ckpt_bytes.parse_recovered_line(text2)
    assert line == {
        "level": kill_at, "states": sum(want[:kill_at]),
        "levels_run": len(want) - kill_at,
    } == {
        "level": st2["resume_level"], "states": st2["resume_states"],
        "levels_run": st2["resume_levels_run"],
    }
    assert st2["ckpt_frames"] == frames_leg2
    for st in (st1, st2):
        assert st["ckpt_retries"] == 0 and st["hbm_recovered"] == 0
    assert ckpt_bytes.parse_recovered_line(text1) is None


def test_a_kill_inside_a_ramp_batch_frames_where_the_batch_ends(tmp_path):
    """The shipped binding's levels all fit a sub-batch: a fused
    dispatch closes levels up to the next due frame boundary, so a kill
    at level 12 frames at 15, twice (the periodic and the suspend
    frame), and the recovered run starts from there."""
    want, _seen = bench_reference.bfs_levels(tlafmt.constants_from_cfg(CFG))
    (rc1, _t1, rows1, st1, sent), there, (rc2, text2, rows2, st2, _) = \
        _cycle(CFG, tmp_path, 12)
    assert (rc1, rc2, sent, there) == (3, 0, 12, True)
    assert rows1[-1][0] == 15 and st1["ckpt_frames"] == 15 // CADENCE + 1
    assert ckpt_bytes.joined_level_sizes(rows1, rows2) == want
    assert ckpt_bytes.parse_recovered_line(text2) == {
        "level": 15, "states": sum(want[:15]), "levels_run": 5}
    assert tlafmt.parse_counts(text2) == (45198, 20)


# ---- the counters --------------------------------------------------------


@pytest.fixture(scope="module")
def two_cycles(tmp_path_factory):
    """Two cycles of the shipped binding in this process, SIGTERM at
    level 12."""
    d = tmp_path_factory.mktemp("cycles")
    return [_cycle(CFG, d, 12) for _ in range(2)]


def test_the_three_parts_add_up_to_the_ckpt_phase(two_cycles):
    for leg1, _there, leg2 in two_cycles:
        for st in (leg1[3], leg2[3]):
            assert all(st[k] > 0 for k in PARTS)
            parts = sum(st[k] for k in PARTS)
            # the phase holds the bookkeeping around the parts too
            assert parts <= st["host_ckpt_s"] + 1e-3
            assert st["host_ckpt_s"] - parts < 0.02 + 0.02 * parts
            # ckpt_write_s keeps its meaning: the whole stall
            assert st["ckpt_write_s"] == pytest.approx(parts, abs=2e-3)
            wall = sum(v for k, v in st.items()
                       if k.startswith("host_") and k.endswith("_s")
                       and k != "host_wait_s")
            assert wall > 0 and abs(st["host_unaccounted_s"]) < 0.05 * wall


def test_frame_counters_are_what_the_frames_hold(two_cycles):
    (_rc, _t, _rows, st1, _s), _there, (_r2, _t2, _rows2, st2, _s2) = \
        two_cycles[0]
    want, _seen = bench_reference.bfs_levels(tlafmt.constants_from_cfg(CFG))
    assert all(k in st1 for k in FRAME_KEYS)
    # frames after levels 5, 10, 15 and the suspend frame at 15; then 20
    n1 = sum(want[:5]) + sum(want[:10]) + 2 * sum(want[:15])
    assert (st1["ckpt_states"], st2["ckpt_states"]) == (n1, sum(want))
    assert (st1["ckpt_last_level"], st2["ckpt_last_level"]) == (15, 20)
    for st, n, frames in ((st1, n1, 4), (st2, sum(want), 1)):
        # K = 2 key words, the slot (64 bits), W = 2 row words, parent,
        # lane: 32 bytes a state, and a few small arrays a frame
        reckoned = ckpt_bytes.frame_bytes(n, 2, 2)
        assert reckoned == 32 * n
        assert 0 <= st["ckpt_raw_bytes"] - reckoned < 1024 * frames
        assert st["ckpt_bytes"] < st["ckpt_raw_bytes"] <= st["ckpt_d2h_bytes"]
    assert not [k for k in RESUME_KEYS if k in st1]
    assert all(k in st2 for k in RESUME_KEYS)
    parts = (st2["restore_load_s"] + st2["restore_unpack_s"]
             + st2["restore_upload_s"])
    assert 0 < parts <= st2["restore_s"] + 1e-3
    assert st2["restore_s"] - parts < 0.01
    assert st2["restore_s"] <= st2["host_init_s"] + 1e-3


def test_a_second_cycle_builds_no_program(two_cycles):
    """The fetch and restore programs are units at bucketed lengths:
    the second cycle of a process finds every one (and every other
    unit of the check) already built."""
    _first, second = two_cycles
    for leg in (second[0], second[2]):
        assert leg[3]["jit_body_traces"] == 0


# ---- the programs --------------------------------------------------------


@pytest.mark.parametrize("fn, args, kw, scope", [
    (bodies.ptt_ckpt_fetch,
     (jax.ShapeDtypeStruct((1 << 14,), jnp.uint32), jnp.int32(0)),
     {"size": 1 << 12}, "ptt.ckpt_fetch"),
    (bodies.ptt_restore_pad,
     (jax.ShapeDtypeStruct((1 << 12,), jnp.int32),),
     {"length": 1 << 14}, "ptt.restore"),
])
def test_the_frame_programs_carry_their_scopes(fn, args, kw, scope):
    hlo = fn.lower(*args, **kw).compile().as_text()
    name = fn.__name__
    assert f"jit({name})/{scope}/" in hlo


def _mk(**kw):
    kw.setdefault("invariants", ())
    kw.setdefault("check_deadlock", False)
    kw.setdefault("sub_batch", 64)
    kw.setdefault("visited_cap", 1 << 9)
    kw.setdefault("frontier_cap", 1 << 9)
    return DeviceChecker(CompactionModel(SMALL_CONFIGS["producer_on"]), **kw)


def test_frames_of_many_lengths_meet_few_fetch_shapes(tmp_path, monkeypatch):
    """A frame a level (1,654 states in 15 levels): the device slices
    powers of two, the host trims, and what is saved is what an eager
    slice at the frame's own length saved."""
    shapes, real = [], bodies.ptt_ckpt_fetch

    def recording(buf, start, *, size):
        shapes.append((buf.shape[0], size))
        return real(buf, start, size=size)

    monkeypatch.setattr(bodies, "ptt_ckpt_fetch", recording)
    seen = []
    real_save = ckpt.save_frame

    def keeping(path, sig, arrays, **kw):
        seen.append({k: np.array(v) for k, v in arrays.items()})
        return real_save(path, sig, arrays, **kw)

    monkeypatch.setattr(ckpt, "save_frame", keeping)
    ck = _mk(checkpoint_path=str(tmp_path / "f.npz"), checkpoint_every=1)
    r = ck.run()
    assert r.distinct_states == 1654 and len(seen) >= 10
    assert len(shapes) >= 3 * 4 and len(set(shapes)) <= 6
    assert all(size & (size - 1) == 0 for _n, size in shapes)
    for fr in seen:
        n = int(fr["n_visited"])
        assert fr["parent"].shape == fr["lane"].shape == (n,)
        assert fr["rows"].shape == (n * ck.W,)
        assert int(fr["fp_cnt"][0]) == n
    # the last frame is the whole run's: its logs are the result's
    last = seen[-1]
    n = int(last["n_visited"])
    assert np.array_equal(last["parent"], np.asarray(ck.last_bufs["parent"])[:n])
    assert np.array_equal(last["rows"],
                          np.asarray(ck.last_bufs["rows"])[: n * ck.W])
    assert ck.last_stats["ckpt_d2h_bytes"] >= ck.last_stats["ckpt_raw_bytes"]


@pytest.mark.parametrize("level", [2, 6, 11])
def test_a_restore_at_any_level_continues_to_the_same_end(tmp_path, level):
    """Frames of three sizes restored through the bucketed upload (the
    small ones padded on the device, by ``ptt_restore_pad``): the
    resumed run ends with the unbroken run's level sizes."""
    whole = _mk().run()
    path = str(tmp_path / "f.npz")
    polls = iter(range(1 << 30))
    # a frame a level holds a fused dispatch to one level, so the
    # suspend poll is answered at the level asked for
    ck = _mk(checkpoint_path=path, checkpoint_every=1,
             suspend_hook=lambda: "suspended" if next(polls) >= level - 1
             else None)
    part = ck.run()
    assert part.truncated and len(part.level_sizes) == level
    ck2 = _mk(checkpoint_path=path, checkpoint_every=1 << 20)
    r = ck2.run(resume=True)
    assert not r.truncated
    assert list(r.level_sizes) == list(whole.level_sizes)
    st = ck2.last_stats
    assert (st["resume_level"], st["resume_states"]) == (
        level, sum(whole.level_sizes[:level]))
    assert st["resume_levels_run"] == len(whole.level_sizes) - level
    assert st["restore_h2d_bytes"] > 0


# ---- a check that asks for no frame --------------------------------------


def test_an_uncheckpointed_check_meets_no_frame_program(monkeypatch):
    def never(*a, **kw):
        raise AssertionError("a frame program in a check with no frame")

    monkeypatch.setattr(bodies, "ptt_ckpt_fetch", never)
    monkeypatch.setattr(bodies, "ptt_restore_pad", never)
    ck = _mk()
    r = ck.run()
    st = ck.last_stats
    assert r.distinct_states == 1654
    assert st["host_ckpt_s"] < 0.01
    assert [st[k] for k in FRAME_KEYS] == [0] * len(FRAME_KEYS)
    assert not [k for k in st if k.startswith(("restore_", "resume_"))]


def test_a_checker_run_again_does_not_keep_the_resume_keys(tmp_path):
    path = str(tmp_path / "f.npz")
    ck = _mk(checkpoint_path=path, checkpoint_every=3)
    ck.run()
    ck.run(resume=True)
    assert ck.last_stats["resume_level"] > 0
    ck.run()
    assert not [k for k in ck.last_stats
                if k.startswith(("restore_", "resume_"))]


def test_cli_without_recover_prints_no_recovered_line(tmp_path):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.main(["check", SPEC, "-config", CFG, "-checkpoint",
                       str(tmp_path / "f.npz")])
    assert rc == 0 and "Recovered" not in out.getvalue()
    assert cli.recovered_line(
        {"resume_level": 18, "resume_states": 2402570,
         "resume_levels_run": 6}
    ) == ("Recovered from the checkpoint frame of level 18 (2402570 "
          "states): 6 levels expanded after it.")


def test_the_unarmed_kill_sends_nothing():
    """With no watcher armed the signal would end the process: the
    cluster manager's stream sends nothing then, and says so."""
    assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL
    err = KillAtLevel(3, signal.SIGTERM)
    print("  level 3: +5 (total 9, 100 st/s)", file=err)
    assert err.unarmed and err.sent_at is None
