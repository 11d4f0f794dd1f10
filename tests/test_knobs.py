"""A check's knobs have one source (ISSUE 48): a checker's shapes and
schedules come from its constructor's arguments and from module
constants.  No file, no environment variable and no controller inside
``run()`` sets them.

- the schedule knobs change cost, never the search: discovery order
  state for state on both published bug oracles and the exact count,
  diameter, level sizes, rows and logs of one complete binding, over
  explicit assignments of ``sub_batch``, ``flush_factor``, ``group``
  and ``fuse_group`` (what the tuner's differential held);
- ``ckpt.model_sig`` is the string a frame is held to: pinned for every
  shipped binding and one compiled spec, and a frame whose signature is
  the one the parent of PR 48 wrote restores;
- a checker's constructor parameters are pinned by name, so the next
  one is a visible act;
- ``engine/``, ``ops/`` and ``store/`` read no environment variable
  outside an allow-list, and a former profile in ``$HOME`` shapes
  nothing;
- the CLI's help names no tuner flag or subcommand.
"""

import ast
import functools
import hashlib
import inspect
import json
import os
import re

import numpy as np
import pytest

from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker
from pulsar_tlaplus_tpu.engine.sharded_device import ShardedDeviceChecker
from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ops import fpset
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.utils import ckpt
from tests.helpers import SMALL_CONFIGS, SPECS, assert_valid_counterexample

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "pulsar_tlaplus_tpu")


# ---- the schedule knobs never change the search ----------------------

# (sub_batch, flush_factor, group, fuse_group); the first is the
# reference the others are held to
ASSIGNMENTS = [
    (2048, 1, 4, None),
    (64, 4, 8, 3),
    (128, 1, 4, None),
    (128, 4, 2, 1),
    (256, 2, 2, 2),
    (256, 1, 8, 8),
    (512, 2, 2, 2),
    (512, 1, 1, 1),
    (512, 3, 4, 4),
    (1024, 2, 4, None),
    (1024, 1, 2, 1),
    (2048, 1, 4, 2),
    (2048, 2, 1, None),
]
TARGETS = {
    # target: (constants, invariants, published depth, initial tiers)
    "leak": (pe.SHIPPED_CFG, ("CompactedLedgerLeak",), 12, 1 << 15),
    "dup_null_key": (
        pe.SHIPPED_CFG, ("DuplicateNullKeyMessage",), 4, 1 << 15
    ),
    "complete": (SMALL_CONFIGS["producer_on"], None, None, 1 << 12),
}


@functools.lru_cache(maxsize=None)
def _searched(target, assignment):
    """One run of ``target`` under ``assignment``: what the search
    found, in the order it found it."""
    c, invariants, _, tier = TARGETS[target]
    sub_batch, flush_factor, group, fuse_group = assignment
    kw = {} if invariants is None else {"invariants": invariants}
    ck = DeviceChecker(
        CompactionModel(c), sub_batch=sub_batch,
        flush_factor=flush_factor, group=group, fuse_group=fuse_group,
        visited_cap=tier, frontier_cap=tier, **kw,
    )
    assert (ck.G, ck.FLUSH, ck.group) == (sub_batch, flush_factor, group)
    assert ck.RMAX == (fuse_group or 8)
    r = ck.run()
    found = dict(
        violation=r.violation, violation_gid=r.violation_gid,
        diameter=r.diameter, trace=r.trace,
        trace_actions=r.trace_actions,
        distinct_states=r.distinct_states,
        level_sizes=list(r.level_sizes), truncated=r.truncated,
    )
    if invariants is None:
        nv = r.distinct_states
        found["parent"] = np.asarray(ck.last_bufs["parent"][:nv])
        found["lane"] = np.asarray(ck.last_bufs["lane"][:nv])
        found["rows"] = np.asarray(ck.last_bufs["rows"][: nv * ck.W])
    ck.last_bufs = None
    return found


@pytest.mark.parametrize(
    "assignment", ASSIGNMENTS[1:],
    ids=lambda a: "-".join(str(x) for x in a),
)
@pytest.mark.parametrize("target", sorted(TARGETS))
def test_schedule_knobs_leave_the_search_state_for_state(
    target, assignment
):
    c, invariants, depth, _ = TARGETS[target]
    want = _searched(target, ASSIGNMENTS[0])
    got = _searched(target, assignment)
    for key, value in want.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(got[key], value), key
        else:
            assert got[key] == value, key
    if invariants is None:
        assert not got["truncated"] and got["violation"] is None
        ref = pe.check(c, invariants=())
        assert got["distinct_states"] == ref.distinct_states == 1654
        assert got["diameter"] == ref.diameter
        assert sum(got["level_sizes"]) == 1654
    else:
        assert got["violation"] == invariants[0]
        assert got["diameter"] == len(got["trace"]) == depth
        assert_valid_counterexample(
            c, got["trace"], got["trace_actions"], invariants[0]
        )


# ---- the identity a frame is held to ---------------------------------

_SHIPPED_SIGS = {
    "compaction": (
        "Constants(message_sent_limit=3, compaction_times_limit=3, "
        "model_consumer=False, consume_times_limit=2, num_keys=2, "
        "num_values=2, retain_null_key=True, max_crash_times=1, "
        "model_producer=False)"
    ),
    "compaction_253k": (
        "Constants(message_sent_limit=3, compaction_times_limit=3, "
        "model_consumer=False, consume_times_limit=2, num_keys=2, "
        "num_values=2, retain_null_key=False, max_crash_times=1, "
        "model_producer=True)"
    ),
    "compaction_1m": (
        "Constants(message_sent_limit=3, compaction_times_limit=3, "
        "model_consumer=False, consume_times_limit=2, num_keys=3, "
        "num_values=2, retain_null_key=True, max_crash_times=2, "
        "model_producer=True)"
    ),
    "compaction_4m": (
        "Constants(message_sent_limit=4, compaction_times_limit=3, "
        "model_consumer=False, consume_times_limit=2, num_keys=2, "
        "num_values=2, retain_null_key=False, max_crash_times=1, "
        "model_producer=True)"
    ),
    "compaction_9m": (
        "Constants(message_sent_limit=4, compaction_times_limit=3, "
        "model_consumer=False, consume_times_limit=2, num_keys=2, "
        "num_values=2, retain_null_key=True, max_crash_times=2, "
        "model_producer=True)"
    ),
}
_COMPILED_SUBSCRIPTION_SIG = (
    "('subscription', [('MaxCrashTimes', '2'), ('MessageLimit', '3')], "
    "('Publish', 'Deliver', 'Deliver', 'Deliver', 'Process', 'Process', "
    "'Process', 'SendAck', 'SendAck', 'SendAck', 'AdvanceMarkDelete', "
    "'ConsumerCrash', 'Terminating'))"
)
# DeviceChecker._config_sig() of SMALL_CONFIGS["producer_on"] as the
# parent of PR 48 (e9ecad8) wrote it into its frames
_PARENT_FRAME_SIG = (
    "(('check_deadlock', 'True'), ('engine', \"'device_bfs_r7'\"), "
    "('invariants', \"('TypeSafe', 'CompactionHorizonCorrectness')\"), "
    "('key_cols', '2'), ('key_exact', 'True'), ('model', "
    "\"'Constants(message_sent_limit=2, compaction_times_limit=2, "
    "model_consumer=False, consume_times_limit=2, num_keys=1, "
    "num_values=1, retain_null_key=True, max_crash_times=1, "
    "model_producer=True)'\"), ('rows_window', \"'all'\"), "
    "('state_bits', '28'), ('visited_impl', \"'fpset'\"))"
)


@pytest.mark.parametrize("binding", sorted(_SHIPPED_SIGS))
def test_model_sig_of_a_shipped_binding_is_pinned(binding):
    from pulsar_tlaplus_tpu.models import registry
    from pulsar_tlaplus_tpu.utils import cfg as cfgmod

    model, _ = registry.COMPILED["compaction"](
        cfgmod.load(os.path.join(SPECS, f"{binding}.cfg"))
    )
    assert ckpt.model_sig(model) == _SHIPPED_SIGS[binding]
    # and it is the string the engine's frame signature carries
    assert repr(_SHIPPED_SIGS[binding]) in DeviceChecker(
        model
    )._config_sig()


def test_model_sig_of_a_compiled_spec_is_pinned():
    from pulsar_tlaplus_tpu.frontend import interp
    from pulsar_tlaplus_tpu.frontend.codegen import CompiledSpec
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.frontend.parser import parse_file
    from pulsar_tlaplus_tpu.utils.cfg import parse_cfg

    mod = parse_file(os.path.join(SPECS, "subscription.tla"))
    with open(os.path.join(SPECS, "subscription.cfg")) as f:
        consts = bind_cfg(mod, parse_cfg(f.read()))
    consts.pop("__string_interning__", None)
    cs = CompiledSpec(interp.Spec(mod, consts), invariants=())
    assert ckpt.model_sig(cs) == _COMPILED_SUBSCRIPTION_SIG


def test_a_frame_of_the_parents_signature_restores(tmp_path):
    """The frame a checker writes carries the signature the parent
    wrote, byte for byte, and a second checker recovers from it to
    ``truncated: false`` with the exact count."""
    frame = str(tmp_path / "run.npz")
    kw = dict(
        sub_batch=256, visited_cap=1 << 8, frontier_cap=1 << 12,
        checkpoint_path=frame,
    )
    m = CompactionModel(SMALL_CONFIGS["producer_on"])
    first = DeviceChecker(m, checkpoint_every=3, **kw)
    assert first._config_sig() == _PARENT_FRAME_SIG
    whole = first.run()
    with np.load(frame) as d:
        assert d["sig"].tobytes().decode() == _PARENT_FRAME_SIG
        at = ckpt.frame_meta(d)["level"]
    assert 0 < at < len(whole.level_sizes)
    ckpt.load_frame(frame, _PARENT_FRAME_SIG)
    r = DeviceChecker(m, **kw).run(resume=True)
    assert not r.truncated and r.distinct_states == 1654
    assert list(r.level_sizes) == list(whole.level_sizes)


# ---- a constructor parameter is a visible act ------------------------

_PARAMETERS = {
    DeviceChecker: (
        "model invariants check_deadlock sub_batch expand_chunk "
        "visited_cap frontier_cap max_states time_budget_s progress "
        "metrics_path group flush_factor fp_bits append_chunk seed_cap "
        "rows_window row_cap_states fuse fuse_group fpset_dense_rounds "
        "fpset_stages hbm_budget spill_dir spill_compress "
        "checkpoint_path checkpoint_every telemetry heartbeat_s "
        "xprof_dir xprof_levels suspend_hook"
    ),
    ShardedDeviceChecker: (
        "model n_devices invariants check_deadlock sub_batch "
        "expand_chunk visited_cap max_states time_budget_s progress "
        "metrics_path group flush_factor fp_bits route_slack "
        "append_chunk checkpoint_path checkpoint_every n_slices "
        "telemetry heartbeat_s"
    ),
    LivenessChecker: (
        "model goal fairness frontier_chunk visited_cap max_states "
        "sweep_chunk sweep_group hbm_budget spill_compress n_devices "
        "explorer_kw max_run checkpoint_path checkpoint_every "
        "telemetry heartbeat_s progress"
    ),
}


@pytest.mark.parametrize(
    "checker", list(_PARAMETERS), ids=lambda c: c.__name__
)
def test_constructor_parameters_are_pinned(checker):
    """A knob a caller can set is an argument here or a module constant
    (ROADMAP D3): whoever adds one edits this list too."""
    got = list(inspect.signature(checker.__init__).parameters)[1:]
    assert got == _PARAMETERS[checker].split()


# ---- nothing outside the call sets a knob ----------------------------

# what the three packages may read: fault injection, the budget's
# documented environment form, the stage-timing drain and the
# compaction's materialization (part of a program's key)
_ENV_ALLOWED = {
    "PTT_FAULT", "PTT_HBM_BUDGET", "PTT_STAGE_TIMING",
    "PTT_COMPACT_MATERIALIZE",
}


def _env_reads(path):
    """The names ``path`` reads from the environment; a read whose name
    is no literal counts as ``"?"``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    consts = {
        t.id: node.value.value
        for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and isinstance(node.value, ast.Constant)
        for t in node.targets
        if isinstance(t, ast.Name)
    }

    def name_of(arg):
        if isinstance(arg, ast.Constant):
            return arg.value
        if isinstance(arg, ast.Name):
            return consts.get(arg.id, "?")
        return "?"

    def is_environ(node):
        return isinstance(node, ast.Attribute) and node.attr == "environ"

    reads = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and (
                (fn.attr in ("get", "pop", "setdefault")
                 and is_environ(fn.value))
                or fn.attr == "getenv"
            ):
                reads.add(name_of(node.args[0]))
        elif isinstance(node, ast.Subscript) and is_environ(node.value):
            reads.add(name_of(node.slice))
        elif isinstance(node, ast.Compare) and any(
            is_environ(c) for c in node.comparators
        ):
            reads.add(name_of(node.left))
    return reads


def test_engine_ops_store_read_only_the_allowed_variables():
    found = {}
    for package in ("engine", "ops", "store"):
        for dirpath, _, files in os.walk(os.path.join(PACKAGE, package)):
            for name in files:
                if name.endswith(".py"):
                    path = os.path.join(dirpath, name)
                    reads = _env_reads(path)
                    if reads:
                        found[os.path.relpath(path, PACKAGE)] = reads
    assert set().union(*found.values()) <= _ENV_ALLOWED, found
    # the scan sees the reads that are there
    assert found["ops/compact.py"] == {"PTT_COMPACT_MATERIALIZE"}
    assert found["store/budget.py"] == {"PTT_HBM_BUDGET"}
    assert "PTT_STAGE_TIMING" in found["engine/device_bfs.py"]


# SMALL_CONFIGS["producer_on"] as a .cfg
_PRODUCER_ON_CFG = """
CONSTANTS
    MessageSentLimit = 2
    CompactionTimesLimit = 2
    ModelConsumer = FALSE
    ConsumeTimesLimit = 2
    KeySpace = {1}
    ValueSpace = {1}
    RetainNullKey = TRUE
    MaxCrashTimes = 1
    ModelProducer = TRUE
SPECIFICATION Spec
INVARIANTS
    TypeSafe
"""


def test_a_former_profile_in_home_shapes_nothing(
    tmp_path, monkeypatch, capsys
):
    """A well-formed profile of the tuner's time, under the key it
    would have been looked up by, in ``$HOME/.ptt_profiles`` and in
    ``PTT_TUNE_DIR``, with the controller's and the ladder's variables
    set: the check builds the shapes its command line gives it."""
    from pulsar_tlaplus_tpu import cli

    cfg = str(tmp_path / "small.cfg")
    with open(cfg, "w") as f:
        f.write(_PRODUCER_ON_CFG)
    invariants = ("TypeSafe",)
    model = CompactionModel(SMALL_CONFIGS["producer_on"])
    sig = hashlib.sha1(
        repr(
            ("device_bfs", ckpt.model_sig(model), invariants, "cpu")
        ).encode()
    ).hexdigest()[:16]
    profile = {
        "profile_v": 1, "sig": sig, "engine": "device_bfs",
        "backend": "cpu", "spec": "compaction", "created_unix": 0.0,
        "knobs": {
            "sub_batch": 512, "flush_factor": 2, "group": 2,
            "fuse_group": 2, "fpset_dense_rounds": 2, "adapt": True,
        },
        "tuner": {},
    }
    home = tmp_path / "home"
    for where in (home / ".ptt_profiles", tmp_path / "tuned"):
        where.mkdir(parents=True)
        (where / f"{sig}.json").write_text(json.dumps(profile))
    monkeypatch.setenv("HOME", str(home))
    monkeypatch.setenv("PTT_TUNE_DIR", str(tmp_path / "tuned"))
    monkeypatch.setenv("PTT_TUNE_ADAPT", "1")
    monkeypatch.setenv("PTT_FPSET_SCHEDULE", "2,8:32")
    ck = DeviceChecker(model, invariants=invariants)
    assert (ck.G, ck.FLUSH, ck.group, ck.RMAX) == (8192, 1, 4, 8)
    assert (ck.fps_dense, ck.fps_stages) == (
        fpset.DENSE_ROUNDS, fpset.STAGES
    )
    stream = str(tmp_path / "run.jsonl")
    rc = cli.main([
        "check", os.path.join(SPECS, "compaction.tla"), "-config", cfg,
        "-cpu", "-telemetry", stream,
    ])
    out, err = capsys.readouterr()
    assert rc == 0 and "1654 distinct states found" in out
    assert "tuned profile" not in out + err
    with open(stream) as f:
        events = [json.loads(x) for x in f]
    head = events[0]
    assert head["event"] == "run_header"
    assert (head["sub_batch"], head["flush_factor"]) == (4096, 1)
    assert head["fuse_group"] == 8 and head["profile_sig"] is None
    assert "adapt" not in head
    assert not [e for e in events if e["event"] == "tune"]


# ---- the CLI names no tuner ------------------------------------------


@pytest.mark.parametrize("argv", [["-h"], ["check", "-h"]], ids=" ".join)
def test_help_names_no_tuner_flag_or_subcommand(argv, capsys):
    from pulsar_tlaplus_tpu import cli

    with pytest.raises(SystemExit) as ei:
        cli.main(argv)
    assert ei.value.code == 0
    text = capsys.readouterr().out
    assert "-fuse-group" in text or "simulate" in text
    assert not re.search(
        r"\btune\b|-no-profile|-adapt\b|-no-adapt|PTT_TUNE|tuned profile",
        text,
    ), text
