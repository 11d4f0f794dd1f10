"""Per-action and per-invariant differential tests vs the Python oracle
(SURVEY.md §4d): every successor lane and every invariant verdict must agree
on a depth-spread sample of reachable states."""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pulsar_tlaplus_tpu.models.compaction import CompactionModel
from pulsar_tlaplus_tpu.ref import pyeval as pe
from pulsar_tlaplus_tpu.utils import cfg as tlc_cfg
from tests.helpers import SMALL_CONFIGS, SPECS, oracle_sample


def _batch(m, sample):
    return jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
        *[m.from_pystate(s) for s in sample],
    )


@pytest.mark.parametrize("name", sorted(SMALL_CONFIGS))
def test_successors_match_oracle(name):
    c = SMALL_CONFIGS[name]
    m = CompactionModel(c)
    sample = oracle_sample(c, n_states=100, seed=2)
    batch = _batch(m, sample)
    succs, valid = jax.jit(jax.vmap(m.successors))(batch)
    valid = np.asarray(valid)
    for i, s in enumerate(sample):
        want = {}
        for a, t in pe.successors(c, s):
            if a <= 7:  # non-stuttering lanes
                want.setdefault(a, []).append(t)
        got = {}
        for lane in range(m.A):
            if valid[i, lane]:
                st = jax.tree.map(lambda x: np.asarray(x)[i, lane], succs)
                got.setdefault(int(m.action_ids[lane]), []).append(
                    m.to_pystate(st)
                )
        assert {k: sorted(v) for k, v in want.items()} == {
            k: sorted(v) for k, v in got.items()
        }, f"state {s}"


@pytest.mark.parametrize("name", sorted(SMALL_CONFIGS))
def test_invariants_match_oracle(name):
    c = SMALL_CONFIGS[name]
    m = CompactionModel(c)
    sample = oracle_sample(c, n_states=100, seed=3)
    batch = _batch(m, sample)
    pairs = [
        ("TypeSafe", pe.type_safe),
        ("CompactedLedgerLeak", pe.compacted_ledger_leak),
        ("CompactionHorizonCorrectness", pe.compaction_horizon_correctness),
        ("DuplicateNullKeyMessage", pe.duplicate_null_key_message),
    ]
    for inv_name, pfn in pairs:
        got = np.asarray(jax.jit(jax.vmap(m.invariants[inv_name]))(batch))
        want = np.array([pfn(c, s) for s in sample])
        assert (got == want).all(), inv_name


@pytest.mark.parametrize("name", sorted(SMALL_CONFIGS))
def test_stutter_enabledness_match_oracle(name):
    c = SMALL_CONFIGS[name]
    m = CompactionModel(c)
    sample = oracle_sample(c, n_states=100, seed=4)
    batch = _batch(m, sample)
    got = np.asarray(jax.jit(jax.vmap(m.stutter_enabled))(batch))
    for i, s in enumerate(sample):
        want = any(a in (8, 9) for a, _ in pe.successors(c, s))
        assert bool(got[i]) == want, s


# ---------------------------------------------------------------------
# The context's ledger, compactedLedgers[compactedTopicContext]: read by
# a one-hot select over the C slots.  Held to the per-state gather it
# replaced (written out here) and to the oracle's ledger, on the two
# bindings whose masks differ in width.
# ---------------------------------------------------------------------

LEDGER_BINDINGS = {"compaction.cfg": 1, "compaction_scaled.cfg": 2}  # -> MW


def _binding(cfg_name):
    c = tlc_cfg.to_constants(tlc_cfg.load(os.path.join(SPECS, cfg_name)))
    m = CompactionModel(c)
    assert m.MW == LEDGER_BINDINGS[cfg_name]
    return c, m


def _gather_ledger_bits(m, s):
    """The read as it was: two gathers by the state's own slot."""
    slot = jnp.clip(s.context - 1, 0, m.C - 1)
    words = s.led_mask[slot]
    present = (s.context >= 1) & (
        jnp.take(s.led_present, slot, axis=0) == 1
    )
    return m._mask_bits(words) & present


def _both_forms(m, batch):
    """(the model's bits, the gather form's) over a batch of states."""
    return tuple(
        np.asarray(jax.jit(jax.vmap(fn))(batch))
        for fn in (m._context_ledger_bits, lambda s: _gather_ledger_bits(m, s))
    )


def _oracle_ledger_bits(m, ps):
    """bool[M] of the positions pyeval's context ledger holds."""
    ledger = ps.ledgers[ps.context - 1] if ps.context >= 1 else None
    bits = np.zeros((m.M,), bool)
    for mid, _k, _v in ledger or ():
        bits[mid - 1] = True
    return bits


def _walk_sample(c, n_walks, depth, seed):
    """Reachable states along random behaviours (the scaled binding's
    levels are too wide for a breadth-first sample to reach a second
    mask word)."""
    rng = random.Random(seed)
    inits = list(pe.initial_states(c))
    out = {}
    for _ in range(n_walks):
        s = rng.choice(inits)
        for _ in range(depth):
            out[s] = None
            succ = [t for _a, t in pe.successors(c, s)]
            if not succ:
                break
            s = rng.choice(succ)
    return list(out)


def _full_state(c, m, context, present, kept):
    """A hand-made state with every message sent: ``present`` the slots
    that hold a ledger, each of the positions ``kept``."""
    messages = tuple(
        (i, 1 + i % c.num_keys, 1 + i % c.num_values)
        for i in range(1, m.M + 1)
    )
    ledger = tuple(messages[p - 1] for p in kept)
    return next(pe.initial_states(c))._replace(
        messages=messages,
        ledgers=tuple(ledger if cc in present else None for cc in range(m.C)),
        context=context,
        horizon=m.M,
    )


def _ledger_cases(c, m, kind):
    every = range(m.C)
    if kind == "reachable":
        return _walk_sample(c, n_walks=12, depth=6 * m.M + 40, seed=5)
    if kind == "context_zero":
        return [_full_state(c, m, 0, every, [1, m.M])]
    if kind == "nil_slot":
        return [
            _full_state(c, m, ctx, [cc for cc in every if cc != ctx - 1], [1, m.M])
            for ctx in range(1, m.C + 1)
        ]
    if kind == "every_slot_present":
        return [
            _full_state(c, m, ctx, every, [ctx, m.M - ctx])
            for ctx in range(1, m.C + 1)
        ]
    if kind == "last_word":
        last = range(32 * (m.MW - 1) + 1, m.M + 1)
        return [
            _full_state(c, m, ctx, [ctx - 1], list(last))
            for ctx in range(1, m.C + 1)
        ]
    raise AssertionError(kind)


@pytest.mark.parametrize(
    "kind",
    ["reachable", "context_zero", "nil_slot", "every_slot_present", "last_word"],
)
@pytest.mark.parametrize("cfg_name", sorted(LEDGER_BINDINGS))
def test_context_ledger_bits_match_gather_and_oracle(cfg_name, kind):
    c, m = _binding(cfg_name)
    sample = _ledger_cases(c, m, kind)
    got, gathered = _both_forms(m, _batch(m, sample))
    want = np.stack([_oracle_ledger_bits(m, ps) for ps in sample])
    assert (got == gathered).all()
    assert (got == want).all()
    if kind not in ("context_zero", "nil_slot"):
        assert got.any(), "the sample never reads a kept position"
    if kind in ("reachable", "last_word") and m.MW > 1:
        assert got[:, 32 * (m.MW - 1):].any(), "no bit of the last word read"


@pytest.mark.parametrize("cfg_name", sorted(LEDGER_BINDINGS))
def test_context_ledger_bits_on_unreachable_states(cfg_name):
    """The same bool[M] for EVERY state: slots that hold bits but no
    ledger, a context past either end (the clip)."""
    c, m = _binding(cfg_name)
    rng = np.random.default_rng(7)
    n = 256
    base = _batch(m, [next(pe.initial_states(c))] * n)
    batch = base._replace(
        context=jnp.asarray(rng.integers(-1, m.C + 2, n), jnp.int32),
        led_present=jnp.asarray(rng.integers(0, 2, (n, m.C)), jnp.int32),
        led_mask=jnp.asarray(
            rng.integers(0, 1 << 32, (n, m.C, m.MW), dtype=np.uint64),
            jnp.uint32,
        ),
    )
    got, gathered = _both_forms(m, batch)
    assert (got == gathered).all()
    assert got.any() and not got.all()


@pytest.mark.parametrize("cfg_name", sorted(LEDGER_BINDINGS))
def test_context_ledger_bits_lowers_to_no_per_state_gather(cfg_name):
    """No more gathers than ``_mask_bits`` alone lowers to (its index is
    a constant): an index that is a value of the state cannot come back
    unseen."""
    c, m = _binding(cfg_name)
    batch = _batch(m, [next(pe.initial_states(c))] * 4)

    def gathers(fn, arg):
        return jax.jit(jax.vmap(fn)).lower(arg).as_text().count("gather")

    allowed = gathers(m._mask_bits, batch.led_mask[:, 0])
    assert gathers(m._context_ledger_bits, batch) <= allowed
    # the comparison can tell: the form it replaced holds more
    assert gathers(lambda s: _gather_ledger_bits(m, s), batch) > allowed
