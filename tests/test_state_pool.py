"""The state pool's step (``ops/state_pool.py`` ``update_live`` and its
kernel, ``ops/pallas/state_update.py``, in the interpreter): the live plan,
each hybrid family's recurrence at the live slots against its dense
``jax.numpy`` statement for several live patterns, a dead slot's state bit
for bit what it was, the bytes a step counts, the position-0 convention at
``launch``, and a dead slot's state through the runtime.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ai4e_tpu.models import granite_hybrid, qwen3_next  # noqa: E402
from ai4e_tpu.ops import state_pool  # noqa: E402
from ai4e_tpu.ops.pallas import state_update  # noqa: E402
from ai4e_tpu.runtime.kvcache import (  # noqa: E402
    PagedDecodeRuntime, build_lm_servable)

SLOTS = 6
# position a slot: none live, one, the last slot only, every slot, alternating
PATTERNS = {"none": [0] * 6, "one": [4, 0, 0, 0, 0, 0],
            "last_only": [0, 0, 0, 0, 0, 3], "every": [1, 2, 3, 4, 5, 6],
            "alternating": [0, 5, 0, 2, 0, 9]}


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_live_plan_lists_the_live_slots_then_holds_the_last(pattern):
    position = np.asarray(PATTERNS[pattern], np.int32)
    plan = np.asarray(state_update.live_plan(jnp.asarray(position)))
    live = np.flatnonzero(position > 0)
    assert plan.dtype == np.int32 and plan.shape == (SLOTS + 2,)
    assert plan[-1] == len(live)
    assert plan[:len(live)].tolist() == live.tolist()
    # past the live count: the block already held — the last live slot's,
    # or slot 0's with nothing live
    held = live[-1] if len(live) else 0
    assert (plan[len(live):-1] == held).all()


def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def _ssd_case(rng):
    """Mamba-2 at 4 heads of 16 by a state of 16: ``(update, dense, the
    dense state, the rest, dense state -> the pool's tensor, and back)``.
    The pool holds a slot's ``S (H, P, N)`` as ``(N, H · P)``."""
    h, p, n = 4, 16, 16
    rest = (_normal(rng, SLOTS, h, p), abs(_normal(rng, SLOTS, h, scale=0.1)),
            -abs(_normal(rng, h, scale=4.0)), _normal(rng, SLOTS, n),
            _normal(rng, SLOTS, n))
    return (granite_hybrid.ssd_update, granite_hybrid.ssd_step,
            _normal(rng, SLOTS, h, p, n), rest,
            lambda s: jnp.moveaxis(s, 3, 1).reshape(SLOTS, n, h * p),
            lambda s: np.moveaxis(np.asarray(s).reshape(SLOTS, n, h, p), 1, 3))


def _delta_case(rng):
    """The gated delta rule at 4 heads of 16 x 16; the pool holds the state
    as the recurrence does."""
    h, d = 4, 16
    rest = (_normal(rng, SLOTS, h, d, scale=0.3),
            _normal(rng, SLOTS, h, d, scale=0.3), _normal(rng, SLOTS, h, d),
            -abs(_normal(rng, SLOTS, h, scale=0.1)),
            abs(_normal(rng, SLOTS, h, scale=0.5)))
    return (qwen3_next.delta_rule_update, qwen3_next.delta_rule_step,
            _normal(rng, SLOTS, h, d, d), rest, lambda s: s, np.asarray)


CASES = {"ssd": _ssd_case, "delta_rule": _delta_case}


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("family", sorted(CASES))
def test_update_live_is_the_dense_recurrence_at_the_live_slots(family,
                                                               pattern):
    """The live slots' read-out and successor are the family's dense
    ``jax.numpy`` recurrence's; a dead slot's state is bit for bit what it
    was and its read-out zero."""
    update, dense, state, rest, to_pool, from_pool = CASES[family](
        np.random.default_rng(len(pattern)))
    position = jnp.asarray(PATTERNS[pattern], jnp.int32)
    live = np.asarray(position) > 0
    out, new = jax.jit(update)(to_pool(state), *rest, position)
    want_out, want_new = dense(state, *rest)
    out, new = np.asarray(out).reshape(SLOTS, -1), from_pool(new)
    assert np.abs(out - np.asarray(want_out).reshape(SLOTS, -1))[live].max(
        initial=0) < 1e-5
    assert np.abs(new - np.asarray(want_new))[live].max(initial=0) < 1e-5
    assert np.array_equal(new[~live], np.asarray(state)[~live])
    assert not out[~live].any()
    if live.any():   # and it did advance something
        assert np.abs(new - np.asarray(state))[live].max() > 1e-3


@pytest.mark.parametrize("family", sorted(CASES))
def test_a_dead_slot_of_garbage_reaches_nothing(family):
    """A dead slot may hold anything — NaN included — and neither it nor
    its read-out reaches a live slot's numbers."""
    update, _, state, rest, to_pool, _ = CASES[family](
        np.random.default_rng(7))
    pooled = to_pool(state)
    position = jnp.asarray(PATTERNS["alternating"], jnp.int32)
    live = np.asarray(position) > 0
    clean = [np.asarray(r) for r in jax.jit(update)(pooled, *rest, position)]
    dead = jnp.asarray(~live).reshape(-1, *(1,) * (pooled.ndim - 1))
    dirty = [np.asarray(r) for r in jax.jit(update)(
        jnp.where(dead, jnp.nan, pooled), *rest, position)]
    for got, want in zip(dirty, clean):
        assert np.array_equal(got[live], want[live])
    assert not dirty[0][~live].any()          # the read-out: zeros
    assert np.isnan(dirty[1][~live]).all()    # the state: as it was


def test_slot_bytes_splits_what_a_step_moves_by_how_it_is_stepped():
    state = (("ssm0", (16, 64), jnp.float32), ("conv0", (3, 96), jnp.bfloat16),
             ("ssm1", (16, 64), jnp.float32), ("conv1", (3, 96), jnp.bfloat16))
    assert state_pool.slot_bytes(state, ("ssm0", "ssm1")) == (
        2 * 16 * 64 * 4, 2 * 3 * 96 * 2)
    assert state_pool.slot_bytes(state, ()) == (0, 2 * (4096 + 576))
    assert state_pool.slot_bytes((), ()) == (0, 0)
    assert sum(state_pool.slot_bytes(state, ("ssm1",))) * 5 == (
        state_pool.nbytes(state, 5))


GRANITE = dict(vocab_size=97, max_len=64, dim=64, depth=4,
               attention_layers=[2], heads=8, kv_heads=2, head_dim=16,
               mlp_dim=96, ssm_heads=4, ssm_head_dim=16, ssm_state=16, chunk=8)
QNEXT = dict(vocab_size=97, max_len=64, dim=64, depth=4, heads=4, kv_heads=2,
             head_dim=32, rotary_dim=8, lin_k_heads=2, lin_v_heads=4,
             lin_dim=16, experts=16, experts_held=8, experts_per_token=3,
             expert_dim=32, shared_dim=32)
SEQFORMER = dict(vocab_size=64, max_len=64, dim=32, depth=1, heads=2)
FAMILIES = {"granite-hybrid": GRANITE, "qwen3-next": QNEXT,
            "seqformer-lm": SEQFORMER}


def _runtime(family):
    runtime = PagedDecodeRuntime(build_lm_servable(family, **FAMILIES[family]),
                                 slots=3, prompt_buckets=(8,))
    runtime.warm()
    return runtime


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_launch_refuses_an_active_slot_at_position_0(family):
    """A slot is live to the device iff its position is > 0 (the K/V read
    and the state update skip a slot at 0): ``launch`` holds the engine to
    it, whatever the family."""
    runtime = _runtime(family)
    with pytest.raises(ValueError, match="position 0"):
        runtime.launch([1, 2, 3], [4, 0, 2], [True, True, False])
    # an inactive slot at 0 is the engine's own convention
    step = runtime.fetch(runtime.launch([1, 2, 3], [4, 0, 2],
                                        [True, False, True]))
    assert len(step.ids) == 3


@pytest.mark.parametrize("family", ["granite-hybrid", "qwen3-next"])
def test_steps_leave_a_dead_slots_state_as_it_was(family):
    """A sequence ends and its slot idles at position 0 while another
    decodes: every stepped-live tensor of the idle slot stays bit for bit
    what it was, the live slot's moves, and the counters count the live
    slot's bytes alone of those tensors."""
    runtime = _runtime(family)
    first = runtime.prefill_into(0, [5, 9, 12, 7])
    second = runtime.prefill_into(2, [3, 1, 4])
    live_names = runtime.servable.model.cache_spec().live
    assert live_names and set(live_names) < set(runtime._state)
    before = {name: np.asarray(t) for name, t in runtime._state.items()}
    token = first
    for position in range(4, 9):
        step = runtime.fetch(runtime.launch(
            [token, None, None], [position, 0, 0], [True, False, False]))
        token = step.ids[0]
    sparse, dense = runtime._state_slot_bytes
    assert step.state_bytes == {"moved": 2 * (sparse + 3 * dense),
                                "live": 2 * (sparse + dense)}
    assert step.cache_bytes["state"] == step.state_bytes["moved"]
    for name in live_names:
        after = np.asarray(runtime._state[name])
        assert np.array_equal(after[1:], before[name][1:]), name
        assert not np.array_equal(after[0], before[name][0]), name
    assert isinstance(second, int)
