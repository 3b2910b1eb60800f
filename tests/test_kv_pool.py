"""``ops/kv_pool.py`` on its own: the pool's layout, the bounded decode
attention, the row write, the prompt-block insert and the prefill attention,
each against plain numpy over a tiny pool, in both cache dtypes served
(float32: ``seqformer-lm``; bfloat16: ``olmoe``). The families' own tests
(``test_decode.py``, ``test_decode_bound.py``, ``test_olmoe.py``) hold the
same functions through the models; a change of the pool's layout or of its
read is written against these.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai4e_tpu.ops import kv_pool

LAYERS, SLOTS, HEADS, MAX_LEN, HEAD_DIM = 2, 4, 2, 16, 8
# float32: the order of a sum. bfloat16: the weights and the output are
# rounded to the cache's dtype (2^-8 each) — the tolerance
# ``test_decode_bound.py`` holds the bfloat16 family to.
TOLERANCE = {"float32": 1e-5, "bfloat16": 0.04}


class Pool:
    """A pool of random keys and values (a step reads whatever lies below a
    slot's position) and one step's new rows, in ``dtype``; ``f32`` gives
    any of them as float32 numpy, the values the device holds."""

    def __init__(self, dtype):
        self.dtype = jnp.dtype(dtype)
        self.tol = TOLERANCE[dtype]
        self.shape = kv_pool.pool_shape((LAYERS, HEADS, HEAD_DIM), SLOTS,
                                        MAX_LEN)
        rng = np.random.default_rng(29)
        self.k = jnp.asarray(rng.standard_normal(self.shape), self.dtype)
        self.v = jnp.asarray(rng.standard_normal(self.shape), self.dtype)
        row = (SLOTS, HEADS, HEAD_DIM)
        self.q, self.k_new, self.v_new = (
            jnp.asarray(rng.standard_normal(row), self.dtype)
            for _ in range(3))
        self.rng = rng

    @staticmethod
    def f32(x):
        return np.asarray(x, np.float32)


def softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def plain_decode_attention(pool, layer, position):
    """softmax over [cached keys ``< position``, the new key], a slot and a
    head at a time."""
    f32 = pool.f32
    out = np.zeros((SLOTS, HEADS, HEAD_DIM), np.float32)
    for s, p in enumerate(position):
        for h in range(HEADS):
            keys = np.concatenate([f32(pool.k)[layer, s, h, :p],
                                   f32(pool.k_new)[s, h][None]])
            values = np.concatenate([f32(pool.v)[layer, s, h, :p],
                                     f32(pool.v_new)[s, h][None]])
            w = softmax(keys @ f32(pool.q)[s, h] / np.sqrt(HEAD_DIM))
            out[s, h] = w @ values
    return out


def attend(pool, layer, position, bound=None):
    out = kv_pool.decode_attention(
        pool.q, pool.k_new, pool.v_new, pool.k, pool.v, layer,
        jnp.asarray(position, jnp.int32), bound)
    assert out.dtype == pool.dtype and out.shape == pool.q.shape
    return pool.f32(out)


def shape_and_allocation(pool):
    assert pool.shape == (LAYERS, SLOTS, HEADS, MAX_LEN, HEAD_DIM)
    clean = kv_pool.allocate(pool.shape, pool.dtype)
    assert clean.shape == pool.shape and clean.dtype == pool.dtype
    assert not pool.f32(clean).any()


def attention_is_a_softmax_over_the_cached_keys_and_the_new_one(pool):
    """Mixed positions, among them 0 (the new key alone: its value comes
    back), the bound itself and the whole length."""
    bound = 8
    for layer, position, cut in [(0, (0, 3, bound, 5), bound),
                                 (1, (MAX_LEN, 0, 1, 9), None)]:
        got = attend(pool, layer, position, cut)
        want = plain_decode_attention(pool, layer, position)
        np.testing.assert_allclose(got, want, rtol=0, atol=pool.tol)
        alone = position.index(0)
        np.testing.assert_allclose(got[alone], pool.f32(pool.v_new)[alone],
                                   rtol=0, atol=pool.tol)


def a_bound_over_every_live_position_reads_what_the_whole_length_reads(pool):
    position = (0, 7, 4, 8)
    whole = attend(pool, 1, position)
    for bound in (8, 12, MAX_LEN):
        np.testing.assert_allclose(attend(pool, 1, position, bound), whole,
                                   rtol=0, atol=pool.tol)
    # and a bound under a live position does not (so the above can fail):
    # the slot at 8 loses keys 4..7, whose values are made large.
    large = pool.v.at[:, :, :, 4:].multiply(8)
    pool.v = large
    cut, kept = attend(pool, 1, position, 4), attend(pool, 1, position)
    assert np.abs(cut[3] - kept[3]).max() > 10 * pool.tol
    np.testing.assert_allclose(cut[[0, 2]], kept[[0, 2]], rtol=0,
                               atol=pool.tol)


def the_row_write_changes_one_row_a_slot_and_a_layer(pool):
    """At ``position[slot]``; a position past the end lands on the last
    row."""
    position = (0, 5, MAX_LEN - 1, MAX_LEN + 7)
    row = (SLOTS, HEADS, HEAD_DIM)
    k_rows = [jnp.asarray(pool.rng.standard_normal(row), pool.dtype)
              for _ in range(LAYERS)]
    v_rows = [jnp.asarray(pool.rng.standard_normal(row), pool.dtype)
              for _ in range(LAYERS)]
    k, v = jax.jit(kv_pool.write_rows)(pool.k, pool.v, k_rows, v_rows,
                                       jnp.asarray(position, jnp.int32))
    for got, before, rows in ((k, pool.k, k_rows), (v, pool.v, v_rows)):
        assert got.shape == pool.shape and got.dtype == pool.dtype
        want = pool.f32(before).copy()
        for layer in range(LAYERS):
            for slot, p in enumerate(position):
                want[layer, slot, :, min(p, MAX_LEN - 1)] = pool.f32(
                    rows[layer])[slot]
        assert (pool.f32(got) == want).all()
        changed = (pool.f32(got) != pool.f32(before)).any(axis=(2, 4))
        assert changed.sum() == LAYERS * SLOTS      # one row each, no more


def the_insert_lands_a_block_in_its_slot_and_touches_no_other(pool):
    prompt, slot = 6, 2
    rows = (1, prompt, HEADS, HEAD_DIM)     # as prefill_attention reads them
    ks = [jnp.asarray(pool.rng.standard_normal(rows), pool.dtype)
          for _ in range(LAYERS)]
    vs = [jnp.asarray(pool.rng.standard_normal(rows), pool.dtype)
          for _ in range(LAYERS)]
    k_block, v_block = kv_pool.prompt_block(ks), kv_pool.prompt_block(vs)
    assert k_block.shape == (LAYERS, 1, HEADS, prompt, HEAD_DIM)
    k, v = jax.jit(kv_pool.insert_block)(pool.k, pool.v, k_block, v_block,
                                         jnp.int32(slot))
    for got, before, per_layer in ((k, pool.k, ks), (v, pool.v, vs)):
        want = pool.f32(before).copy()
        for layer in range(LAYERS):
            # position p, head h of the prompt → [layer, slot, h, p]
            want[layer, slot, :, :prompt] = pool.f32(
                per_layer[layer])[0].transpose(1, 0, 2)
        assert (pool.f32(got) == want).all()


def prefill_attention_is_a_causal_softmax_over_the_real_tokens(pool):
    batch, prompt = 2, 6
    length = (prompt, 3)
    shape = (batch, prompt, HEADS, HEAD_DIM)
    q, k, v = (jnp.asarray(pool.rng.standard_normal(shape), pool.dtype)
               for _ in range(3))
    mask = jnp.arange(prompt)[None, :] < jnp.asarray(length)[:, None]
    got = kv_pool.prefill_attention(q, k, v, mask)
    assert got.shape == shape and got.dtype == pool.dtype
    f32 = pool.f32
    for b in range(batch):
        for i in range(length[b]):          # a padded row is never read
            for h in range(HEADS):
                w = softmax(f32(k)[b, :i + 1, h] @ f32(q)[b, i, h]
                            / np.sqrt(HEAD_DIM))
                np.testing.assert_allclose(
                    f32(got)[b, i, h], w @ f32(v)[b, :i + 1, h], rtol=0,
                    atol=pool.tol)


CASES = [
    shape_and_allocation,
    attention_is_a_softmax_over_the_cached_keys_and_the_new_one,
    a_bound_over_every_live_position_reads_what_the_whole_length_reads,
    the_row_write_changes_one_row_a_slot_and_a_layer,
    the_insert_lands_a_block_in_its_slot_and_touches_no_other,
    prefill_attention_is_a_causal_softmax_over_the_real_tokens,
]


@pytest.mark.parametrize("dtype", list(TOLERANCE))
@pytest.mark.parametrize("case", CASES, ids=lambda case: case.__name__)
def test_kv_pool(case, dtype):
    case(Pool(dtype))
