"""``ops/kv_pool.py`` on its own: the pool's layout (a position is one row
of ``heads x head_dim``), the decode attention (a Pallas kernel that reads
each slot as far as it has written, here in the interpreter), the row write,
the prompt-block insert and the prefill attention, each against plain numpy
over a tiny pool, in both cache dtypes served (float32: ``seqformer-lm``;
bfloat16: ``olmoe``), and the kernel at both served row widths. The
families' own tests (``test_decode.py``, ``test_decode_bound.py``,
``test_olmoe.py``) hold the same functions through the models; a change of
the pool's layout or of its read is written against these.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai4e_tpu.ops import kv_pool
from ai4e_tpu.ops.pallas import decode_attention as kernel

LAYERS, SLOTS, HEADS, MAX_LEN, HEAD_DIM = 2, 4, 2, 16, 8
# float32: the order of a sum. bfloat16: the weights and the output are
# rounded to the cache's dtype (2^-8 each) — the tolerance
# ``test_decode_bound.py`` holds the bfloat16 family to.
TOLERANCE = {"float32": 1e-5, "bfloat16": 0.04}


class Pool:
    """A pool of random keys and values (a step reads whatever lies below a
    slot's position) and one step's new rows, in ``dtype``; ``f32`` gives
    any of them as float32 numpy, the values the device holds."""

    def __init__(self, dtype, layers=LAYERS, slots=SLOTS, heads=HEADS,
                 max_len=MAX_LEN, head_dim=HEAD_DIM):
        self.dtype = jnp.dtype(dtype)
        self.tol = TOLERANCE[dtype]
        self.layers, self.slots, self.heads = layers, slots, heads
        self.max_len, self.head_dim = max_len, head_dim
        self.shape = kv_pool.pool_shape(
            kv_pool.Rows("k", layers, heads * head_dim, self.dtype), slots,
            max_len)
        rng = np.random.default_rng(29)
        self.k = jnp.asarray(rng.standard_normal(self.shape), self.dtype)
        self.v = jnp.asarray(rng.standard_normal(self.shape), self.dtype)
        row = (slots, heads, head_dim)
        self.q, self.k_new, self.v_new = (
            jnp.asarray(rng.standard_normal(row), self.dtype)
            for _ in range(3))
        self.rng = rng

    @staticmethod
    def f32(x):
        return np.asarray(x, np.float32)

    def by_head(self, x):
        """A pool tensor as float32 (layers, slots, max_len, heads,
        head_dim): a row split back into its heads."""
        return self.f32(x).reshape(*x.shape[:3], self.heads, self.head_dim)


def softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def plain_decode_attention(pool, layer, position):
    """softmax over [cached keys ``< position``, the new key], a slot and a
    head at a time, in float32."""
    f32 = pool.f32
    k, v = pool.by_head(pool.k)[layer], pool.by_head(pool.v)[layer]
    out = np.zeros(pool.q.shape, np.float32)
    for s, p in enumerate(position):
        for h in range(pool.heads):
            keys = np.concatenate([k[s, :p, h], f32(pool.k_new)[s, h][None]])
            values = np.concatenate([v[s, :p, h],
                                     f32(pool.v_new)[s, h][None]])
            w = softmax(keys @ f32(pool.q)[s, h] / np.sqrt(pool.head_dim))
            out[s, h] = w @ values
    return out


def attend(pool, layer, position, bound=None):
    out = kv_pool.decode_attention(
        pool.q, pool.k_new, pool.v_new, pool.k, pool.v, layer,
        jnp.asarray(position, jnp.int32), bound)
    assert out.dtype == pool.dtype and out.shape == pool.q.shape
    return pool.f32(out)


def shape_and_allocation(pool):
    assert pool.shape == (LAYERS, SLOTS, MAX_LEN, HEADS * HEAD_DIM)
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
    large = pool.v.at[:, :, 4:].multiply(8)
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
    k, v = jax.jit(kv_pool.write_rows)((pool.k, pool.v), (k_rows, v_rows),
                                       jnp.asarray(position, jnp.int32))
    for got, before, rows in ((k, pool.k, k_rows), (v, pool.v, v_rows)):
        assert got.shape == pool.shape and got.dtype == pool.dtype
        want = pool.f32(before).copy()
        for layer in range(LAYERS):
            for slot, p in enumerate(position):
                want[layer, slot, min(p, MAX_LEN - 1)] = pool.f32(
                    rows[layer])[slot].reshape(-1)
        assert (pool.f32(got) == want).all()
        changed = pool.f32(got) != pool.f32(before)
        # layers x heads x head_dim elements a slot: one row each, no more
        assert (changed.sum(axis=(0, 2, 3))
                == LAYERS * HEADS * HEAD_DIM).all()
        assert changed.any(axis=3).sum() == LAYERS * SLOTS


def the_insert_lands_a_block_in_its_slot_and_touches_no_other(pool):
    prompt, slot = 6, 2
    rows = (1, prompt, HEADS, HEAD_DIM)     # as prefill_attention reads them
    ks = [jnp.asarray(pool.rng.standard_normal(rows), pool.dtype)
          for _ in range(LAYERS)]
    vs = [jnp.asarray(pool.rng.standard_normal(rows), pool.dtype)
          for _ in range(LAYERS)]
    k_block, v_block = kv_pool.prompt_block(ks), kv_pool.prompt_block(vs)
    assert k_block.shape == (LAYERS, 1, prompt, HEADS * HEAD_DIM)
    k, v = jax.jit(kv_pool.insert_block)((pool.k, pool.v), (k_block, v_block),
                                         jnp.int32(slot))
    for got, before, per_layer in ((k, pool.k, ks), (v, pool.v, vs)):
        want = pool.f32(before).copy()
        for layer in range(LAYERS):
            # position p, head h of the prompt → [layer, slot, p, h's lanes]
            want[layer, slot, :prompt] = pool.f32(
                per_layer[layer])[0].reshape(prompt, -1)
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


def a_prompt_inserted_then_attended_is_the_prefills_own_attention(pool):
    """``prompt_block`` → ``insert_block`` → ``decode_attention`` for a
    prompt's last token equals ``prefill_attention``'s row for it."""
    prompt, slot = 7, 1
    shape = (1, prompt, HEADS, HEAD_DIM)
    q, k, v = (jnp.asarray(pool.rng.standard_normal(shape), pool.dtype)
               for _ in range(3))
    want = pool.f32(kv_pool.prefill_attention(
        q, k, v, jnp.ones((1, prompt), bool)))[0, -1]
    for layer in range(LAYERS):
        blocks = [[jnp.zeros_like(x[:, :-1]) for _ in range(LAYERS)]
                  for x in (k, v)]
        blocks[0][layer], blocks[1][layer] = k[:, :-1], v[:, :-1]
        k_pool, v_pool = kv_pool.insert_block(
            (pool.k, pool.v), (kv_pool.prompt_block(blocks[0]),
                               kv_pool.prompt_block(blocks[1])),
            jnp.int32(slot))
        new = [jnp.zeros((SLOTS, HEADS, HEAD_DIM), pool.dtype)
               .at[slot].set(x[0, -1]) for x in (q, k, v)]
        got = kv_pool.decode_attention(
            *new, k_pool, v_pool, layer,
            jnp.zeros(SLOTS, jnp.int32).at[slot].set(prompt - 1))
        np.testing.assert_allclose(pool.f32(got)[slot], want, rtol=0,
                                   atol=pool.tol)


CASES = [
    shape_and_allocation,
    attention_is_a_softmax_over_the_cached_keys_and_the_new_one,
    a_bound_over_every_live_position_reads_what_the_whole_length_reads,
    the_row_write_changes_one_row_a_slot_and_a_layer,
    the_insert_lands_a_block_in_its_slot_and_touches_no_other,
    prefill_attention_is_a_causal_softmax_over_the_real_tokens,
    a_prompt_inserted_then_attended_is_the_prefills_own_attention,
]


@pytest.mark.parametrize("dtype", list(TOLERANCE))
@pytest.mark.parametrize("case", CASES, ids=lambda case: case.__name__)
def test_kv_pool(case, dtype):
    case(Pool(dtype))


# -- the decode read's kernel, over several blocks a slot ------------------

BLOCK, LONG, WIDE = 8, 32, 8     # positions a block; of the pool; slots


def blocked(dtype, monkeypatch, block=BLOCK):
    """A pool of WIDE slots x LONG positions whose decode read fetches
    ``block`` positions a grid step (the rule is bytes: the budget shrinks
    to ``block`` of this pool's narrow rows)."""
    pool = Pool(dtype, slots=WIDE, max_len=LONG)
    monkeypatch.setattr(
        kv_pool, "READ_BLOCK_BYTES",
        block * pool.shape[-1] * pool.dtype.itemsize)
    assert kv_pool.read_block(pool.shape, pool.dtype) == block
    return pool


def ragged_batches_match_the_plain_softmax(dtype, monkeypatch):
    """Positions 0, 1, a block's edge - 1 / exactly / + 1, the bound - 1
    and the bound itself, side by side, at both a cut and the whole
    length."""
    pool = blocked(dtype, monkeypatch)
    for layer, position, bound in [
            (0, (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 23, 24, 2 * BLOCK), 24),
            (1, (LONG, 0, 1, 2 * BLOCK + 1, LONG - 1, 0, 3 * BLOCK, 5), None)]:
        np.testing.assert_allclose(
            attend(pool, layer, position, bound),
            plain_decode_attention(pool, layer, position),
            rtol=0, atol=pool.tol)


def every_slot_full(dtype, monkeypatch):
    pool = blocked(dtype, monkeypatch)
    position = (LONG,) * WIDE
    np.testing.assert_allclose(
        attend(pool, 1, position), plain_decode_attention(pool, 1, position),
        rtol=0, atol=pool.tol)


def every_slot_dead_returns_the_new_values(dtype, monkeypatch):
    """Nothing cached: the softmax is over the new key alone, and the new
    value comes back bit for bit — whatever the pool holds."""
    pool = blocked(dtype, monkeypatch)
    pool.k = pool.k.at[:].set(jnp.nan)
    pool.v = pool.v.at[:].set(jnp.inf)
    assert (attend(pool, 0, (0,) * WIDE) == pool.f32(pool.v_new)).all()


def garbage_beyond_a_slots_position_disturbs_nothing(dtype, monkeypatch):
    """NaN and inf in dead slots and at or above a live slot's position
    (inside its last block and in the blocks after it): the same bits."""
    pool = blocked(dtype, monkeypatch)
    position = (0, 3, BLOCK, 0, 2 * BLOCK + 1, LONG - 1, 0, 1)
    clean = attend(pool, 1, position)
    for slot, p in enumerate(position):
        pool.k = pool.k.at[:, slot, p:].set(jnp.nan)
        pool.v = pool.v.at[:, slot, p:].set(
            jnp.inf if slot % 2 else jnp.nan)
    dirty = attend(pool, 1, position)
    assert np.isfinite(dirty).all() and (dirty == clean).all()
    assert (attend(pool, 1, position, 24)[:5] == clean[:5]).all()


def the_block_does_not_change_the_result(dtype, monkeypatch):
    position = (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, LONG - 1, LONG, 13)
    whole = attend(blocked(dtype, monkeypatch, LONG), 0, position)
    for block in (BLOCK, 2 * BLOCK):
        np.testing.assert_allclose(
            attend(blocked(dtype, monkeypatch, block), 0, position), whole,
            rtol=0, atol=TOLERANCE[dtype])


def both_rungs_read_the_same_blocks(dtype, monkeypatch):
    """A bound over every live position only trims the grid: the blocks
    read and their order are the same, and so are the bits."""
    pool = blocked(dtype, monkeypatch)
    position = (0, 24, 1, BLOCK, 0, 2 * BLOCK + 3, 23, BLOCK + 1)
    assert (attend(pool, 1, position, 24) == attend(pool, 1, position)).all()


def a_bound_inside_a_block_cuts_at_the_bound(dtype, monkeypatch):
    """``bound`` need not be whole blocks: a slot past it is read up to it
    exactly (its output is one nobody may read; the cut still is exact)."""
    pool = blocked(dtype, monkeypatch)
    position, bound = (0, 5, 12, 13, LONG, 11, 20, BLOCK), 12
    np.testing.assert_allclose(
        attend(pool, 0, position, bound),
        plain_decode_attention(pool, 0, [min(p, bound) for p in position]),
        rtol=0, atol=pool.tol)


def positions_read_counts_the_fetched_blocks_and_the_new_tokens(
        dtype, monkeypatch):
    pool = blocked(dtype, monkeypatch)
    position = (0, 1, BLOCK, BLOCK + 1, LONG, 0, 0, 23)
    active = [p > 0 for p in position]
    count = kv_pool.positions_read(pool.shape, pool.dtype, position, active,
                                   24)
    assert count == (0 + 8 + 8 + 16 + 24 + 0 + 0 + 24) + 5
    # never under what is live (position + 1 a live slot, cut at the bound)
    assert count >= sum(min(p, 24) + 1 for p in position if p)
    assert kv_pool.positions_read(pool.shape, pool.dtype, (0,) * WIDE,
                                  (False,) * WIDE, LONG) == 0


KERNEL_CASES = [
    ragged_batches_match_the_plain_softmax,
    every_slot_full,
    every_slot_dead_returns_the_new_values,
    garbage_beyond_a_slots_position_disturbs_nothing,
    the_block_does_not_change_the_result,
    both_rungs_read_the_same_blocks,
    a_bound_inside_a_block_cuts_at_the_bound,
    positions_read_counts_the_fetched_blocks_and_the_new_tokens,
]


@pytest.mark.parametrize("dtype", list(TOLERANCE))
@pytest.mark.parametrize("case", KERNEL_CASES, ids=lambda case: case.__name__)
def test_decode_kernel(case, dtype, monkeypatch):
    case(dtype, monkeypatch)


@pytest.mark.parametrize("position", [
    (0, 0, 9, 0, 24, 1, 0, 0), (3,) + (0,) * 7, (0,) * 8, (LONG,) * 8,
    (0, 0, 0, 0, 0, 0, 0, 17)],
    ids=["mixed", "first-alone", "all-dead", "all-full", "last-alone"])
def test_dead_blocks_and_dead_slots_fetch_nothing(position):
    """The plan's block index changes only where a live block begins, so a
    grid step over a dead block or a dead slot moves no bytes, and the
    blocks it does name are each slot's live ones in order."""
    bound, blocks = 24, 3
    plan = np.asarray(kernel.block_plan(
        jnp.asarray(position, jnp.int32), bound, BLOCK))
    fetched, held = [], None
    for slot in range(WIDE):
        for b in range(blocks):
            index = tuple(int(i) for i in kernel._pool_index(
                slot, b, plan, [1]))
            if index != held:
                fetched.append(index)
            held = index
    live = [(1, slot, b, 0) for slot, p in enumerate(position)
            for b in range(-(-min(p, bound) // BLOCK))]
    assert fetched == (live or [(1, 0, 0, 0)]), (position, fetched)


@pytest.mark.parametrize("dtype, head_dim", [("float32", 64),
                                             ("bfloat16", 128)])
def test_decode_kernel_at_the_served_row_widths(dtype, head_dim):
    """16 heads of 64 in float32 and of 128 in bfloat16 — rows of 4 KB, so
    the block is the 256 positions the cells run — over three blocks a
    slot: ragged positions against the plain softmax at the top rung, and
    the same bits at the rung below for the slots it holds."""
    pool = Pool(dtype, layers=1, slots=6, heads=16, max_len=768,
                head_dim=head_dim)
    assert kv_pool.read_block(pool.shape, pool.dtype) == 256
    position = (0, 255, 256, 257, 767, 768)
    top = attend(pool, 0, position)
    np.testing.assert_allclose(top, plain_decode_attention(pool, 0, position),
                               rtol=0, atol=pool.tol)
    assert (attend(pool, 0, position, 640)[:4] == top[:4]).all()


# -- grouped heads: more query heads than the row has K/V heads ---------------

def _grouped(dtype, heads, kv_heads, head_dim, slots, max_len, seed=31):
    """A one-layer pool of ``kv_heads`` heads a row and one step's new rows
    for ``heads`` query heads, as float32 numpy beside the device values."""
    rng = np.random.default_rng(seed)
    shape = kv_pool.pool_shape(kv_pool.Rows("k", 1, kv_heads * head_dim, dtype),
                               slots, max_len)
    k, v = (jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(2))
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((slots, n, head_dim)), dtype)
        for n in (heads, kv_heads, kv_heads))
    return shape, (q, k_new, v_new, k, v)


def _plain_grouped(args, position, kv_heads, scale=None):
    """The float32 oracle: query head ``h`` reads K/V head ``h // group``;
    the scores by ``scale``, ``1/√head_dim`` when None."""
    q, k_new, v_new, k, v = (np.asarray(a, np.float32) for a in args)
    slots, heads, head_dim = q.shape
    scale = head_dim ** -0.5 if scale is None else scale
    group = heads // kv_heads
    k, v = (a[0].reshape(slots, -1, kv_heads, head_dim) for a in (k, v))
    out = np.zeros(q.shape, np.float32)
    for s, p in enumerate(position):
        for h in range(heads):
            g = h // group
            keys = np.concatenate([k[s, :p, g], k_new[s, g][None]])
            values = np.concatenate([v[s, :p, g], v_new[s, g][None]])
            out[s, h] = softmax(keys @ q[s, h] * scale) @ values
    return out


@pytest.mark.parametrize("dtype", list(TOLERANCE))
@pytest.mark.parametrize("heads, kv_heads", [(16, 2), (8, 8), (16, 16),
                                             (32, 8), (6, 2), (4, 2)],
                         ids=["16on2", "8on8", "16on16", "32on8", "6on2",
                              "4on2"])
def test_decode_kernel_with_grouped_heads(dtype, heads, kv_heads,
                                          monkeypatch):
    """16 query heads on 2 K/V heads (eight a K/V head, laid onto its lanes
    in the kernel's block-diagonal operand), one a K/V head at 8 and at
    16, and groups that are no whole sublane tile — 4 a K/V head at 32 on 8
    and at 4 on 2, 3 at 6 on 2, whose rows select their line: ragged
    positions over three blocks a slot against the float32 oracle, and a
    bound below a slot's position cuts at the bound."""
    head_dim, max_len = 16, 96
    row = kv_heads * head_dim * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(kv_pool, "READ_BLOCK_BYTES", 32 * row)
    position = (0, 1, 31, 32, 33, 95, 96)
    shape, args = _grouped(dtype, heads, kv_heads, head_dim, len(position),
                           max_len)
    assert kv_pool.read_block(shape, dtype) == 32
    pos = jnp.asarray(position, jnp.int32)
    got = np.asarray(kv_pool.decode_attention(*args, 0, pos), np.float32)
    assert got.shape == (len(position), heads, head_dim)
    np.testing.assert_allclose(got, _plain_grouped(args, position, kv_heads),
                               rtol=0, atol=TOLERANCE[dtype])
    cut = np.asarray(kv_pool.decode_attention(*args, 0, pos, 64), np.float32)
    assert (cut[:5] == got[:5]).all()
    # the two slots past the bound read their first 64 positions
    tail = [a[5:] if i < 3 else a[:, 5:] for i, a in enumerate(args)]
    np.testing.assert_allclose(cut[5:], _plain_grouped(tail, (64, 64),
                                                       kv_heads),
                               rtol=0, atol=TOLERANCE[dtype])


@pytest.mark.parametrize("scale", [None, 1.0 / 64, 0.3])
def test_decode_kernel_at_the_narrow_group_served_row_width(scale):
    """32 query heads on 8 K/V heads of 64 in bfloat16 — a group of 4, a K/V
    head on half a lane tile, a row of 1 KB and so one block of the whole
    1,024-position pool — at ragged positions against the float32 oracle,
    with the family's own score multiplier (1/64, not 1/√64) handed over,
    another one, and the default; the same bits at the rung below for the
    slots it holds."""
    shape, args = _grouped("bfloat16", 32, 8, 64, 6, 1024)
    assert shape == (1, 6, 1024, 512)
    assert kv_pool.read_block(shape, jnp.bfloat16) == 1024
    position = (0, 1, 511, 767, 1023, 1024)
    pos = jnp.asarray(position, jnp.int32)
    top = np.asarray(kv_pool.decode_attention(*args, 0, pos, scale=scale),
                     np.float32)
    np.testing.assert_allclose(top, _plain_grouped(args, position, 8, scale),
                               rtol=0, atol=TOLERANCE["bfloat16"])
    if scale is not None:   # and it is not the default's result
        default = _plain_grouped(args, position, 8)
        assert np.abs(top - default).max() > 10 * TOLERANCE["bfloat16"]
    below = np.asarray(kv_pool.decode_attention(*args, 0, pos, 768,
                                                scale=scale), np.float32)
    assert (below[:4] == top[:4]).all()


def test_decode_kernel_at_the_grouped_served_row_width():
    """16 query heads on 2 K/V heads of 256 in bfloat16 — a row of 1 KB, so
    by the same 1 MB rule the block is 1,024 positions — over three blocks a
    slot of a 3,072-position pool: ragged positions against the float32
    oracle at the top rung, and the same bits at the rung below for the
    slots it holds."""
    shape, args = _grouped("bfloat16", 16, 2, 256, 6, 3072)
    assert shape == (1, 6, 3072, 512)
    assert kv_pool.read_block(shape, jnp.bfloat16) == 1024
    position = (0, 1023, 1024, 1025, 3071, 3072)
    pos = jnp.asarray(position, jnp.int32)
    top = np.asarray(kv_pool.decode_attention(*args, 0, pos), np.float32)
    np.testing.assert_allclose(top, _plain_grouped(args, position, 2),
                               rtol=0, atol=TOLERANCE["bfloat16"])
    below = np.asarray(kv_pool.decode_attention(*args, 0, pos, 2304),
                       np.float32)
    assert (below[:4] == top[:4]).all()


@pytest.mark.parametrize("scale", [None, 1.0 / 64])
@pytest.mark.parametrize("dtype", list(TOLERANCE))
def test_prefill_attention_with_grouped_heads(dtype, scale):
    """Four query heads on two K/V heads over a padded prompt: a causal
    softmax over the real tokens, head ``h`` reading K/V head ``h // 2``,
    the scores by ``1/√head_dim`` or by the multiplier handed over."""
    batch, prompt, heads, kv_heads, head_dim = 2, 6, 4, 2, 8
    length = (prompt, 3)
    rng = np.random.default_rng(37)
    q = jnp.asarray(rng.standard_normal((batch, prompt, heads, head_dim)),
                    dtype)
    k, v = (jnp.asarray(rng.standard_normal(
        (batch, prompt, kv_heads, head_dim)), dtype) for _ in range(2))
    mask = jnp.arange(prompt)[None, :] < jnp.asarray(length)[:, None]
    got = np.asarray(kv_pool.prefill_attention(q, k, v, mask, scale=scale),
                     np.float32)
    assert got.shape == q.shape
    qf, kf, vf = (np.asarray(a, np.float32) for a in (q, k, v))
    for b in range(batch):
        for i in range(length[b]):
            for h in range(heads):
                w = softmax(kf[b, :i + 1, h // 2] @ qf[b, i, h]
                            * (head_dim ** -0.5 if scale is None else scale))
                np.testing.assert_allclose(
                    got[b, i, h], w @ vf[b, :i + 1, h // 2], rtol=0,
                    atol=TOLERANCE[dtype])
