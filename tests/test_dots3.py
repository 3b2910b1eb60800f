"""The ``dots3`` LM family (``models/dots3.py``) against its plain reference
(``benchmark/references/dots3.py``) at a small size on the CPU: logits of
prefill and of decode through the cache (latent rows, indexer keys and the
window's ring) below and above ``index_topk`` and across the ring's wrap, the
exact top-k, the latent kernel against ``jax.numpy``, a slot's reuse, the
eight shares of the expert layer against the uncut one, the routing rules of
``models/experts.py``, the cache's declaration and counters, and the family
through the worker's own runtime.
"""

import importlib
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ai4e_tpu.models import experts as expert_layer  # noqa: E402
from ai4e_tpu.models.dots3 import create_dots3_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool  # noqa: E402
from ai4e_tpu.ops.pallas import decode_attention  # noqa: E402
from benchmark.references import dots3 as reference  # noqa: E402

# Two periods of full, full, sliding x 3 | full, sliding x 3 cut to eight
# layers; a quarter of the experts held, not from 0, so a share that is taken
# for the whole shows; full and sliding layers differ in every width.
SPEC = dict(vocab_size=97, dim=64,
            layer_types=("full", "full", "sliding", "sliding", "sliding",
                         "full", "sliding", "sliding"),
            dense_layers=1, heads=4, q_rank=32, kv_rank=16, nope=16,
            rope_dim=8, v_dim=16, rope_theta=8e7, swa_heads=2, swa_q_rank=32,
            swa_kv_rank=32, swa_nope=24, swa_rope_dim=8, swa_v_dim=16,
            swa_rope_theta=5e4, window=5, index_heads=4, index_dim=16,
            index_topk=8, mlp_dim=96, experts=16, experts_held=4,
            first_expert=4, experts_per_token=2, expert_dim=32, shared_dim=32,
            route_scale=1.0, rms_eps=1e-5)
CACHE = 64
BUCKETS = (8, 16, 32)
# float32: both sides compute in float32 and differ in the order of their
# sums and in the absorbed form of the step. bfloat16: the same weights, the
# system rounds every activation through eight layers and now and then keeps
# another position at the selection's edge or picks another second expert:
# that case guards the dtype's plumbing; the faults are held to the float32
# pair.
TOLERANCE = {"float32": 2e-4, "bfloat16": 1.0}


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    model, params = create_dots3_lm(dtype=request.param, **SPEC)
    return SimpleNamespace(model=model, params=params, dtype=request.param,
                           raw=params["params"])


@pytest.fixture(scope="module")
def lm32():
    model, params = create_dots3_lm(dtype="float32", **SPEC)
    return SimpleNamespace(model=model, params=params, dtype="float32",
                           raw=params["params"])


def _garbage_cache(model, slots, seed):
    """Pools of garbage: whatever a slot held before."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(
        kv_pool.pool_shape(rows, slots, CACHE)), rows.dtype)
        for rows in model.cache_spec().rows)


def _served_logits(lm, seq, prompt_len, slot=1, slots=3, pools=None):
    """Logits of every position of ``seq`` as the serving path computes them:
    one prefill of the prompt (padded to its bucket) into ``slot`` of a cache
    of garbage, then one decode step a token, teacher-forced, the other slots
    riding along at position 0."""
    apply = lm.model.apply
    pools = pools or _garbage_cache(lm.model, slots, slot)
    bucket = next(b for b in BUCKETS if b >= prompt_len)
    padded = np.full((1, bucket), 7, np.int32)   # past the prompt: not zeros
    padded[0, :prompt_len] = seq[:prompt_len]
    logits, *blocks, _ = apply(lm.params, padded,
                               np.asarray([prompt_len], np.int32),
                               method="prefill_logits")
    out = [np.asarray(logits[0, :prompt_len], np.float32)]
    pools = kv_pool.insert_block(pools, tuple(blocks), slot)
    step = jax.jit(lambda *a: apply(lm.params, *a, method="decode_logits"))
    for position in range(prompt_len, len(seq)):
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        tokens[slot], positions[slot] = seq[position], position
        logits, *pools, _ = step(tokens, *pools, {}, positions)
        out.append(np.asarray(logits[slot:slot + 1], np.float32))
    return np.concatenate(out), tuple(pools)


# Contexts below ``index_topk`` (8) and above it, inside the window (5) and
# across the ring's wrap (4 rows), a prompt of one token, one of a bucket's
# exact size, and a decode that goes on long after both.
@pytest.mark.parametrize("prompt_len,decoded", [
    (1, 6), (3, 3), (7, 4), (8, 4), (9, 6), (16, 5), (21, 12), (32, 9)])
def test_prefill_then_decode_logits_match_the_reference(lm, prompt_len,
                                                        decoded):
    rng = np.random.default_rng(prompt_len)
    seq = rng.integers(0, SPEC["vocab_size"],
                       size=prompt_len + decoded).tolist()
    want = reference.forward(lm.raw, SPEC, seq)
    got, _ = _served_logits(lm, seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE[lm.dtype]


def test_a_slot_reused_after_a_longer_sequence_holds_nothing_of_it(lm32):
    rng = np.random.default_rng(5)
    long = rng.integers(0, 97, size=40).tolist()
    short = rng.integers(0, 97, size=14).tolist()
    _, pools = _served_logits(lm32, long, 30)
    got, _ = _served_logits(lm32, short, 6, pools=pools)
    want = reference.forward(lm32.raw, SPEC, short)
    assert np.abs(got - want).max() < TOLERANCE["float32"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_faults_move_the_logits(lm32, fault):
    """Each wrong model the tolerance study computes is far outside the
    float32 pair's agreement: the comparison sees it."""
    seq = np.random.default_rng(3).integers(0, 97, size=40).tolist()
    right = reference.forward(lm32.raw, SPEC, seq)
    wrong = reference.forward(lm32.raw, SPEC, seq, fault=fault)
    assert np.abs(right - wrong).max() > 50 * TOLERANCE["float32"]


def test_the_cache_holds_the_cache_dtype_and_the_selection_float32():
    """The bfloat16 family keeps every tensor of rows in bfloat16; its index
    scores and its selection are float32 whatever the dtype."""
    from ai4e_tpu.models import dots3
    model, _ = create_dots3_lm(dtype="bfloat16", **SPEC)
    assert {jnp.dtype(r.dtype) for r in model.cache_spec().rows} == {
        jnp.dtype(jnp.bfloat16)}
    rng = np.random.default_rng(0)
    iq = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.bfloat16)
    ik = jnp.asarray(rng.standard_normal((9, 16)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    scores = dots3.index_scores(iq, ik, w)
    assert scores.dtype == jnp.float32 and scores.shape == (3, 9)
    want = (np.maximum(np.einsum("qjd,kd->qjk", np.asarray(iq, np.float32),
                                 np.asarray(ik, np.float32)), 0)
            * np.asarray(w)[..., None]).sum(axis=1)
    assert np.abs(np.asarray(scores) - want).max() < 1e-5


# -- the exact top-k -----------------------------------------------------------

def _top_by_sort(scores, valid, k):
    masked = np.where(valid, scores, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")[:, :k]
    out = np.zeros(scores.shape, bool)
    np.put_along_axis(out, order, True, axis=-1)
    return out & valid


def _causal(at, rows, n):
    """A block of ``rows`` queries from position ``at`` against ``n`` keys."""
    return np.arange(n)[None, :] <= (at + np.arange(rows))[:, None]


def _step_shaped(rows, n):
    """What a step hands over: a slot's cached positions, then its own new
    token in the last column."""
    valid = np.arange(n)[None, :] < np.linspace(1, n - 1, rows).astype(
        int)[:, None]
    valid[:, -1] = True
    return valid


# the shape of a call -> (valid, k): the few rows a step selects over and
# the first test's (``jax.numpy``), a block of a prompt's queries from its
# middle and a width that is no whole lane tile nor a whole row tile (the
# kernel, interpreted)
SELECT_SHAPES = {
    "rows": (np.tril(np.ones((50, 50), bool))[[0, 3, 7, 8, 30, 49]], 8),
    "block": (_causal(768, 256, 1536), 200),
    "step": (_step_shaped(16, 513), 64),
    "ragged": (_causal(1000, 72, 3700), 1500),
}


@pytest.mark.parametrize("shape", list(SELECT_SHAPES))
@pytest.mark.parametrize("case", ["random", "ties", "zeros", "few", "all",
                                  "negative", "huge"])
def test_select_top_is_the_exact_top_k_with_ties_to_the_lower_index(case,
                                                                    shape):
    rng = np.random.default_rng(len(case))
    valid, k = SELECT_SHAPES[shape]
    rows, n = valid.shape
    in_kernel = rows * n * 4 >= kv_pool.SELECT_KERNEL_BYTES
    assert in_kernel == (shape in ("block", "ragged"))
    scores = rng.standard_normal((rows, n)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores)            # many equal values at the edge
    elif case == "zeros":
        scores = np.where(rng.random((rows, n)) < 0.7, 0.0, scores)
        scores = scores * np.where(rng.random((rows, n)) < 0.5, -1.0, 1.0)
    elif case == "few":
        valid = valid & (rng.random((rows, n)) < 0.1)
    elif case == "all":
        valid = np.ones((rows, n), bool)
    elif case == "negative":
        scores = -np.abs(scores) - 1.0
    elif case == "huge":
        scores = scores * 1e30
    scores = scores.astype(np.float32)
    got = np.asarray(jax.jit(kv_pool.select_top, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(valid), k))
    assert got.dtype == bool
    assert np.array_equal(got, _top_by_sort(scores, valid, k))
    assert np.array_equal(np.asarray(kv_pool.select_top(
        jnp.asarray(scores), jnp.asarray(valid), n)), valid)


def test_select_top_ranks_ties_across_the_kernels_tile_edges():
    """The ties at the ``k``-th value lie over three lane tiles and two of
    the kernel's column chunks, and the last one kept falls in a different
    place row by row — before, on and after the edges at 384 and 512; the
    mask a prompt asks for comes back in bytes."""
    rows, n, k = 256, 1536, 300
    rng = np.random.default_rng(7)
    scores = -rng.random((rows, n)).astype(np.float32)
    scores[:, 300:900] = 1.0                               # the ties
    for r in range(rows):                                  # above them
        scores[r, rng.choice(np.arange(900, 1200), r % 250, replace=False)] = 2.0
    valid = _causal(1200, rows, n)
    got = jax.jit(kv_pool.select_top, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(valid, jnp.int8), k)
    assert got.dtype == jnp.int8
    want = _top_by_sort(scores, valid, k)
    assert np.array_equal(np.asarray(got), want.astype(np.int8))
    last_kept = (want[:, 300:900].sum(axis=1) + 299).tolist()
    assert min(last_kept) < 384 < 512 < max(last_kept)


# -- the latent kernel ---------------------------------------------------------

def _latent_by_numpy(q, new, pool, layer, position, value, scale, keep, own):
    f = np.float64
    q, new, rows = (np.asarray(a, f) for a in (q, new, pool[layer]))
    out = np.zeros((*q.shape[:2], value))
    for s, p in enumerate(position):
        kept = np.ones(p, bool) if keep is None else np.asarray(keep[s, :p],
                                                                bool)
        keys = np.concatenate([rows[s, :p][kept],
                               new[s:s + 1] if own[s] else new[:0]])
        if not len(keys):
            continue
        scores = q[s] @ keys.T * scale
        w = np.exp(scores - scores.max(axis=-1, keepdims=True))
        out[s] = (w / w.sum(axis=-1, keepdims=True)) @ keys[:, :value]
    return out


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("heads,row,value", [(128, 640, 512), (64, 1152, 1024)])
def test_the_latent_kernel_is_the_softmax_over_shared_rows(
        dtype, tol, masked, heads, row, value):
    """Group ``heads`` on ONE row: a 576-lane key (padded to 640) whose
    first 512 lanes are the value, and the window's 1,088 / 1,024 — under the
    interpreter, blocks of 128 positions, slots dead, short, on a block's
    edge and long; masked: some blocks wholly left out, and the new token's
    own term left out of one slot."""
    rng = np.random.default_rng(heads + masked)
    slots, length, block = 5, 512, 128
    position = np.asarray([0, 37, 128, 300, 512], np.int32)
    pool = jnp.asarray(rng.standard_normal((2, slots, length, row)) * 0.3,
                       dtype)
    q = jnp.asarray(rng.standard_normal((slots, heads, row)) * 0.2, dtype)
    new = jnp.asarray(rng.standard_normal((slots, row)) * 0.3, dtype)
    keep = own = None
    if masked:
        keep = rng.random((slots, length)) < 0.4
        keep[3, :256] = False            # whole blocks with nothing kept
        keep[4, 128:] = False
        own = np.asarray([1, 1, 0, 1, 1], np.int32)
    got = decode_attention.latent_attention(
        q, new, pool, 1, jnp.asarray(position), value=value, bound=length,
        block=block, scale=0.07, keep=None if keep is None else
        jnp.asarray(keep), own=None if own is None else jnp.asarray(own),
        interpret=True)
    assert got.shape == (slots, heads, value) and got.dtype == q.dtype
    want = _latent_by_numpy(q, new, pool, 1, position, value, 0.07, keep,
                            np.ones(slots) if own is None else own)
    live = position > 0
    assert np.abs(np.asarray(got, np.float64)[live] - want[live]).max() < tol


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_a_global_latent_pool_is_read_whole_without_a_selection(dtype, tol):
    """``keep=None`` on a GLOBAL pool (no ring, no indexer: the ``xing4``
    family's read), through ``kv_pool.latent_decode_attention`` with the
    block ``read_block`` gives a 640-lane row, at a bound above one block:
    every block under a slot's position is read unmasked, a slot past the
    bound reads the bound, and what lies above a position is never seen."""
    rng = np.random.default_rng(32)
    slots, length, heads, row, value = 4, 2048, 32, 640, 512
    pool = jnp.asarray(rng.standard_normal((2, slots, length, row)) * 0.3,
                       dtype)
    block = kv_pool.read_block(pool.shape, pool.dtype)
    bound = 1536
    assert block < bound < length          # 768 (bfloat16) / 384 (float32)
    position = np.asarray([0, block - 1, block + 5, 1990], np.int32)
    q = jnp.asarray(rng.standard_normal((slots, heads, row)) * 0.2, dtype)
    new = jnp.asarray(rng.standard_normal((slots, row)) * 0.3, dtype)
    got = kv_pool.latent_decode_attention(
        q, new, pool, 1, jnp.asarray(position), value=value, bound=bound,
        scale=0.07, interpret=True)
    assert got.shape == (slots, heads, value) and got.dtype == q.dtype
    want = _latent_by_numpy(q, new, pool, 1, np.minimum(position, bound),
                            value, 0.07, None, np.ones(slots))
    live = position > 0
    assert np.abs(np.asarray(got, np.float64)[live] - want[live]).max() < tol


def _prompt_by_numpy(q, k, v, scale, mask, window):
    f = np.float64
    q, k, v = (np.asarray(a, f) for a in (q, k, v))   # (P, H, d)
    t = np.arange(q.shape[0])
    allowed = t[None, :] <= t[:, None]
    if window is not None:
        allowed &= t[None, :] > t[:, None] - window
    if mask is not None:
        allowed &= np.asarray(mask) != 0
    scores = np.einsum("qhd,khd->hqk", q, k) * scale
    scores = np.where(allowed[None], scores, -np.inf)
    w = np.exp(scores - scores.max(axis=-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", w / w.sum(axis=-1, keepdims=True), v)


def _prompt_operands(rng, p, heads, dtype, masked, dqk=24, dv=16):
    """q, k (P, H, dqk), v (P, H, dv) and, where ``masked``, a selection
    that leaves whole blocks of a query's keys out (its own key among
    them)."""
    q, k = (jnp.asarray(rng.standard_normal((p, heads, dqk)) * 0.5, dtype)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((p, heads, dv)), dtype)
    mask = None
    if masked:
        mask = rng.random((p, p)) < 0.3
        mask[:, 0] = True                    # every query keeps a key
        mask[p // 2:, p // 4:] = False       # whole blocks left out
        mask = jnp.asarray(mask)
    return q, k, v, mask


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("p,window,masked,heads", [
    (16, None, True, 3),        # one block: a tiny prompt
    (640, None, True, 3),       # five blocks of 128, a selection
    (640, 130, False, 3),       # a band that spans three blocks of keys
    (1024, 513, False, 3),      # the published window over two blocks of 512
    (1280, None, False, 3),     # causal alone, blocks of 256
    (200, None, False, 1),      # one head, one block that is no lane tile's
    (200, 7, False, 5),         # ... and five heads under a narrow window
    (1024, None, True, 16),     # four groups of 4 heads, two blocks of 512
    (1024, 513, False, 17),     # a count no group divides: a head a step
    (2560, None, True, 2),      # five blocks of 512, a selection
    (2560, 513, False, 2)])     # ... and the band: two blocks a query block
def test_prompt_attention_is_the_softmax_under_a_mask_and_a_window(
        dtype, tol, p, window, masked, heads):
    """The prefill's kernel under the interpreter against ``numpy``: keys 24
    wide against values 16 wide, a group of heads a grid step, blocks above
    the diagonal and behind the window never walked, a selection that leaves
    whole blocks of a query's keys out (the query's own key among them)."""
    q, k, v, mask = _prompt_operands(np.random.default_rng(p), p, heads,
                                     dtype, masked)
    got = kv_pool.prompt_attention(q, k, v, 0.2, mask=mask, window=window,
                                   interpret=True)
    assert got.shape == (p, heads, 16) and got.dtype == v.dtype
    want = _prompt_by_numpy(q, k, v, 0.2, mask, window)
    assert np.abs(np.asarray(got, np.float64) - want).max() < tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window,masked", [
    (None, True), (513, False), (None, False)])
def test_a_heads_output_does_not_depend_on_its_group(dtype, window, masked):
    """Sixteen heads in groups of four (keys 200 wide: two lane tiles)
    against the same heads a call each (a group of one): bit for bit the
    same output — the block's bias is shared, the scores, the softmax and
    the value product are the head's own."""
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    p, heads = 1024, 16
    assert 1 < flash._head_group(heads, 512, 200, 16,
                                 jnp.dtype(dtype).itemsize, masked) < heads
    *qkv, mask = _prompt_operands(np.random.default_rng(44), p, heads, dtype,
                                  masked, dqk=200)
    q, k, v = (jnp.swapaxes(a, 0, 1) for a in qkv)   # heads first
    together = flash.prompt_attention(q, k, v, scale=0.2, mask=mask,
                                      window=window, interpret=True)
    alone = jnp.concatenate([flash.prompt_attention(
        q[h:h + 1], k[h:h + 1], v[h:h + 1], scale=0.2, mask=mask,
        window=window, interpret=True) for h in range(heads)])
    assert together.shape == (heads, p, 16)
    assert np.array_equal(np.asarray(together, np.float32),
                          np.asarray(alone, np.float32))


@pytest.mark.parametrize("block", [128, 256, 512])
@pytest.mark.parametrize("window", [None, 5, 513])
def test_the_list_of_pairs_holds_each_block_a_query_block_reads_once(
        block, window):
    """``_prompt_pairs`` for 1-24 blocks: exactly the blocks in which some
    query may read some key, each once, none above the diagonal or behind
    the window; a query block's pairs adjacent and in the keys' order, the
    first and the last marked."""
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    for blocks in range(1, 25):
        pairs = flash._prompt_pairs(blocks, block, window)
        assert pairs.dtype == np.int32 and pairs.shape[0] == 4
        iq, ik = pairs[flash.IQ], pairs[flash.IK]
        # a block is read iff its nearest pair is permitted: the query
        # block's last query (first, behind the window) against the key
        # block's first key (last)
        want = [(a, b) for a in range(blocks) for b in range(a + 1)
                if window is None
                or (b + 1) * block - 1 > a * block - window]
        assert list(zip(iq.tolist(), ik.tolist())) == want
        assert (ik <= iq).all() and (np.diff(iq) >= 0).all()
        first = np.r_[True, np.diff(iq) != 0]
        last = np.r_[np.diff(iq) != 0, True]
        assert np.array_equal(pairs[flash.FIRST], first)
        assert np.array_equal(pairs[flash.LAST], last)
        assert (ik[last] == iq[last]).all()       # the diagonal closes it
        if window is not None and window <= block + 1:
            assert (np.bincount(iq) <= 2).all()   # the band, not the prompt


@pytest.mark.parametrize("heads,block,dqk,dv,itemsize,masked,group", [
    (32, 512, 192, 128, 2, True, 4),      # dots3's full layers, 6,144
    (32, 512, 256, 128, 2, False, 4),     # ... its sliding layers
    (32, 256, 192, 128, 2, True, 4),      # the cache's own length, 12,544
    (32, 128, 192, 128, 2, False, 4),     # xing4's 128 bucket
    (4, 512, 192, 128, 2, True, 4),       # validate.py's four heads
    (1, 512, 192, 128, 2, True, 1),
    (17, 512, 24, 16, 4, False, 1),       # no group divides: a head a step
    (3, 128, 24, 16, 4, True, 3),
    (6, 128, 24, 16, 4, True, 3),         # the largest divisor up to four
    (32, 512, 512, 512, 4, True, 2),      # wide heads: what VMEM holds
    (32, 512, 2048, 2048, 4, False, 1)])
def test_the_group_of_heads_follows_from_the_shapes(
        heads, block, dqk, dv, itemsize, masked, group):
    """``_head_group``: the largest divisor of the heads, up to
    ``PROMPT_HEADS``, whose blocks, with the block of bias and one head's
    scores, fit the VMEM the call asks for."""
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    assert flash._head_group(heads, block, dqk, dv, itemsize,
                             masked) == group <= flash.PROMPT_HEADS
    assert group == 1 or flash.prompt_vmem_bytes(
        group, block, dqk, dv, itemsize, masked) <= flash.PROMPT_VMEM_BYTES
    wider = [g for g in range(group + 1, flash.PROMPT_HEADS + 1)
             if heads % g == 0]
    assert all(flash.prompt_vmem_bytes(g, block, dqk, dv, itemsize, masked)
               > flash.PROMPT_VMEM_BYTES for g in wider)


@pytest.mark.parametrize("p,queries,first", [
    (16, 8, 8), (640, 128, 0), (640, 128, 256), (1280, 256, 1024)])
def test_prompt_index_scores_are_the_weighted_relu_products(p, queries,
                                                            first):
    """The indexer's kernel under the interpreter: a block of queries from
    ``first`` against every key at or before its last query; what lies in
    blocks wholly after it reads 0."""
    rng = np.random.default_rng(p + first)
    heads, d = 4, 16
    iq = jnp.asarray(rng.standard_normal((heads, queries, d)), jnp.float32)
    ik = jnp.asarray(rng.standard_normal((p, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((queries, heads)), jnp.float32)
    got = np.asarray(kv_pool.prompt_index_scores(iq, ik, w, jnp.int32(first)))
    want = (np.maximum(np.einsum("jqd,kd->qjk", *(np.asarray(a, np.float64)
                                                  for a in (iq, ik))), 0)
            * np.asarray(w, np.float64)[:, :, None]).sum(axis=1)
    causal = np.arange(p)[None, :] <= first + np.arange(queries)[:, None]
    assert got.shape == (queries, p) and got.dtype == np.float32
    assert np.abs(np.where(causal, got - want, 0)).max() < 1e-4
    assert np.isfinite(got).all()


# -- the expert layer ----------------------------------------------------------

def test_route_softmax_path_is_what_it_was():
    """The scoring rule is an option: the softmax families' call is bit for
    bit the arithmetic it was before the option."""
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((11, 64)), jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((64, 16)), jnp.bfloat16)
    for renormalise in (False, True):
        top_e, top_p = expert_layer.route(h, router, 3, renormalise)
        p = jax.nn.softmax(jnp.einsum(
            "...d,de->...e", h, router,
            preferred_element_type=jnp.float32), axis=-1)
        want_p, want_e = jax.lax.top_k(p, 3)
        if renormalise:
            want_p = want_p / want_p.sum(axis=-1, keepdims=True)
        assert np.array_equal(np.asarray(top_e), np.asarray(want_e))
        assert np.array_equal(np.asarray(top_p), np.asarray(want_p))


def test_sigmoid_route_chooses_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.2, jnp.float32)
    top_e, top_p = expert_layer.route(h, router, 3, True, scoring="sigmoid",
                                      bias=bias, scale=2.5)
    want_e, want_p = reference.route(np.asarray(h), np.asarray(router),
                                     np.asarray(bias), 3, 2.5)
    assert np.array_equal(np.asarray(top_e), want_e)
    assert np.abs(np.asarray(top_p) - want_p).max() < 1e-6
    plain_e, _ = expert_layer.route(h, router, 3, True, scoring="sigmoid")
    assert not np.array_equal(np.asarray(plain_e), want_e)   # the bias bites
    with pytest.raises(ValueError):
        expert_layer.route(h, router, 3, scoring="tanh")


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """A deployment's eight chips each hold an eighth of the routed experts
    and all hold the shared one: the program's shares (``first_expert``,
    ``experts_held``), the shared expert counted once, add up to the
    reference's layer over ALL experts — in a step's dense form and a
    prefill's routed one."""
    spec = dict(SPEC, experts=16, experts_held=16, first_expert=0)
    _, params = create_dots3_lm(dtype="float32", **spec)
    layer = params["params"]["layer3"]
    h = jnp.asarray(np.random.default_rng(8).standard_normal((23, 64)),
                    jnp.float32)

    def w(a):
        return jnp.asarray(a, jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = np.asarray(
            reference.moe(h, layer, spec, w, held=(0, 16))
            + reference.swiglu(h, layer["s_gate"], layer["s_up"],
                               layer["s_down"]))
        top_e, top_p = expert_layer.route(
            h, layer["router"], 2, True, scoring="sigmoid",
            bias=layer["router_bias"], scale=1.0)
        for form in ("dense", "routed"):
            got = np.asarray(expert_layer.shared(
                h, None, layer["s_gate"], layer["s_up"], layer["s_down"]))
            for share in range(8):
                held = tuple(layer[n][2 * share:2 * share + 2]
                             for n in ("w_gate", "w_up", "w_down"))
                if form == "dense":
                    got = got + np.asarray(expert_layer.dense(
                        h, expert_layer.gate_matrix(top_e, top_p, 2,
                                                    2 * share), *held))
                else:
                    got = got + np.asarray(expert_layer.routed(
                        h, top_e, top_p, *held, total=16, first_held=2 * share))
            assert np.abs(got - want).max() < 2e-5, form
    # and one share alone is the reference's share
    part = np.asarray(reference.moe(h, layer, spec, w, held=(6, 2)))
    assert 0 < np.abs(part).max() < np.abs(want).max()


# -- the declaration, the counters, the runtime --------------------------------

def test_cache_spec_declares_three_kinds_of_rows_and_no_second_value():
    model, _ = create_dots3_lm(dtype="bfloat16", **SPEC)
    spec = model.cache_spec()
    assert spec.state == () and spec.live == ()
    assert [(r.name, r.layers, r.width, r.length, r.kind, r.select, r.whole)
            for r in spec.rows] == [
        ("latent", 3, 128, None, "latent", 8, False),
        ("index", 3, 16, None, "index", None, True),
        ("window", 5, 128, 4, "window", None, False)]
    assert kv_pool.rows_nbytes(spec.rows, 3, 64) == 2 * 3 * (
        3 * 64 * 128 + 3 * 64 * 16 + 5 * 4 * 128)


def test_step_reads_and_prefill_pairs_count_by_kind():
    model, _ = create_dots3_lm(dtype="bfloat16", **SPEC)
    rows = model.cache_spec().rows
    attended, nbytes, selected = kv_pool.step_reads(
        rows, 3, 64, [0, 19, 3], [False, True, True], 48)
    # the latent pool's read block is its whole 64 positions here
    assert attended == 64 + 64 + 2 and selected == 8 + 4
    assert nbytes == {"latent": 3 * 128 * 2 * (attended + 2),
                      "index": 3 * 16 * 2 * (3 * 48 + 2 + 2),
                      "window": 5 * 128 * 2 * (4 + 4 + 2 + 2)}
    assert kv_pool.prefill_pairs(rows, 19) == {
        "selected": 36 + 11 * 8, "index": 190, "window": 15 + 14 * 5}
    # a family that keeps K and V counts its causal pairs under one kind
    kv = kv_pool.kv_slot(2, 2, 16, jnp.bfloat16).rows
    assert kv_pool.prefill_pairs(kv, 5) == {"kv": 15}
    assert kv_pool.step_reads(kv, 2, 64, [5, 0], [True, False], 64)[2] is None


def _runtime(**kwargs):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    lm = build_lm_servable("dots3", max_len=CACHE, dtype="float32", **SPEC)
    return PagedDecodeRuntime(lm, slots=3, prompt_buckets=BUCKETS[:2],
                              **kwargs)


def test_the_runtime_serves_the_family_and_counts_its_cache():
    """Through ``PagedDecodeRuntime``: the ids of prefill + steps are the
    reference's argmax, the launch reports the three kinds of bytes and the
    positions kept, the report carries the routing series, and nothing
    compiles after ``warm()``."""
    runtime = _runtime()
    runtime.warm()
    told = []
    runtime.phase_hook = lambda phase, seconds: told.append(phase)
    prompt = np.random.default_rng(11).integers(0, 97, size=13).tolist()
    out = [runtime.prefill_into(2, prompt)]
    for i in range(9):
        fresh, positions, active = [None] * 3, [0] * 3, [False] * 3
        fresh[2], positions[2], active[2] = out[-1], len(prompt) + i, True
        step = runtime.fetch(runtime.launch(fresh, positions, active))
        out.append(step.ids[2])
    assert "compile" not in told
    logits = reference.forward(runtime.servable.params["params"], SPEC,
                               prompt + out[:-1])
    assert out == logits[len(prompt) - 1:].argmax(axis=-1).tolist()
    assert set(step.cache_bytes) == {"latent", "index", "window", "state"}
    assert step.selected == 8 and step.state_bytes == {}
    assert set(step.report) == {"experts_touched", "expert_peak_load",
                                "held_picks_share"}
    assert runtime.prefill_report(13) == {
        "tokens": {"real": 13, "padded": 16},
        "pairs": kv_pool.prefill_pairs(runtime.rows_spec(), 13)}
    assert runtime.cache_nbytes() == kv_pool.rows_nbytes(
        runtime.rows_spec(), 3, CACHE)
    assert runtime._rows[2].shape == (5, 3, 4, 128)   # a ring, not max_len


def _served(requests: int, new_tokens: int):
    """The registry of a ``DecodeEngine`` over ``_runtime()`` after
    ``requests`` requests of one 11-token prompt, one after another."""
    import asyncio

    from ai4e_tpu.metrics.registry import MetricsRegistry
    from ai4e_tpu.runtime.decode import DecodeEngine

    async def main():
        reg = MetricsRegistry()
        engine = DecodeEngine(_runtime(), metrics=reg)
        await engine.start()
        try:
            for _ in range(requests):
                await engine.submit([5, 9, 12, 4, 4, 8, 1, 2, 3, 6, 7],
                                    new_tokens)
        finally:
            await engine.stop()
        return reg

    return asyncio.run(main())


def test_a_join_report_comes_once_with_the_read_of_its_first_id():
    """What a prefill appends to its first id (the passes of its seven
    expert layers) stays on the device beside it and reaches the host with
    the read that brings the id — the first step's fetch, ``first_ids``,
    ``prefill_into`` — once: a later read that carries the same column
    again is not taken twice."""
    runtime = _runtime()
    runtime.warm()
    assert runtime.report_kinds == ("first", "extra")
    prompt = np.random.default_rng(12).integers(0, 97, size=13).tolist()
    token = runtime.servable.model.apply(
        runtime.servable.params, np.asarray([prompt + [0] * 3], np.int32),
        np.asarray([13], np.int32), method="prefill")[0]
    want = dict(zip(runtime.report_kinds, token[1:].tolist()))
    assert 5 <= want["first"] <= 7 and want["extra"] == 0

    def step(positions):
        return runtime.fetch(runtime.launch(
            [None] * 3, positions, [p > 0 for p in positions]))

    runtime.join(1, prompt)
    assert runtime.join_report(1) == {}      # on the device still
    step([0, 13, 0])
    assert runtime.join_report(1) == want
    assert runtime.join_report(1) == {}      # handed over once
    step([0, 14, 0])
    assert runtime.join_report(1) == {}      # the step zeroed its rows
    runtime.join(0, prompt)                  # read alone, no step to carry it
    runtime.first_ids()
    assert runtime.join_report(0) == want
    step([13, 15, 0])                        # carries slot 0's column again
    assert runtime.join_report(0) == {}
    runtime.prefill_into(2, prompt)          # the blocking join reads it
    assert runtime.join_report(2) == want
    step([14, 16, 13])
    assert all(runtime.join_report(slot) == {} for slot in range(3))
    runtime.reset_cache()
    assert runtime._reports == {}


@pytest.mark.parametrize("new_tokens", [1, 6])
def test_the_engine_counts_the_passes_of_a_prefills_expert_layers(new_tokens):
    """``ai4e_decode_prefill_expert_passes_total``: seven expert layers, one
    pass each, whether a step carried the report (6 tokens) or the first id
    was read alone (a request for one token)."""
    passes = _served(2, new_tokens).counter(
        "ai4e_decode_prefill_expert_passes_total")
    assert passes.value(model="lm", kind="first") == 2 * 7
    assert passes.value(model="lm", kind="extra") == 0


def test_the_engine_counts_selected_positions_and_prefill_work():
    reg = _served(1, 6)
    positions = reg.counter("ai4e_decode_kv_positions_total")
    assert positions.value(model="lm", kind="selected") == 5 * 8
    assert positions.value(model="lm", kind="live") == sum(range(12, 17))
    tokens = reg.counter("ai4e_decode_prefill_tokens_total")
    assert (tokens.value(model="lm", kind="real"),
            tokens.value(model="lm", kind="padded")) == (11, 16)
    pairs = reg.counter("ai4e_decode_prefill_pairs_total")
    assert pairs.value(model="lm", kind="index") == 66
    assert pairs.value(model="lm", kind="selected") == 36 + 3 * 8
    assert pairs.value(model="lm", kind="window") == 15 + 6 * 5
    kinds = reg.counter("ai4e_decode_cache_bytes_total")
    assert all(kinds.value(model="lm", kind=k) > 0
               for k in ("latent", "index", "window"))
