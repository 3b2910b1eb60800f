"""Pallas TPU kernel: one decode step's attention over the K/V pool, read
per slot only as far as that slot has written.

The pool (``ops/kv_pool.py``) holds a position's K (V) as one contiguous
row of ``heads x head_dim`` lanes — whole lane tiles — with ``max_len`` on
the sublanes::

    k, v : (layers, slots, max_len, heads * head_dim)

so a block of positions is a plain ``(block, row)`` tile in VMEM and the
kernel takes the pool tensor itself, never a slice of it (a slice that
reaches a custom call is a copy of a layer's K and V every step).

Grid ``(slots, blocks under bound)``, the block axis fastest, with the
step's plan and the layer as scalar-prefetch operands: how far each slot is
read (``min(position, bound)``), and which block its grid steps fetch. A
live slot fetches blocks ``0 .. last`` — those that hold a position under
its limit — and its later steps ask again for ``last``; a dead slot
(position 0: every inactive slot) asks for the block the step before it
held, or the one the first live slot starts with. Pallas skips a DMA whose
block index did not change, so a dead block and a dead slot cost a grid
step (~0.25 us on a v5e) and no bytes.

Heads share a row, so a head's scores are a segment of it. The row of ``q``
becomes a block-diagonal ``(heads, row)`` matrix (head ``h`` holds ``q`` on
its own lanes, zero elsewhere) and both products run on the MXU with the
heads on the sublanes: ``scores (heads, block) = q_bd . k^T`` contracts the
whole row, ``acc (heads, row) += p . v`` gives every head every lane, and
the lanes a head does not own are dropped at the end. Grouped heads are the
same matrix with more rows than the pool's row has heads: the row holds
``kv_heads x head_dim`` lanes, the ``group = heads / kv_heads`` query heads
of one K/V head all lie on that head's lanes (rows ``g * group .. (g + 1) *
group - 1`` of ``q_bd``), and ``q`` and the output travel as ``(group,
row)`` — line ``j`` holds the ``j``-th query head of every K/V head, each
on its K/V head's lanes. One query head a K/V head is ``group = 1``. A
group of whole float32 sublane tiles (a multiple of 8) stacks ``q``'s lines
by a concatenate and unstacks the output by slices, both tile-aligned; any
other group (4 query heads a K/V head, ...) has no aligned piece to stack,
so each row of ``q_bd`` selects its line (row ``r`` holds line ``r % group``)
and each line of the output sums the rows that hold it — the same matrix,
built from whole ``(heads, row)`` tiles. A K/V head may own any part of a
lane tile (64 lanes are half of one): its lanes are a mask, not a slice.
The scores are scaled by ``scale``, ``head_dim ** -0.5`` unless the family
hands over its own (a model that multiplies ``q . k`` by a constant of its
configuration). An online softmax
(running max and sum a head, a float32 accumulator) carries a slot across
its blocks in VMEM scratch. Float32 operands multiply at
``Precision.HIGHEST`` (the MXU's default would round them to bfloat16);
bfloat16 operands multiply exactly as they are, with float32 sums, and the
float32 weights meet a bfloat16 V as three bfloat16 terms that add up to
them: no weight is rounded (the XLA read before this kernel cast them to
V's dtype). On a v5e the products hide under the blocks' DMA in both
dtypes: a busy grid runs at ~735 GB/s of K and V (CHANGES.md PR 30).

The new token's own key and value are one more term of the same softmax,
joined in the slot's last grid step, which normalises and writes the slot's
row. (Joined outside, on ``(slots, heads)``-sized values in ``jax.numpy``,
the same arithmetic was ~17 small XLA operations a layer at head width 64 —
lane spreads and relayouts — a seventh of the step program's operations.)

A second body, ``latent_attention``, reads a pool whose row is shared by
EVERY head (latent attention in its absorbed form): ``q`` is a plain
``(heads, row)`` matrix — no head owns lanes — the scores contract the whole
row, and the value is the row's first ``value`` lanes, taken from the block
the key came in (no second tensor, no second DMA). It walks the same plan,
takes an optional ``keep`` mask over the positions (a learned selection
reaches it as data: a block is fetched whole and the rows the selection
left out are masked) and a flag a slot that says whether the new token's
own term joins its softmax.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret

NEG_INF = -1e30  # finite: a slot with nothing cached keeps exp() defined

# Rows of the plan, (3, slots): with the layer, (1,), the kernel's
# scalar-prefetch operands.
LIMIT, SOURCE, HOLD = range(3)
OWN = 3        # a fourth row, of the latent read's plan alone

SUBLANES = 8   # rows of a float32 tile: what Mosaic stacks and slices whole


def block_plan(position, bound: int, block: int):
    """The plan of a step that attends the cached positions ``< bound``
    in blocks of ``block``. Row LIMIT: ``min(position, bound)``, (slots,)
    int32 — the positions of a slot the step reads. A live slot (limit > 0)
    fetches from itself (SOURCE) the blocks up to HOLD, its last live one;
    a dead slot holds what the step before it left in VMEM — the last live
    slot's HOLD — and dead slots before any live one hold block 0 of the
    first live slot, which that slot starts with. The same for every
    layer of a step: XLA computes it once."""
    slots = position.shape[0]
    limit = jnp.minimum(position, bound)
    live = limit > 0
    last = jnp.maximum(-(-limit // block) - 1, 0)
    before = jax.lax.cummax(jnp.where(live, jnp.arange(slots), -1))
    source = jnp.where(before >= 0, before, jnp.argmax(live))
    hold = jnp.where(before >= 0, last[source], 0)
    return jnp.stack([limit, source, hold]).astype(jnp.int32)


def _pool_index(s, b, plan, layer):
    """Block index into a pool tensor for grid step (s, b)."""
    hold = plan[HOLD, s]
    block = jnp.where(plan[LIMIT, s] > 0, jnp.minimum(b, hold), hold)
    return layer[0], plan[SOURCE, s], block, 0


def _slot_index(s, b, plan, layer):
    return s, 0, 0


def _kernel(plan_ref, layer_ref, q_ref, k_new_ref, v_new_ref, k_ref, v_ref,
            out_ref, q_bd, acc, m, l, *, block: int, head_dim: int,
            scale: float):
    # k_new_ref, v_new_ref: (1, row) — the slot's new token; q_ref,
    # out_ref: (group, row), its query heads; k_ref, v_ref: (block, row).
    # Scratch, carried across a slot's blocks: q_bd, acc (heads, row); m, l
    # (heads, 1).
    heads, row = q_bd.shape
    group = q_ref.shape[0]
    # program_id is read at the top level: inside a pl.when branch it
    # escapes the trace in the interpreter (flash_attention.py).
    s, b = pl.program_id(0), pl.program_id(1)
    limit = plan_ref[LIMIT, s]

    def own_lanes():
        """(heads, row), True where the lane is one of the head's own.
        Built where it is used: a step over a dead block runs none of it."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (heads, row), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, row), 0)
        if group > 1:   # rows g * group .. (g + 1) * group - 1: K/V head g
            head = head // group
        first = head * head_dim
        return (lane >= first) & (lane < first + head_dim)

    def holds_line(j):
        """(heads, 1), True on the rows that hold line ``j`` of the group:
        rows ``g * group + j``."""
        return jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0) % group == j

    @pl.when(b == 0)
    def _init():
        if group % SUBLANES:   # no aligned piece to stack: rows pick lines
            q = jnp.zeros((heads, row), jnp.float32)
            for j in range(group):
                q = jnp.where(holds_line(j),
                              q_ref[j:j + 1, :].astype(jnp.float32), q)
        else:
            q = q_ref[...].astype(jnp.float32)
            if group > 1:   # K/V head g's query heads: rows g * group + j
                q = jnp.concatenate([q] * (heads // group), axis=0)
        q_bd[...] = jnp.where(own_lanes(), q, 0.0).astype(q_bd.dtype)
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    def accumulate(ragged: bool):
        k, v = k_ref[...], v_ref[...]
        # Float32 operands in float32; Mosaic takes no precision with
        # bfloat16 ones, whose products are exact as they are.
        precision = (jax.lax.Precision.HIGHEST if k.dtype == jnp.float32
                     else None)
        scores = jax.lax.dot_general(
            q_bd[...], k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale    # (heads, block)
        if ragged:
            # The slot's last block: what lies at or above its limit is
            # another sequence's or nothing's. The scores are masked; V's
            # rows are zeroed, since 0 x NaN is NaN.
            start = b * block
            cols = start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            rows = start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            scores = jnp.where(cols < limit, scores, NEG_INF)
            v = jnp.where(rows < limit, v, 0)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        shrink = jnp.exp(m_prev - m_new)
        l[...] = l[...] * shrink + p.sum(axis=-1, keepdims=True)
        m[...] = m_new
        # p (float32) x v, (heads, row): against a float32 V at HIGHEST;
        # against a narrower V as three terms of V's dtype that add up to
        # p — each product exact, the sums float32, no rounded weights.
        weighted = jnp.zeros(acc.shape, jnp.float32)
        for _ in range(1 if v.dtype == jnp.float32 else 3):
            term = p.astype(v.dtype)
            weighted += jax.lax.dot_general(
                term, v, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
            p = p - term.astype(jnp.float32)
        acc[...] = acc[...] * shrink + weighted

    pl.when((b + 1) * block <= limit)(partial(accumulate, False))
    pl.when((b * block < limit) & (limit < (b + 1) * block))(
        partial(accumulate, True))

    @pl.when(b == pl.num_programs(1) - 1)
    def _finish():
        # The new token's own key and value: one more term of the same
        # softmax (they are not in the pool yet). A slot that read nothing
        # has m = NEG_INF and l = 0: its new value comes back bit for bit.
        k_new = k_new_ref[...].astype(jnp.float32)
        v_new = v_new_ref[...].astype(jnp.float32)
        own = (q_bd[...].astype(jnp.float32) * k_new).sum(
            axis=-1, keepdims=True) * scale                 # (heads, 1)
        top = jnp.maximum(m[...], own)
        w_pool, w_own = jnp.exp(m[...] - top), jnp.exp(own - top)
        rows = ((acc[...] * w_pool + w_own * v_new)
                / (l[...] * w_pool + w_own))                # (heads, row)
        # each head keeps its own lanes of its row; a K/V head's lanes
        # hold its ``group`` query heads, one a line
        rows = jnp.where(own_lanes(), rows, 0.0)
        if group == 1:
            out_ref[...] = rows.sum(axis=0, keepdims=True).astype(
                out_ref.dtype)
        elif group % SUBLANES:
            for j in range(group):
                out_ref[j:j + 1, :] = jnp.where(holds_line(j), rows, 0.0).sum(
                    axis=0, keepdims=True).astype(out_ref.dtype)
        else:
            out_ref[...] = sum(
                rows[g:g + group] for g in range(0, heads, group)).astype(
                    out_ref.dtype)


@partial(jax.jit, static_argnames=("heads", "bound", "block", "scale",
                                   "interpret"))
def _pooled(q, k_new, v_new, k_pool, v_pool, layer, position, *, heads: int,
            bound: int, block: int, scale: float, interpret: bool):
    """Jitted on its own so that the layers of a step program share one
    traced and lowered kernel: ``layer`` is a value, not a constant."""
    slots, row = k_new.shape
    head_dim = q.shape[1] // heads
    kv_heads = row // head_dim
    group = heads // kv_heads
    # (slots, heads * head_dim) -> (slots, group, row): line j holds query
    # head g * group + j of every K/V head g, on g's lanes. One head a K/V
    # head: nothing moves, and the program is what it was before groups.
    if group > 1:
        q = q.reshape(slots, kv_heads, group, head_dim).swapaxes(1, 2)
    q = q.reshape(slots, group, row)
    plan = block_plan(position, bound, block)
    pool = pl.BlockSpec((None, None, block, row), _pool_index)
    per_slot = pl.BlockSpec((None, 1, row), _slot_index)
    per_group = pl.BlockSpec((None, group, row), _slot_index)
    out = pl.pallas_call(
        partial(_kernel, block=block, head_dim=head_dim, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, -(-bound // block)),
            in_specs=[per_group, per_slot, per_slot, pool, pool],
            out_specs=per_group,
            scratch_shapes=[pltpu.VMEM((heads, row), q.dtype),
                            pltpu.VMEM((heads, row), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((slots, group, row), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(plan, layer.reshape(1), q, k_new[:, None], v_new[:, None],
      k_pool, v_pool)
    if group > 1:
        out = out.reshape(slots, group, kv_heads, head_dim).swapaxes(1, 2)
    return out.reshape(slots, heads * head_dim)


def _latent_kernel(plan_ref, layer_ref, q_ref, new_ref, *rest, block: int,
                   value: int, scale: float, masked: bool):
    # q_ref: (heads, row); new_ref: (1, row) — the slot's new token;
    # keep_ref (``masked``): (1, block) float32, 0 where the position is
    # kept and NEG_INF where it is left out (added to the scores);
    # pool_ref: (block, row); out_ref: (heads, value). Scratch, carried
    # across a slot's blocks: acc (heads, value); m, l (heads, 1).
    if masked:
        keep_ref, pool_ref, out_ref, acc, m, l = rest
    else:
        (pool_ref, out_ref, acc, m, l), keep_ref = rest, None
    s, b = pl.program_id(0), pl.program_id(1)
    limit = plan_ref[LIMIT, s]

    @pl.when(b == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    def accumulate(ragged: bool):
        rows = pool_ref[...]
        precision = (jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32
                     else None)
        scores = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale    # (heads, block)
        v = rows[:, :value]
        if masked:
            scores = scores + keep_ref[...]    # 0 kept, NEG_INF left out
        if ragged:
            start = b * block
            cols = start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            at = start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            scores = jnp.where(cols < limit, scores, NEG_INF)
            v = jnp.where(at < limit, v, 0)    # 0 x NaN is NaN
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        if masked:
            # a head whose every position so far was left out would have
            # m_new at NEG_INF and exp(0) = 1 on positions it must not
            # read: held above NEG_INF, they underflow to 0
            m_new = jnp.maximum(m_new, 0.1 * NEG_INF)
        p = jnp.exp(scores - m_new)
        shrink = jnp.exp(m_prev - m_new)
        l[...] = l[...] * shrink + p.sum(axis=-1, keepdims=True)
        m[...] = m_new
        weighted = jnp.zeros(acc.shape, jnp.float32)
        for _ in range(1 if v.dtype == jnp.float32 else 3):
            term = p.astype(v.dtype)
            weighted += jax.lax.dot_general(
                term, v, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
            p = p - term.astype(jnp.float32)
        acc[...] = acc[...] * shrink + weighted

    pl.when((b + 1) * block <= limit)(partial(accumulate, False))
    pl.when((b * block < limit) & (limit < (b + 1) * block))(
        partial(accumulate, True))

    @pl.when(b == pl.num_programs(1) - 1)
    def _finish():
        new = new_ref[...].astype(jnp.float32)              # (1, row)
        own = (q_ref[...].astype(jnp.float32) * new).sum(
            axis=-1, keepdims=True) * scale                 # (heads, 1)
        own = jnp.where(plan_ref[OWN, s] > 0, own, NEG_INF)
        top = jnp.maximum(m[...], own)
        w_pool, w_own = jnp.exp(m[...] - top), jnp.exp(own - top)
        out_ref[...] = ((acc[...] * w_pool + w_own * new[:, :value])
                        / (l[...] * w_pool + w_own)).astype(out_ref.dtype)


def _keep_index(s, b, plan, layer):
    return s, 0, _pool_index(s, b, plan, layer)[2]


@partial(jax.jit, static_argnames=("value", "bound", "block", "scale",
                                   "interpret"))
def _latent(q, new, pool, layer, position, keep, own, *, value: int,
            bound: int, block: int, scale: float, interpret: bool):
    slots, heads, row = q.shape
    plan = jnp.concatenate([block_plan(position, bound, block),
                            own.astype(jnp.int32)[None]])
    masked = keep is not None
    per_slot = pl.BlockSpec((None, 1, row), _slot_index)
    in_specs = [pl.BlockSpec((None, heads, row), _slot_index), per_slot]
    operands = [q, new[:, None]]
    if masked:
        in_specs.append(pl.BlockSpec((None, 1, block), _keep_index))
        operands.append(jnp.where(keep != 0, 0.0, NEG_INF).astype(
            jnp.float32)[:, None])
    in_specs.append(pl.BlockSpec((None, None, block, row), _pool_index))
    return pl.pallas_call(
        partial(_latent_kernel, block=block, value=value, scale=scale,
                masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, -(-bound // block)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, heads, value), _slot_index),
            scratch_shapes=[pltpu.VMEM((heads, value), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, value), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="latent_attention",
    )(plan, layer.reshape(1), *operands, pool)


def latent_attention(q, new, pool, layer, position, *, value: int,
                     bound: int, block: int, scale: float, keep=None,
                     own=None, interpret: bool | None = None):
    """Attention of one new token a slot over rows every head shares.

    q: (slots, heads, row) in the pool's dtype — a head's query against the
    whole row; new: (slots, row), the new token's own row; pool: (layers,
    slots, length, row), whole; the value of a position is the first
    ``value`` lanes of its row. A slot reads ``layer``'s rows ``<
    min(position[slot], bound)`` — of those, where ``keep (slots, length)``
    is given, the ones it marks nonzero — and, unless ``own (slots,)`` says
    no, the new token's own term. ``scale`` multiplies the scores. Returns
    (slots, heads, value) in q's dtype. The order of the rows does not
    matter (a ring may be read as it lies)."""
    if not 0 < bound <= pool.shape[2] or not 0 < block <= pool.shape[2]:
        raise ValueError(f"bound {bound} and block {block} must lie within "
                         f"the pool's {pool.shape[2]} positions")
    if keep is not None and block % 128 and block != pool.shape[2]:
        raise ValueError(f"a masked read takes blocks of whole lane tiles of "
                         f"positions, not {block}")
    if own is None:
        own = jnp.ones(position.shape, jnp.int32)
    return _latent(q, new, pool, jnp.asarray(layer, jnp.int32),
                   position.astype(jnp.int32), keep, own, value=value,
                   bound=bound, block=block, scale=float(scale),
                   interpret=resolve_interpret("decode_attention", interpret))


def pooled_attention(q, k_new, v_new, k_pool, v_pool, layer, position, *,
                     heads: int, bound: int, block: int,
                     scale: float | None = None,
                     interpret: bool | None = None):
    """Attention of one new token a slot over ``layer``'s cached positions
    ``< min(position[slot], bound)`` and the new token itself.

    k_new, v_new: (slots, row) — the new token's key and value, ``row =
    kv_heads * head_dim``; q: (slots, heads * head_dim), its query heads,
    ``heads`` a multiple of the row's K/V heads and query head ``h`` reading
    K/V head ``h // (heads // kv_heads)``; q and k_new in the pool's dtype;
    k_pool, v_pool: (layers, slots, max_len, row), whole; layer: an int or an
    int32 scalar; position: (slots,) int32; ``block``: positions a grid
    step fetches, at most ``max_len``; ``scale``: what multiplies ``q . k``,
    ``head_dim ** -0.5`` when None. Returns q's shape and dtype: the softmax
    over [cached keys, the new key] of each head, times the values. A slot at position 0 reads nothing of the pool and returns
    its new value."""
    head_dim = q.shape[1] // heads
    if k_new.shape[1] % head_dim or heads % (k_new.shape[1] // head_dim):
        raise ValueError(f"{heads} query heads of {head_dim} do not group "
                         f"onto a row of {k_new.shape[1]}")
    if not 0 < bound <= k_pool.shape[2] or not 0 < block <= k_pool.shape[2]:
        raise ValueError(f"bound {bound} and block {block} must lie within "
                         f"the pool's {k_pool.shape[2]} positions")
    return _pooled(q, k_new, v_new, k_pool, v_pool,
                   jnp.asarray(layer, jnp.int32), position.astype(jnp.int32),
                   heads=heads, bound=bound, block=block,
                   scale=float(head_dim ** -0.5 if scale is None else scale),
                   interpret=resolve_interpret("decode_attention", interpret))
