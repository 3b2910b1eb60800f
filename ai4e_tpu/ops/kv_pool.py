"""The decode path's pool of cached rows — its layout and the operations on
it.

One preallocated buffer per tensor of rows a family declares
(``SlotSpec.rows`` of ``Rows``); for a family that keeps K and V::

    k, v : (layers, slots, max_len, kv_heads * head_dim)

and in general ``(layers, slots, length or max_len, width)``: a latent row
every head shares, an indexer's key, a window's ring of ``length`` rows are
declared in the same words (``models/dots3.py``).

A slot is a row of it (``runtime/decode.SlotPool`` hands slots out; the
device never reallocates per request), and one position of a slot is one
contiguous row of ``kv_heads x head_dim`` elements: whole lane tiles (1,024
float32, 2,048 or — two K/V heads under sixteen query heads — 512 bfloat16
in the configurations served: 4 KB or 1 KB), ``max_len`` on the sublanes,
nothing padded. ``layers`` counts the layers that keep K/V: a family whose
other layers keep a fixed-size state a slot declares that beside it
(``SlotSpec.state``; ``ops/state_pool.py`` holds it). A step writes a position as
that one row, and a kernel can take blocks of positions straight from the
pool; with ``head_dim`` minor (64 wide in float32) the chip laid ``max_len``
on the lanes, a position was a column through thousands of tiles, and no
Mosaic operand could hold the pool unpadded (CHANGES.md PR 25, PR 30).

Everything that knows this layout is here and in the kernel this file
calls (``ops/pallas/decode_attention.py``): the allocation, the decode
attention over the pool, the row a step writes, the block a prefill returns
and its insert. An LM family (``models/``) owns its block's own math —
norms, projections, positions, MLP or experts — and calls these;
``runtime/kvcache.py`` owns the compiled programs and asks here for shapes,
the insert and what a step read. A change of layout or of the read (a block
table, another block rule) is a change to these two files.

The decode read is a Pallas kernel (Mosaic on the chip, the interpreter
elsewhere: ``ops/pallas/lowering.resolve_interpret``), and so are a long
prompt's attention under a mask or a window and its index scores
(``prompt_attention``, ``prompt_index_scores``:
``ops/pallas/flash_attention.py``); the rest is ``jax.numpy``. Scopes name the device side for the trace's readers
(``benchmark/lib/xplane_spans.py``) — they are metadata and change no
program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from typing import Any, NamedTuple

from .pallas.decode_attention import latent_attention, pooled_attention
from .pallas.flash_attention import (
    index_scores as flash_index_scores,
    prompt_attention as flash_prompt_attention)
from .pallas.select_top import select_top as pallas_select_top

# What one grid step of the decode read fetches of each tensor: 256
# positions of a 4 KB row, 1,024 of a 1 KB one. Smaller, and the grid's
# ~0.35 us a step shows; larger, and a short sequence pays for positions it
# has not written.
READ_BLOCK_BYTES = 1 << 20


class Rows(NamedTuple):
    """One tensor of rows a slot keeps, a row a position: ``layers`` layers
    of rows ``width`` wide in ``dtype``. ``length``: how many a slot holds —
    None, the cache's whole length (a row a position of the sequence), or a
    ring of that many: position ``p`` lies at row ``p % length`` and a step
    reads the ``min(p, length)`` last ones, in any order. ``kind``: the label
    its bytes are counted under (``ai4e_decode_cache_bytes_total``).
    ``select``: the family's read keeps at most that many of the positions
    it scores (a learned selection); None: it keeps all it reads. ``whole``:
    the step reads every slot's first ``bound`` positions in ``jax.numpy``,
    not the blocks a slot has written through the kernel. ``every``: the
    tensor keeps one row every that many positions — a block of ``every``
    positions' pooled row, position ``p``'s block at row ``p // every``: a
    slot holds ``ceil(length / every)`` rows, a prefill's block is a row a
    block of the prompt, a step writes row ``p // every`` (what it writes
    before the block closes at ``p % every == every - 1`` is overwritten by
    the next step and read by none) and reads the rows of the blocks closed
    under ``bound``."""

    name: str
    layers: int
    width: int
    dtype: Any
    length: int | None = None
    kind: str = "kv"
    select: int | None = None
    whole: bool = False
    every: int = 1


class SlotSpec(NamedTuple):
    """Everything one slot of a family's cache holds — what its
    ``cache_spec()`` declares and ``runtime/kvcache.py`` allocates. ``rows``:
    the tensors that keep a row a position (``Rows``), in the order the
    family's ``prefill`` returns their blocks and its ``decode_step`` takes
    and returns them; K and V of every layer that keeps them are the
    two-tensor case (``kv_slot``). ``state``: fixed-size tensors, each
    ``(name, shape a slot, dtype)`` (``ops/state_pool.py``); none for a
    family whose every layer keeps rows. ``live``: the names of the state
    tensors its step advances at the live slots only
    (``state_pool.update_live``); a tensor not named there the step reads
    and writes at every slot."""

    rows: tuple
    state: tuple = ()
    live: tuple = ()


def kv_slot(layers: int, kv_heads: int, head_dim: int, dtype,
            state: tuple = (), live: tuple = ()) -> SlotSpec:
    """The declaration of a family that keeps K and V of ``layers`` layers,
    ``kv_heads x head_dim`` a row each."""
    return SlotSpec((Rows("k", layers, kv_heads * head_dim, dtype),
                     Rows("v", layers, kv_heads * head_dim, dtype)),
                    tuple(state), tuple(live))


def pool_shape(rows: Rows, slots: int, max_len: int) -> tuple:
    """Shape of the pool tensor of one ``Rows`` declaration."""
    return (rows.layers, slots, -(-(rows.length or max_len) // rows.every),
            rows.width)


def rows_nbytes(spec: tuple, slots: int, max_len: int) -> int:
    """Resident bytes of the pool tensors of ``SlotSpec.rows``."""
    return sum(int(np.prod(pool_shape(rows, slots, max_len)))
               * np.dtype(rows.dtype).itemsize for rows in spec)


def allocate(shape: tuple, dtype):
    """One clean pool tensor (K or V). A caller that replaces a pool drops
    the old one first: while it lives, building the new one holds three
    pool tensors on the device at once, which would be the allocator's
    peak of the whole worker."""
    return jnp.zeros(shape, dtype)


def read_block(shape: tuple, dtype) -> int:
    """Positions a grid step of ``decode_attention`` fetches from a pool of
    ``shape``: a block of one tensor within ``READ_BLOCK_BYTES``, on whole
    sublane tiles of any dtype, or the pool's whole length."""
    fit = max(READ_BLOCK_BYTES // (shape[-1] * np.dtype(dtype).itemsize), 1)
    # whole lane tiles of positions where a row allows (a mask over the
    # positions lies on the lanes), else whole sublane tiles
    return min(fit - fit % 128 or fit - fit % 32 or fit, shape[2])


def positions_read(shape: tuple, dtype, position, active, bound: int) -> int:
    """Cached positions plus new tokens one step's attention reads, a
    layer: every block ``decode_attention`` fetches, whole — a slot at
    ``position`` p the blocks under ``min(p, bound)``, a slot at 0 none —
    and each active slot's own new token. ``position``, ``active``: per
    slot, host values."""
    block = read_block(shape, dtype)
    bound = min(bound, shape[2])
    return sum(-(-min(p, bound) // block) * block for p in position) + sum(
        map(bool, active))


def step_reads(spec: tuple, slots: int, max_len: int, position, active,
               bound: int) -> tuple:
    """What one step reads and writes of the tensors of ``SlotSpec.rows``,
    from the host's positions: ``(attended, bytes by kind, selected)``.
    ``attended``: the positions its attention reads of the first tensor, a
    layer (``positions_read``). Bytes: of each tensor the rows read — the
    blocks the kernel fetches (``positions_read``), or every slot's first
    ``bound`` where it is read ``whole`` (of a tensor that keeps a row
    ``every`` positions, the rows of the blocks under ``bound``) — and the
    one row a live slot writes, every layer, summed under the tensor's
    ``kind``. ``selected``: the positions the softmax kept, a layer, where a
    tensor declares a selection; else None. A selection over keys pooled
    ``every`` positions at a time (another tensor of the spec) keeps whole
    blocks and, always, the query's own block up to the query, which takes
    one of the places: a live slot at ``p`` keeps ``min(p − p % every,
    select − every) + p % every + 1`` — ``min(p + 1, select)`` where no
    tensor is pooled."""
    block = max(rows.every for rows in spec)
    live = sum(map(bool, active))
    attended, selected, nbytes = None, None, {}
    for rows in spec:
        shape = pool_shape(rows, slots, max_len)
        read = (slots * min(-(-bound // rows.every), shape[2]) + live
                if rows.whole
                else positions_read(shape, rows.dtype, position, active,
                                    bound))
        attended = read if attended is None else attended
        row = rows.layers * rows.width * np.dtype(rows.dtype).itemsize
        nbytes[rows.kind] = nbytes.get(rows.kind, 0) + row * (read + live)
        if rows.select:
            selected = sum(min(p - p % block, rows.select - block)
                           + p % block + 1
                           for p, on in zip(position, active) if on)
    return attended, nbytes, selected


def prefill_pairs(spec: tuple, n: int) -> dict:
    """(query, key) pairs the attention of a prompt of ``n`` tokens is
    over, a layer, by kind: causal pairs ``n (n + 1) / 2`` under a tensor's
    ``kind``; a ring of ``length`` keeps the ``length + 1`` last keys of a
    query (the query's own among them), and a selection at most ``select``
    of them, counted as ``selected`` (what it scored is another tensor's);
    against a tensor that keeps a row ``every`` positions a query at ``t``
    meets the ``t // every`` blocks closed before it."""
    def pairs(cap):
        full = min(n, cap)
        return full * (full + 1) // 2 + (n - full) * cap

    out = {}
    for rows in spec:
        if rows.select:
            out["selected"] = pairs(rows.select)
        elif rows.every > 1:
            blocks, rest = divmod(n, rows.every)
            out[rows.kind] = (rows.every * blocks * (blocks - 1) // 2
                              + rest * blocks)
        else:
            out[rows.kind] = pairs(rows.length + 1 if rows.length else n)
    return out


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def prefill_attention(q, k, v, mask, scale: float | None = None):
    """Materialised causal attention over a padded prompt. q: (B, P, H,
    hd); k, v: (B, P, KVH, hd), query head ``h`` reading K/V head ``h // (H
    // KVH)``; mask: (B, P), True on real tokens. Float32 scores and
    softmax, the weights cast to ``v``'s dtype for the value product.
    ``scale`` multiplies ``q . k`` where a family hands one over; without
    it the scores are divided by ``sqrt(hd)``. Returns (B, P, H, hd) in
    ``q``'s dtype."""
    p = q.shape[1]

    def scaled(scores):
        return (scores / np.sqrt(q.shape[-1]) if scale is None
                else scores * scale)

    with jax.named_scope("attention"):
        allowed = (jnp.tril(jnp.ones((p, p), bool))[None, None]
                   & mask[:, None, None, :])
        if q.shape[2] != k.shape[2]:
            grouped = (*q.shape[:2], k.shape[2], -1, q.shape[3])
            scores = scaled(_dot("bqhgd,bkhd->bhgqk", q.reshape(grouped), k))
            w = jax.nn.softmax(jnp.where(allowed[:, :, None], scores, -1e30),
                               axis=-1)
            return _dot("bhgqk,bkhd->bqhgd", w.astype(v.dtype),
                        v).reshape(q.shape).astype(q.dtype)
        scores = scaled(_dot("bqhd,bkhd->bhqk", q, k))
        w = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return _dot("bhqk,bkhd->bqhd", w.astype(v.dtype), v).astype(q.dtype)


# Queries a block of ``query_blocks``: what a block keeps of its work against
# every key of the prompt is ``QUERY_BLOCK x keys`` — 12.8 MB of float32 index
# scores at 12.5k keys.
QUERY_BLOCK = 256


# The scores of a ``select_top`` call from which the selection is the kernel's:
# under it XLA keeps the ``jax.numpy`` form's key image on chip (a step's
# 16 slots x 12,545 positions are 0.8 MB).
SELECT_KERNEL_BYTES = 1 << 20


def select_top(scores, valid, k: int):
    """The exact top-``k`` of ``scores (..., N)`` (float32) among the
    positions ``valid (..., N)`` marks, as a mask ``(..., N)`` of
    ``valid``'s dtype (a caller that wants bytes hands bytes in): the ``k``
    largest, a tie to the lower index; every valid position where fewer than
    ``k`` are. No sort and no gather: the ``k``-th largest value is found
    bit by bit on the scores' order-preserving integer image (32 counts),
    then the ties at it are ranked by a running count — a prompt's block of
    queries in ``pallas.select_top``, which holds the image in VMEM, a
    step's few rows as ``jax.numpy``."""
    n = scores.shape[-1]
    if k >= n:
        return valid
    if scores.size * 4 >= SELECT_KERNEL_BYTES:
        return pallas_select_top(
            scores.astype(jnp.float32).reshape(-1, n),
            jnp.broadcast_to(valid, scores.shape).reshape(-1, n).astype(
                jnp.int8), k).reshape(scores.shape).astype(valid.dtype)
    kind, valid = valid.dtype, valid.astype(bool)
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    sign = jnp.uint32(1 << 31)
    bits = jnp.where(bits == sign, jnp.uint32(0), bits)      # -0.0 is 0.0
    key = jnp.where(bits >= sign, ~bits, bits | sign)
    key = jnp.where(valid, key, jnp.uint32(0))   # under every valid key

    def bit(i, found):
        trial = found | (sign >> i.astype(jnp.uint32))
        count = (key >= trial).sum(axis=-1, keepdims=True)
        return jnp.where(count >= k, trial, found)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros((*scores.shape[:-1], 1), jnp.uint32))
    above = key > kth
    ties = (key == kth) & valid
    room = k - above.sum(axis=-1, keepdims=True)
    return (valid & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))
            ).astype(kind)


def query_blocks(fn, p: int, block: int = QUERY_BLOCK):
    """``fn(at, q_pos (B,), k_pos (P,)) -> (B, ...)`` over the ``p`` queries
    of a prompt in blocks of ``block``, against all ``p`` keys: ``(p, ...)``."""
    block = math.gcd(p, block)
    out = jax.lax.map(
        lambda i: fn(i * block, i * block + jnp.arange(block), jnp.arange(p)),
        jnp.arange(p // block))
    return out.reshape(p, *out.shape[2:])


def prompt_index_scores(iq, ik, w, first):
    """The index scores of a block of a prompt's queries, from position
    ``first`` (traced or not), against every key of the prompt: ``I[t, s] =
    sum_j w[t, j] * relu(iq[j, t] . ik[s])`` in float32 (``pallas
    .flash_attention.index_scores``). iq: (J, B, d); ik: (P, d); w: (B, J).
    Returns (B, P); keys in blocks wholly after the last query read 0 (they
    are not scored: the caller's causal mask leaves them out)."""
    with jax.named_scope("indexer"):
        return flash_index_scores(iq, ik, w, first)


def prompt_attention(q, k, v, scale: float, mask=None,
                     window: int | None = None,
                     interpret: bool | None = None):
    """Causal attention of one padded prompt whose scores never leave the
    chip's fast memory, at any length (``pallas.flash_attention
    .prompt_attention``: one kernel, blocks of queries against the blocks of
    keys under the diagonal). q, k: (P, H, dqk) — a key's and a value's
    widths may differ —, v: (P, H, dv). A query at ``t`` reads the keys ``s
    <= t``: of them, where ``window`` is given, the ``window`` last ones
    (itself among them) — a banded read —, and, where ``mask (P, P)`` is,
    those it marks nonzero — a selection, one byte a pair for every head.
    Float32 scores and softmax, the weights cast to ``v``'s dtype for the
    value product. A grid step carries a few of the ``H`` heads (up to four
    that the kernel's fast memory holds at these widths) against one block
    of the mask, which is fetched and unpacked once for them; the grid is as long as
    the blocks the queries read — under the diagonal, inside the band — and
    a head's output is the same whatever group it ran in. Returns (P, H, dv)
    in ``v``'s dtype."""
    with jax.named_scope("attention"):
        heads_first = [jnp.swapaxes(a, 0, 1) for a in (q, k, v)]
        return jnp.swapaxes(flash_prompt_attention(
            *heads_first, scale=scale, mask=mask, window=window,
            interpret=interpret), 0, 1)


def latent_decode_attention(q, new, pool, layer: int, position, *,
                            value: int, bound: int, scale: float,
                            keep=None, own=None,
                            interpret: bool | None = None):
    """One layer's attention of one decode step over rows every head shares
    (latent attention in its absorbed form). q: (S, H, row); new: (S, row),
    the new token's own row; pool: (layers, slots, length, row), whole and
    never rewritten; a position's value is the first ``value`` lanes of its
    row. A slot reads its ``min(position, bound)`` first rows — of them,
    where ``keep (S, bound)`` (bool) is given, those it marks — and, unless
    ``own (S,)`` says no, the new token's own term. Returns (S, H, value) in
    ``q``'s dtype. All of it is ``pallas.decode_attention.latent_attention``."""
    block = read_block(pool.shape, pool.dtype)
    if keep is not None:
        # whole blocks of positions: what lies past ``bound`` is left out
        keep = jnp.pad(keep, ((0, 0), (0, -keep.shape[1] % block)))
    with jax.named_scope("attention"):
        return latent_attention(
            q.astype(pool.dtype), new.astype(pool.dtype), pool, layer,
            position, value=value, bound=bound, block=block, scale=scale,
            keep=keep, own=own, interpret=interpret).astype(q.dtype)


def prompt_block(rows):
    """A prefill's K (or V) as the block ``insert_block`` takes: ``rows`` —
    per-layer (B, P, H, hd), as ``prefill_attention`` reads them — become
    (layers, B, P, H * hd), rank-matched to the pool: a stack and a
    reshape, nothing moves."""
    rows = jnp.stack(rows)
    return rows.reshape(*rows.shape[:3], -1)


def insert_block(pools: tuple, blocks: tuple, slot) -> tuple:
    """Land one prompt's blocks (``prompt_block`` with B = 1), a tensor of
    the declaration each, at the start of ``slot``'s rows — ``slot`` may be
    traced: one program a block length, any slot. Blocks are rank-matched to
    the pool, so one dynamic_update_slice a tensor lands the whole prompt. A
    ring's block is the whole ring, each position at its row already; the
    block of a tensor that keeps a row ``every`` positions holds a row a
    block of the prompt, which is where they lie."""
    zero = (0, slot, 0, 0)
    with jax.named_scope("cache_insert"):
        return tuple(jax.lax.dynamic_update_slice(pool, block, zero)
                     for pool, block in zip(pools, blocks))


def decode_attention(q, k_new, v_new, k_pool, v_pool, layer: int, position,
                     bound: int | None = None, interpret: bool | None = None,
                     scale: float | None = None):
    """One layer's attention of one decode step: one new token per slot
    against the pool. q: (S, H, hd), k_new, v_new: (S, KVH, hd) — the new
    token's, query head ``h`` reading K/V head ``h // (H // KVH)``; k_pool,
    v_pool: the pool, read and never rewritten: a slot's positions
    ``< position`` hold its sequence so far; position: (S,) — the cache
    index the new token belongs at. The new token's own key and value
    enter the softmax as one more term beside the cached ones, so
    attention needs no updated cache. ``bound`` (a Python int, static under
    jit; default the whole length) cuts the read to the cached positions
    ``< bound``: the same result, to the order of a float32 sum, for any
    bound ``>=`` the largest position of a slot whose output is read.
    ``scale`` multiplies ``q . k`` (``head_dim ** -0.5`` when None). Float32
    scores and accumulation, the weights never rounded. Returns
    (S, H, hd) in ``q``'s dtype.

    All of it is ``pallas.decode_attention.pooled_attention``: per slot,
    blocks of ``read_block`` positions up to that slot's position and no
    further — a slot at position 0 reads nothing — then the new token's own
    term, so ``bound`` only trims the kernel's grid. The kernel is handed the pool tensors whole,
    with ``layer`` as a value: a slice of the pool (``k_pool[layer]``) that
    reaches a custom call is a copy of that layer's K and V, every layer,
    every step (tests/test_tpu_aot_compile.py holds the compiled programs
    to that)."""
    slots, heads, head_dim = q.shape
    bound = k_pool.shape[2] if bound is None else bound
    with jax.named_scope("attention"):
        return pooled_attention(
            q.reshape(slots, -1).astype(k_pool.dtype),
            k_new.reshape(slots, -1).astype(k_pool.dtype),
            v_new.reshape(slots, -1), k_pool, v_pool, layer, position,
            heads=heads, bound=bound,
            block=read_block(k_pool.shape, k_pool.dtype), scale=scale,
            interpret=interpret).reshape(q.shape).astype(q.dtype)


def write_rows(pools: tuple, rows: tuple, position,
               every: tuple | None = None) -> tuple:
    """Store one decode step's new rows: ``rows[i]`` is the per-layer list
    of (S, ...) the step made for the tensor ``pools[i]``, ``position``
    (S,) — the row of each slot the new ones land at (a ring's caller
    hands ``position % length``). ``every[i]``, where given: the tensor's
    ``Rows.every`` — its row of a slot is ``position // every[i]``.

    One row per slot, all layers at once, written where the pool already
    lives: ``layers`` contiguous rows of whole lane tiles a slot and a
    tensor. A Python loop of dynamic_update_slice on purpose: a scatter
    (``.at[].set``), a vmap or a fori_loop of the same writes makes XLA:TPU
    re-lay or copy the whole pool every step (CHANGES.md PR 25 has the
    compiled programs side by side). A position past the last row is
    clamped onto it, not dropped: the engine retires a sequence before it
    gets there."""
    with jax.named_scope("cache_update"):
        pools = list(pools)
        rows = [jnp.stack(new).reshape(len(new), position.shape[0], 1,
                                       pool.shape[-1])
                for pool, new in zip(pools, rows)]
        at = [position if step == 1 else position // step
              for step in every or (1,) * len(pools)]
        for slot in range(position.shape[0]):
            for i, (pool, new) in enumerate(zip(pools, rows)):
                pools[i] = jax.lax.dynamic_update_slice(
                    pool, new[:, slot:slot + 1], (0, slot, at[i][slot], 0))
    return tuple(pools)
