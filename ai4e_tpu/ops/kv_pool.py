"""The decode path's K/V pool — its layout and the operations on it.

One preallocated buffer per tensor::

    k, v : (layers, slots, max_len, kv_heads * head_dim)

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
elsewhere: ``ops/pallas/lowering.resolve_interpret``); the rest is
``jax.numpy``. Scopes name the device side for the trace's readers
(``benchmark/lib/xplane_spans.py``) — they are metadata and change no
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from typing import Any, NamedTuple

from .pallas.decode_attention import pooled_attention

# What one grid step of the decode read fetches of each tensor: 256
# positions of a 4 KB row, 1,024 of a 1 KB one. Smaller, and the grid's
# ~0.35 us a step shows; larger, and a short sequence pays for positions it
# has not written.
READ_BLOCK_BYTES = 1 << 20


class SlotSpec(NamedTuple):
    """Everything one slot of a family's cache holds — what its
    ``cache_spec()`` declares and ``runtime/kvcache.py`` allocates: the K/V
    of ``kv`` = (layers that keep K/V, K/V heads, head_dim) in ``dtype``,
    and ``state``: fixed-size tensors, each ``(name, shape a slot, dtype)``
    (``ops/state_pool.py``); none for a family whose every layer keeps
    K/V. ``live``: the names of the state tensors its step advances at the
    live slots only (``state_pool.update_live``); a tensor not named there
    the step reads and writes at every slot."""

    kv: tuple
    dtype: Any
    state: tuple = ()
    live: tuple = ()


def pool_shape(spec: tuple, slots: int, max_len: int) -> tuple:
    """Shape of each pool tensor for a model whose ``cache_spec().kv`` is
    ``spec = (layers, kv_heads, head_dim)``."""
    layers, heads, head_dim = spec
    return layers, slots, max_len, heads * head_dim


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
    return min(fit - fit % 32 or fit, shape[2])


def positions_read(shape: tuple, dtype, position, active, bound: int) -> int:
    """Cached positions plus new tokens one step's attention reads, a
    layer: every block ``decode_attention`` fetches, whole — a slot at
    ``position`` p the blocks under ``min(p, bound)``, a slot at 0 none —
    and each active slot's own new token. ``position``, ``active``: per
    slot, host values."""
    block = read_block(shape, dtype)
    return sum(-(-min(p, bound) // block) * block for p in position) + sum(
        map(bool, active))


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


def prompt_block(rows):
    """A prefill's K (or V) as the block ``insert_block`` takes: ``rows`` —
    per-layer (B, P, H, hd), as ``prefill_attention`` reads them — become
    (layers, B, P, H * hd), rank-matched to the pool: a stack and a
    reshape, nothing moves."""
    rows = jnp.stack(rows)
    return rows.reshape(*rows.shape[:3], -1)


def insert_block(k_pool, v_pool, k_block, v_block, slot):
    """Land one prompt's blocks (``prompt_block`` with B = 1) at the start
    of ``slot``'s rows — ``slot`` may be traced: one program a block length,
    any slot. Blocks are rank-matched to the pool, so one
    dynamic_update_slice a tensor lands the whole prompt."""
    zero = (0, slot, 0, 0)
    with jax.named_scope("cache_insert"):
        return (jax.lax.dynamic_update_slice(k_pool, k_block, zero),
                jax.lax.dynamic_update_slice(v_pool, v_block, zero))


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


def write_rows(k_pool, v_pool, k_rows, v_rows, position):
    """Store one decode step's K/V: ``k_rows``/``v_rows`` are per-layer lists
    of (S, H, hd), ``position`` (S,).

    One row per slot, all layers at once, written where the pool already
    lives: ``layers`` contiguous rows of whole lane tiles a slot and a
    tensor. A Python loop of dynamic_update_slice on purpose: a scatter
    (``.at[].set``), a vmap or a fori_loop of the same writes makes XLA:TPU
    re-lay or copy the whole pool every step (CHANGES.md PR 25 has the
    compiled programs side by side). A position past the last row is
    clamped onto it, not dropped: the engine retires a sequence before it
    gets there."""
    with jax.named_scope("cache_update"):
        rows = (len(k_rows), position.shape[0], 1, k_pool.shape[-1])
        k_rows = jnp.stack(k_rows).reshape(rows)
        v_rows = jnp.stack(v_rows).reshape(rows)
        for slot in range(position.shape[0]):
            at = (0, slot, position[slot], 0)
            k_pool = jax.lax.dynamic_update_slice(
                k_pool, k_rows[:, slot:slot + 1], at)
            v_pool = jax.lax.dynamic_update_slice(
                v_pool, v_rows[:, slot:slot + 1], at)
    return k_pool, v_pool
