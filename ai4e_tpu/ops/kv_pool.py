"""The decode path's K/V pool — its layout and the operations on it.

One preallocated buffer per tensor::

    k, v : (layers, slots, heads, max_len, head_dim)

A slot is a row of it (``runtime/decode.SlotPool`` hands slots out; the
device never reallocates per request). Everything that knows this layout
is here: the allocation, the view a layer's attention reads, the decode
attention over that view, the row a step writes, the block a prefill
returns and its insert. An LM family (``models/``) owns its block's own
math — norms, projections, positions, MLP or experts — and calls these;
``runtime/kvcache.py`` owns the compiled programs and asks here for
shapes and the insert. A change of layout or of the read (a per-slot
kernel, a contiguous row, a block table) is a change to this file.

Pure ``jax.numpy``; scopes name the device side for the trace's readers
(``benchmark/lib/xplane_spans.py``) — they are metadata and change no
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def pool_shape(spec: tuple, slots: int, max_len: int) -> tuple:
    """Shape of each pool tensor for a model whose ``cache_spec()`` gives
    ``spec = (layers, heads, head_dim)``."""
    layers, heads, head_dim = spec
    return layers, slots, heads, max_len, head_dim


def allocate(shape: tuple, dtype):
    """One clean pool tensor (K or V). A caller that replaces a pool drops
    the old one first: while it lives, building the new one holds three
    pool tensors on the device at once, which would be the allocator's
    peak of the whole worker."""
    return jnp.zeros(shape, dtype)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def prefill_attention(q, k, v, mask):
    """Materialised causal attention over a padded prompt. q, k, v:
    (B, P, H, hd); mask: (B, P), True on real tokens. Float32 scores and
    softmax, the weights cast to ``v``'s dtype for the value product.
    Returns (B, P, H, hd) in ``q``'s dtype."""
    p = q.shape[1]
    with jax.named_scope("attention"):
        scores = _dot("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        allowed = (jnp.tril(jnp.ones((p, p), bool))[None, None]
                   & mask[:, None, None, :])
        w = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return _dot("bhqk,bkhd->bqhd", w.astype(v.dtype), v).astype(q.dtype)


def prompt_block(rows):
    """A prefill's K (or V) as the block ``insert_block`` takes: ``rows`` —
    per-layer (B, P, H, hd), as ``prefill_attention`` reads them — become
    (layers, B, H, P, hd), rank-matched to the pool."""
    return jnp.stack(rows).transpose(0, 1, 3, 2, 4)


def insert_block(k_pool, v_pool, k_block, v_block, slot):
    """Land one prompt's blocks (``prompt_block`` with B = 1) at the start
    of ``slot``'s rows — ``slot`` may be traced: one program a block length,
    any slot. Blocks are rank-matched to the pool, so one
    dynamic_update_slice a tensor lands the whole prompt."""
    zero = (0, slot, 0, 0, 0)
    with jax.named_scope("cache_insert"):
        return (jax.lax.dynamic_update_slice(k_pool, k_block, zero),
                jax.lax.dynamic_update_slice(v_pool, v_block, zero))


def decode_attention(q, k_new, v_new, k_pool, v_pool, layer: int, position,
                     bound: int | None = None):
    """One layer's attention of one decode step: one new token per slot
    against the pool. q, k_new, v_new: (S, H, hd) — the new token's;
    k_pool, v_pool: the pool, read and never rewritten: a slot's positions
    ``< position`` hold its sequence so far; position: (S,) — the cache
    index the new token belongs at. The new token's own key and value
    enter the softmax as one more term beside the cached ones, so
    attention needs no updated cache. ``bound`` (a Python int, static under
    jit; default the whole length) cuts the read to the cached positions
    ``< bound``: the same result, to the order of a float32 sum, for any
    bound ``>=`` the largest position of a slot whose output is read.
    Float32 scores and accumulation; the weights are cast to the cache's
    dtype for the value product. Returns (S, H, hd) in ``q``'s dtype."""
    # ONE static slice a tensor, layer and bound at once: XLA:TPU fuses it
    # into the attention's reads. A cut of the layer's view
    # (``k_pool[layer][:, :, :bound]``) costs a copy of every layer's K and
    # V (tests/test_tpu_aot_compile.py).
    k_view = k_pool[layer, :, :, :bound]  # (S, H, L, hd)
    v_view = v_pool[layer, :, :, :bound]
    length = k_view.shape[2]
    with jax.named_scope("attention"):
        scale = 1.0 / np.sqrt(q.shape[-1])
        scores = _dot("shd,shld->shl", q, k_view) * scale
        valid = (jnp.arange(length)[None, :]
                 < position[:, None])  # keys before the new token
        scores = jnp.where(valid[:, None, :], scores, -1e30)
        own = _dot("shd,shd->sh", q, k_new) * scale
        # softmax over [cached keys, the new token's key], by hand: the
        # new key is not in the cache yet.
        top = jnp.maximum(scores.max(axis=-1), own)
        w = jnp.exp(scores - top[..., None])
        w_own = jnp.exp(own - top)
        return ((_dot("shl,shld->shd", w.astype(v_view.dtype), v_view)
                 + w_own[..., None] * v_new.astype(jnp.float32))
                / (w.sum(axis=-1) + w_own)[..., None]).astype(q.dtype)


def write_rows(k_pool, v_pool, k_rows, v_rows, position):
    """Store one decode step's K/V: ``k_rows``/``v_rows`` are per-layer lists
    of (S, H, hd), ``position`` (S,).

    One row per slot, all layers at once, written where the pool already
    lives. A Python loop of dynamic_update_slice on purpose: a scatter
    (``.at[].set``), a vmap or a fori_loop of the same writes makes XLA:TPU
    re-lay or copy the whole pool every step (CHANGES.md PR 25 has the
    compiled programs side by side). A position past the last row is
    clamped onto it, not dropped: the engine retires a sequence before it
    gets there."""
    with jax.named_scope("cache_update"):
        k_rows = jnp.stack(k_rows)[:, :, :, None, :]  # (layers, S, H, 1, hd)
        v_rows = jnp.stack(v_rows)[:, :, :, None, :]
        for slot in range(position.shape[0]):
            at = (0, slot, 0, position[slot], 0)
            k_pool = jax.lax.dynamic_update_slice(
                k_pool, k_rows[:, slot:slot + 1], at)
            v_pool = jax.lax.dynamic_update_slice(
                v_pool, v_rows[:, slot:slot + 1], at)
    return k_pool, v_pool
