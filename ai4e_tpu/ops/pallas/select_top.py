"""Pallas TPU kernel: the exact top-``k`` of each row of a block of float32
scores among the positions a mask marks, as a one-byte mask — the scores read
from HBM once, everything else where they lie.

A row's ``k``-th largest score is found bit by bit on the scores'
order-preserving integer image: 32 counts of ``key >= trial`` over the row;
then the ties at it are ranked to the lower index by a running count along
the row. As ``jax.numpy`` on a prompt's ``(256, 12,288)`` block of queries
that running count is a ``reduce-window`` with four layout copies about it
and each count a pass of its own: 308 µs a block, two thirds of it the
ranking (``PERF.md`` section 6, PR 50). Here a grid step holds ``ROW_TILE``
rows in VMEM:

* the image is built once, a chunk of columns at a time, as a signed int32
  (``-0.0`` as ``0.0``; a negative float's magnitude bits turned, so that
  integer order is the floats'; a position the mask leaves out at the least
  integer — under every marked key but a NaN's all-ones pattern, which the
  last pass tells apart by the mask itself);
* the 32 counts run over the chunks up to the tile's last marked column only
  (``last``, from the mask, one scalar a tile: a causal block of queries
  never looks past its own diagonal) — a compare, a select and an add a
  vector register, the rows' counts kept on every lane;
* the count at the last trial that failed is the number of keys above the
  ``k``-th — that trial is the ``k``-th key plus one — so no pass counts
  them again;
* the ties are ranked by one product a lane tile on the MXU — the tile's 0/1
  ties against an upper triangle of ones beside a square of ones: their
  running count within the tile, and the tile's total on every lane, exact
  in any precision — and the mask leaves as int8.

The next tile's scores and mask arrive while this one is searched (one grid
axis, every block whole rows). The counts are the kernel: 96 vector
operations a register of keys, about a cycle a register a count on a v5e
(PR 50's chip runs: a compare of three trials a pass, or the counts as a
product with a column of ones, both read slower).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret

ROW_TILE = 128   # rows a grid step (an int8 tile is 32 rows)
MAX_CHUNK = 1024  # columns a step of the kernel's loops, at most
LANES = 128

# Beyond the blocks: Mosaic's own scratch.
VMEM_HEADROOM_BYTES = 8 << 20

_LEAST = -(1 << 31)


def vmem_bytes(n: int) -> int:
    """What one call holds in VMEM at ``n`` columns: a tile's scores, its
    mask and its result double-buffered, the image once, and the headroom —
    also the limit the call asks Mosaic for."""
    return ROW_TILE * n * (2 * 4 + 2 * 1 + 2 * 1 + 4) + VMEM_HEADROOM_BYTES


def _kernel(last_ref, scores_ref, valid_ref, out_ref, key_ref, *, k: int,
            chunk: int):
    rows, n = scores_ref.shape
    chunks = last_ref[pl.program_id(0)]   # those that hold a marked column
    least = jnp.int32(_LEAST)

    def at(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def lane_tiles(*wide):
        return zip(*([a[:, t:t + LANES] for t in range(0, chunk, LANES)]
                     for a in wide))

    def image(c, _):
        bits = jax.lax.bitcast_convert_type(scores_ref[:, at(c)], jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
        key = jnp.where(bits == least, 0, key)                # -0.0 is 0.0
        marked = valid_ref[:, at(c)].astype(jnp.int32) != 0
        key_ref[:, at(c)] = jnp.where(marked, key, least)
        return 0

    jax.lax.fori_loop(0, chunks, image, 0)

    def bit(i, carry):
        # ``found``: the k-th key's bits so far, as the unsigned image (the
        # signed one with its top bit turned); ``above``: the count at the
        # last trial that failed
        found, above = carry
        trial = found | jax.lax.shift_left(jnp.int32(1), 31 - i)
        signed = trial ^ least

        def count(c, acc):
            for (key,) in lane_tiles(key_ref[:, at(c)]):
                acc = acc + jnp.where(key >= signed, 1, 0)
            return acc

        acc = jax.lax.fori_loop(0, chunks, count,
                                jnp.zeros((rows, LANES), jnp.int32))
        total = jnp.broadcast_to(acc.sum(axis=-1, keepdims=True),
                                 (rows, LANES))
        enough = total >= k
        return (jnp.where(enough, trial, found),
                jnp.where(enough, above, total))

    zeros = jnp.zeros((rows, LANES), jnp.int32)
    found, above = jax.lax.fori_loop(0, 32, bit, (zeros, zeros))
    kth = found ^ least
    room = (k - above).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 1)
    # a tile's ties times this: their running count, then their total
    ranks = jnp.where((row <= col) | (col >= LANES), 1.0, 0.0)

    def emit(c, seen):
        kept = []
        for key, marked in lane_tiles(
                key_ref[:, at(c)], valid_ref[:, at(c)].astype(jnp.int32)):
            ties = (key == kth) & (marked != 0)
            counted = jnp.dot(jnp.where(ties, 1.0, 0.0), ranks,
                              preferred_element_type=jnp.float32)
            keep = (key > kth) | (ties & (counted[:, :LANES] + seen <= room))
            kept.append(jnp.where(keep, 1, 0).astype(jnp.int8))
            seen = seen + counted[:, LANES:]
        out_ref[:, at(c)] = jnp.concatenate(kept, axis=1)
        return seen

    jax.lax.fori_loop(0, chunks, emit, jnp.zeros((rows, LANES), jnp.float32))

    def none(c, _):
        out_ref[:, at(c)] = jnp.zeros((rows, chunk), jnp.int8)
        return 0

    jax.lax.fori_loop(chunks, n // chunk, none, 0)


@partial(jax.jit, static_argnames=("k", "interpret"))
def _select(scores, valid, *, k: int, interpret: bool):
    """Jitted on its own so that the layers of a prefill program share one
    traced and lowered kernel."""
    rows, n = scores.shape
    pad_r, pad_n = -rows % ROW_TILE, -n % LANES
    if pad_r or pad_n:
        scores, valid = (jnp.pad(a, ((0, pad_r), (0, pad_n)))
                         for a in (scores, valid))
    tiles, width = (rows + pad_r) // ROW_TILE, n + pad_n
    chunk = math.gcd(width, MAX_CHUNK)
    # the chunks of a tile up to its last marked column
    last = jnp.where(
        (valid != 0).reshape(tiles, ROW_TILE, width).any(axis=1),
        jnp.arange(width, dtype=jnp.int32) // chunk + 1, 0).max(axis=1)
    block = pl.BlockSpec((ROW_TILE, width), lambda i, last: (i, 0))
    out = pl.pallas_call(
        partial(_kernel, k=k, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[block, block], out_specs=block,
            scratch_shapes=[pltpu.VMEM((ROW_TILE, width), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((tiles * ROW_TILE, width), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(width)),
        interpret=interpret,
        name="select_top",
    )(last, scores, valid)
    return out[:rows, :n]


def select_top(scores, valid, k: int, *, interpret: bool | None = None):
    """The ``k`` largest of each row of ``scores (rows, N)`` float32 among
    the positions ``valid (rows, N)`` int8 marks (non-zero), a tie to the
    lower index; every marked position of a row with fewer than ``k``.
    ``k < N``. Returns the mask ``(rows, N)`` int8, 1 on what is kept."""
    return _select(scores, valid, k=k,
                   interpret=resolve_interpret("select_top", interpret))
