"""Pallas TPU kernel: fused segmentation postprocess (argmax → uint8 map).

The land-cover API's hottest non-matmul op: converting (B, H, W, C) float32
logits into a (B, H, W) uint8 class map. Done naively this reads 4·H·W·C
bytes and writes H·W·C intermediate softmax values; fused in one kernel it
reads the logits once and writes only the 1-byte class ids — a ~17×
write-bandwidth cut for C=4, which matters because the UNet's output layer is
HBM-bound, not MXU-bound.

Layout notes (pallas_guide.md tiling): a channels-last block (1, TH, W, C)
puts C on the 128-lane axis — C=4 pads to 128 lanes, inflating every VMEM
buffer 32× (a (1, 64, 256, 4) f32 block costs 8 MB instead of 256 KB and
blows the 16 MB scoped-VMEM budget under double buffering). So the array is
transposed to (B, C, H, W) first — one cheap XLA pass over the 4-channel
logits — and the kernel blocks as (1, C, TH, W): the (H, W) plane sits on
the (sublane, lane) axes at full utilization, and the class comparison
unrolls as C-1 vector max/select ops on the VPU.

Per-class pixel counts (the API's response payload) are computed outside the
kernel from the uint8 map — at 1 byte/pixel that second pass is ~0.4% of the
logits traffic, not worth fusing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret, shard_over_batch


def _argmax_kernel(logits_ref, out_ref, *, num_classes: int):
    # logits_ref: (1, C, TH, W); out_ref: (1, TH, W) uint8. Compared in
    # float32 whatever the logits dtype: a bfloat16 compare yields a mask in
    # bf16's (16, 128) tiling, and Mosaic cannot relayout that i1 vector to
    # select the int32 index plane ("Invalid relayout ... xi1"). The widening
    # is exact, so the argmax is unchanged.
    best = logits_ref[0, 0].astype(jnp.float32)
    idx = jnp.zeros(best.shape, jnp.int32)
    for c in range(1, num_classes):
        cand = logits_ref[0, c].astype(jnp.float32)
        take = cand > best
        best = jnp.where(take, cand, best)
        idx = jnp.where(take, c, idx)
    out_ref[0] = idx.astype(jnp.uint8)


def segmentation_argmax(logits: jax.Array, tile_h: int = 64,
                        interpret: bool | None = None,
                        mesh=None) -> jax.Array:
    """(B, H, W, C) float32/bfloat16 logits → (B, H, W) uint8 class map.

    ``interpret`` defaults to True off-TPU so the same code path runs in CPU
    CI (pallas interpreter) and compiles to Mosaic on device. ``mesh``: the
    serving mesh when the batch is sharded over its data axes
    (``lowering.shard_over_batch``).
    """
    _, h, w, c = logits.shape
    interpret = resolve_interpret("segmentation_argmax", interpret)
    tile_h = min(tile_h, h)
    if h % tile_h:
        raise ValueError(f"H={h} not divisible by tile_h={tile_h}")

    def call(logits_cf):
        n = logits_cf.shape[0]  # this device's share of the batch
        return pl.pallas_call(
            partial(_argmax_kernel, num_classes=c),
            out_shape=jax.ShapeDtypeStruct((n, h, w), jnp.uint8),
            grid=(n, h // tile_h),
            in_specs=[pl.BlockSpec((1, c, tile_h, w),
                                   lambda i, j: (i, 0, j, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, tile_h, w), lambda i, j: (i, j, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
        )(logits_cf)

    return shard_over_batch(call, mesh, interpret)(
        jnp.transpose(logits, (0, 3, 1, 2)))  # (B, C, H, W)


def class_histogram(classmap: jax.Array, num_classes: int) -> jax.Array:
    """(B, H, W) uint8 → (B, num_classes) int32 pixel counts (XLA; cheap)."""
    onehot = jax.nn.one_hot(classmap, num_classes, dtype=jnp.int32)
    return jnp.sum(onehot, axis=(1, 2))


def fused_seg_postprocess(logits: jax.Array,
                          interpret: bool | None = None,
                          with_classmap: bool = True, mesh=None) -> dict:
    """Full API postprocess: per-class counts, plus the uint8 class map when
    ``with_classmap``. Histogram-only APIs pass False so the map never leaves
    the device — the counts are B·C int32s, ~4000× less device→host traffic
    than the map (which itself is 16× less than the logits)."""
    classmap = segmentation_argmax(logits, interpret=interpret, mesh=mesh)
    counts = class_histogram(classmap, logits.shape[-1])
    if with_classmap:
        return {"classmap": classmap, "counts": counts}
    return {"counts": counts}
