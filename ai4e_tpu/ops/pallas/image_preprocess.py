"""Pallas TPU kernel: fused uint8 image normalization.

Input path hot op: clients send uint8 pixels; shipping uint8 to the device and
normalizing on-chip cuts host→device transfer 4× versus sending float32 (HBM
and interconnect bandwidth are the serving bottleneck, not FLOPs). The kernel
fuses cast → scale → mean/std normalization in one VMEM pass.

Layout notes (pallas_guide.md tiling): a channels-last block (1, TH, W, C)
would put C=3 on the 128-lane axis and pad it 42× in VMEM. Instead the image
is viewed as (B, H, W·C) — a free reshape, C is the dense minor dim — so the
lane axis is fully utilized. The per-channel mean/std scalars become (W·C,)
rows with the channel pattern pre-tiled (computed once at trace time), and
the kernel is a pure row-broadcast multiply-add on the VPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret, shard_over_batch


def _normalize_kernel(img_ref, scale_ref, bias_ref, out_ref):
    # img_ref: (1, TH, W*C) uint8; out: (1, TH, W*C) float32
    # normalized = (x/255 - mean) / std  ==  x * scale + bias  with
    # scale = 1/(255*std), bias = -mean/std (folded at trace time).
    # Mosaic has no direct u8→f32 cast; widen through int32 on the VPU.
    x = img_ref[0].astype(jnp.int32).astype(jnp.float32)
    out_ref[0] = x * scale_ref[0][None, :] + bias_ref[0][None, :]


def normalize_image(images: jax.Array, mean=None, std=None,
                    tile_h: int = 64, interpret: bool | None = None,
                    mesh=None) -> jax.Array:
    """(B, H, W, C) uint8 → (B, H, W, C) float32 in normalized range.
    ``mesh``: the serving mesh when the batch is sharded over its data axes
    (``lowering.shard_over_batch``)."""
    b, h, w, c = images.shape
    if images.dtype != jnp.uint8:
        raise ValueError(f"expected uint8 input, got {images.dtype}")
    interpret = resolve_interpret("normalize_image", interpret)
    # Largest divisor of H within the target keeps the grid exact for
    # non-multiple-of-64 sizes (224 → 56, 512 → 64) — but never below the
    # 8-sublane minimum Mosaic tiles f32 at: a prime-ish H would otherwise
    # silently degrade to (1, W·C) blocks and fail/crawl on device.
    tile_h = min(tile_h, h)
    while h % tile_h and tile_h > 8:
        tile_h -= 1
    if h % tile_h:
        raise ValueError(
            f"H={h} has no tile divisor >= 8; pad the image height "
            "(e.g. to a multiple of 8) before normalize_image")
    mean = jnp.asarray([0.0] * c if mean is None else mean, jnp.float32)
    std = jnp.asarray([1.0] * c if std is None else std, jnp.float32)

    scale_row = jnp.tile(1.0 / (255.0 * std), w)    # (W*C,)
    bias_row = jnp.tile(-mean / std, w)

    def call(flat, scale, bias):
        n = flat.shape[0]  # this device's share of the batch
        return pl.pallas_call(
            _normalize_kernel,
            out_shape=jax.ShapeDtypeStruct((n, h, w * c), jnp.float32),
            grid=(n, h // tile_h),
            in_specs=[
                pl.BlockSpec((1, tile_h, w * c), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, w * c), lambda i, j: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, w * c), lambda i, j: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, tile_h, w * c), lambda i, j: (i, j, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
        )(flat, scale, bias)

    out = shard_over_batch(call, mesh, interpret, replicated_args=2)(
        images.reshape(b, h, w * c), scale_row[None], bias_row[None])
    return out.reshape(b, h, w, c)
