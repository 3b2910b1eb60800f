"""YUV 4:2:0 host↔device wire codec — halve h2d bytes for image models.

A 256×256×3 uint8 tile is 196 608 bytes on the host→device link. Whether
that link bounds image throughput on a locally attached chip has not been
measured (ROADMAP.md Speed 8 decides this codec's fate on that number).
Camera/ aerial imagery arrives as JPEG, which already stores chroma
subsampled 4:2:0 — so shipping the device full-resolution chroma carries no
information the source had. This codec moves the subsampling boundary to the
host↔device link:

- host (``rgb_to_yuv420``): decoded RGB → planar JPEG-convention YCbCr with
  2×2-averaged chroma — 1.5 bytes/pixel, exactly half of raw RGB;
- device (``yuv420_to_rgb``): flat planes → nearest-upsampled chroma →
  inverse transform → normalized [0,1] float RGB, fused by XLA into the
  model's first convolution (one extra VMEM pass, zero extra HBM round
  trips).

The transform pair is JPEG's own (JFIF full-range BT.601), so accuracy
matches what the reference's JPEG-ingesting pipelines already see.
"""

from __future__ import annotations

import numpy as np


def yuv420_nbytes(h: int, w: int) -> int:
    return h * w + 2 * (h // 2) * (w // 2)


_native_encode = None
_native_tried = False


def _get_native_encode():
    """C++ encoder (``native/yuv_codec.cpp``) or None — the conversion runs
    per request on the serving host's core, and the numpy version's
    channel-interleaved reductions cost ~2 ms per 256² tile where the
    single-pass C++ loop costs ~0.2 ms."""
    global _native_encode, _native_tried
    if _native_tried:
        return _native_encode
    _native_tried = True
    import ctypes

    from ..utils.native_build import load_native_function
    _native_encode = load_native_function(
        "yuv_codec.cpp", "libyuv_codec.so", "yuv420_encode",
        restype=ctypes.c_int,
        argtypes=[ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
                  ctypes.c_int, ctypes.POINTER(ctypes.c_uint8)])
    return _native_encode


def rgb_to_yuv420(arr: np.ndarray) -> np.ndarray:
    """(H, W, 3) uint8 RGB → flat planar uint8 [Y | Cb | Cr], chroma 2×2
    box-averaged. H and W must be even (tile sizes are). Dispatches to the
    C++ encoder when available (same contract within 1 LSB — rounding of
    exact halves differs); numpy otherwise."""
    if arr.ndim != 3 or arr.shape[-1] != 3 or arr.dtype != np.uint8:
        # Validate BEFORE dispatch: the C++ path reinterprets raw bytes and
        # would return plausible garbage for float/RGBA input with rc==0.
        raise ValueError(
            f"expected (H, W, 3) uint8, got {arr.shape} {arr.dtype}")
    h, w, _ = arr.shape
    if h % 2 or w % 2:
        raise ValueError(f"yuv420 needs even dims, got {arr.shape}")
    encode = _get_native_encode()
    if encode is not None:
        import ctypes

        arr = np.ascontiguousarray(arr)
        out = np.empty(yuv420_nbytes(h, w), np.uint8)
        rc = encode(arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    h, w, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if rc == 0:
            return out
    return _rgb_to_yuv420_numpy(arr)


def _rgb_to_yuv420_numpy(arr: np.ndarray) -> np.ndarray:
    h, w, _ = arr.shape
    n = h * w
    q = (h // 2) * (w // 2)
    out = np.empty(yuv420_nbytes(h, w), np.uint8)
    f = arr.astype(np.float32)
    r, g, b = f[..., 0], f[..., 1], f[..., 2]
    y = 0.299 * r + 0.587 * g + 0.114 * b
    cb = 128.0 - 0.168736 * r - 0.331264 * g + 0.5 * b
    cr = 128.0 + 0.5 * r - 0.418688 * g - 0.081312 * b
    cb = cb.reshape(h // 2, 2, w // 2, 2).mean(axis=(1, 3))
    cr = cr.reshape(h // 2, 2, w // 2, 2).mean(axis=(1, 3))
    out[:n] = (y + 0.5).astype(np.uint8).reshape(-1)  # y ∈ [0,255] exactly
    out[n:n + q] = np.clip(np.round(cb), 0, 255).astype(np.uint8).reshape(-1)
    out[n + q:] = np.clip(np.round(cr), 0, 255).astype(np.uint8).reshape(-1)
    return out


def yuv420_to_rgb_numpy(flat: np.ndarray, h: int, w: int) -> np.ndarray:
    """Host-side inverse: flat planes → (H, W, 3) uint8 RGB — for consumers
    that need the image back on the HOST (e.g. a pipeline crops handoff
    cropping a yuv-wire detector's input). Same math as the device inverse."""
    flat = np.asarray(flat, np.uint8)
    n = h * w
    q = (h // 2) * (w // 2)
    y = flat[:n].reshape(h, w).astype(np.float32)
    cb = flat[n:n + q].reshape(h // 2, w // 2).astype(np.float32) - 128.0
    cr = flat[n + q:].reshape(h // 2, w // 2).astype(np.float32) - 128.0
    cb = np.repeat(np.repeat(cb, 2, axis=0), 2, axis=1)
    cr = np.repeat(np.repeat(cr, 2, axis=0), 2, axis=1)
    r = y + 1.402 * cr
    g = y - 0.344136 * cb - 0.714136 * cr
    b = y + 1.772 * cb
    rgb = np.stack([r, g, b], axis=-1)
    return np.clip(np.round(rgb), 0, 255).astype(np.uint8)


def yuv420_to_rgb(flat, h: int, w: int):
    """Device-side inverse: (B, yuv420_nbytes) uint8 → (B, H, W, 3) float32
    in [0, 1]. Chroma upsamples nearest (what fast JPEG decoders do); the
    whole thing is elementwise + reshape, so XLA fuses it into the consumer.
    """
    import jax.numpy as jnp

    n = h * w
    q = (h // 2) * (w // 2)
    bsz = flat.shape[0]
    y = flat[:, :n].reshape(bsz, h, w).astype(jnp.float32)
    cb = flat[:, n:n + q].reshape(bsz, h // 2, w // 2).astype(jnp.float32)
    cr = flat[:, n + q:].reshape(bsz, h // 2, w // 2).astype(jnp.float32)
    cb = jnp.repeat(jnp.repeat(cb, 2, axis=1), 2, axis=2) - 128.0
    cr = jnp.repeat(jnp.repeat(cr, 2, axis=1), 2, axis=2) - 128.0
    r = y + 1.402 * cr
    g = y - 0.344136 * cb - 0.714136 * cr
    b = y + 1.772 * cb
    rgb = jnp.stack([r, g, b], axis=-1)
    return jnp.clip(rgb / 255.0, 0.0, 1.0)
