"""Plain reference for the ``unet`` family (land-cover segmentation), the
comparison that decides ``correct`` for its cells, and the family's operation
and byte counts.

The forward pass below is written from the architecture, in ``jax.numpy`` and
float32 at ``highest`` matmul precision, with no import from
``ai4e_tpu.models``, no kernel, no cache and no batching: 3x3 convolutions
without bias, GroupNorm (min(32, C) groups, eps 1e-6), tanh-GELU, stride-2
convolutions down, nearest-neighbour x2 + 1x1 convolution up, skip
concatenation ``[up, skip]``, a 1x1 classifier with bias, argmax per pixel.
Input is rgb8 / 255. The program's ``create_unet`` is called for the
parameter VALUES only (the worker serves the family's seeded init, key 0).
"""

from __future__ import annotations

import numpy as np

# A served class histogram may differ from the reference's by at most this
# share of the tile's pixels (pixels that changed class = half the L1
# distance). Reason: the worker computes in bfloat16 and the reference in
# float32, so near-tie pixels of a randomly initialised net land on either
# side. Measured on the chip (PR 23, TPU v5 lite): see PIXELS_MEASURED. A
# dropped layer, a wrong normalisation or another tile's answer moves tens of
# percent of the pixels.
PIXEL_SHARE_TOLERANCE = 0.01
PIXELS_MEASURED = "worst 46-77 of 65,536 pixels (0.12 %) over 48 tiles in 4 runs (my chip runs, PR 23)"


def _model_spec(config: dict) -> dict:
    return next(m for m in config["models"]["models"] if m["family"] == "unet")


def ops_and_bytes(config: dict, rows: int) -> tuple[float, float]:
    """Floating-point operations and least bytes moved for one forward pass
    over ``rows`` tiles, from the shapes alone: 2·H·W·k²·Cin·Cout per
    convolution; bytes = rgb8 input + float32 parameters once + the class
    counts out. (Normalisation and activation arithmetic is left out of the
    operations: under 1 % of the convolutions'.)"""
    spec = _model_spec(config)
    tile, widths, classes = spec["tile"], spec["widths"], spec["num_classes"]
    flops, params, size, cin = 0, 0, tile, 3

    def conv(hw, k, ci, co, bias=False):
        nonlocal flops, params
        flops += 2 * hw * hw * k * k * ci * co
        params += k * k * ci * co + (co if bias else 0)

    for i, w in enumerate(widths):
        conv(size, 3, cin, w)
        conv(size, 3, w, w)
        params += 4 * w                      # two GroupNorms: scale + bias
        cin = w
        if i < len(widths) - 1:
            size //= 2
            conv(size, 3, w, w)              # stride 2: output positions
    for w in reversed(widths[:-1]):
        size *= 2
        conv(size, 1, cin, w)
        conv(size, 3, 2 * w, w)
        conv(size, 3, w, w)
        params += 4 * w
        cin = w
    conv(size, 1, cin, classes, bias=True)
    nbytes = rows * (tile * tile * 3 + 4 * classes) + 4 * params
    return float(rows * flops), float(nbytes)


def _forward(params: dict, x, widths):
    import jax
    import jax.numpy as jnp

    def conv(x, kernel, stride=1):
        return jax.lax.conv_general_dilated(
            x, kernel, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)

    def group_norm(x, p):
        b, h, w, c = x.shape
        g = min(32, c)
        xg = x.reshape(b, h, w, g, c // g)
        mean = xg.mean(axis=(1, 2, 4), keepdims=True)
        var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
        xg = (xg - mean) / jnp.sqrt(var + 1e-6)
        return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]

    def gelu(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))

    def block(x, p):
        for i in range(2):
            x = gelu(group_norm(conv(x, p[f"Conv_{i}"]["kernel"]),
                                p[f"GroupNorm_{i}"]))
        return x

    skips, n = [], len(widths)
    for i in range(n):
        x = block(x, params[f"ConvBlock_{i}"])
        if i < n - 1:
            skips.append(x)
            x = conv(x, params[f"Conv_{i}"]["kernel"], stride=2)
    for j, skip in enumerate(reversed(skips)):
        x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
        x = conv(x, params[f"Conv_{n - 1 + j}"]["kernel"])
        x = block(jnp.concatenate([x, skip], axis=-1),
                  params[f"ConvBlock_{n + j}"])
    head = params[f"Conv_{2 * n - 2}"]
    return conv(x, head["kernel"]) + head["bias"]


def histogram(params: dict, tile_u8: np.ndarray, spec: dict) -> dict:
    """Reference class histogram of one rgb8 tile, keyed like the API's."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        logits = _forward(params, jnp.asarray(tile_u8[None], jnp.float32)
                          / 255.0, spec["widths"])
    classes = np.asarray(jnp.argmax(logits[0], axis=-1))
    counts = np.bincount(classes.ravel(), minlength=spec["num_classes"])
    return {str(c): int(n) for c, n in enumerate(counts) if n}


def pixels_moved(a: dict, b: dict) -> int:
    return sum(abs(int(a.get(k, 0)) - int(b.get(k, 0)))
               for k in set(a) | set(b)) // 2


def prepare(config: dict, pre: dict) -> dict:
    """Parameter values on the host CPU, and the reference histograms of the
    tiles ``pre`` names — computed while the worker warms up."""
    import jax
    from ai4e_tpu.models import create_unet   # parameter VALUES only
    from benchmark.lib.payloads import POOL, TilePayloads
    spec = _model_spec(config)
    _, variables = create_unet(tile=spec["tile"], widths=tuple(spec["widths"]),
                               num_classes=spec["num_classes"])
    params = jax.tree.map(np.asarray, variables["params"])
    tiles = TilePayloads(pre["seed"], spec["tile"])
    expected = {c: histogram(params, tiles.array(c), spec)
                for c in range(pre.get("reference_bases", POOL))}
    return {"spec": spec, "expected": expected, "pool": POOL}


def check(state: dict, jobs: list[dict]) -> dict:
    """Every sampled served histogram against the reference's for the same
    base tile (the counter stamp changes eight bytes of 196,608)."""
    total = state["spec"]["tile"] ** 2
    limit = int(PIXEL_SHARE_TOLERANCE * total)
    worst, bad = 0, []
    for job in jobs:
        want = state["expected"][job["counter"] % state["pool"]]
        got = {str(k): int(v) for k, v in
               job["result"]["class_histogram"].items()}
        moved = pixels_moved(want, got)
        worst = max(worst, moved)
        if sum(got.values()) != total or moved > limit:
            bad.append({"counter": job["counter"], "moved": moved,
                        "got": got, "want": want})
    return {"ok": not bad and bool(jobs), "checked": len(jobs),
            "worst_pixels_moved": worst, "limit_pixels": limit,
            "bad": bad[:3]}
