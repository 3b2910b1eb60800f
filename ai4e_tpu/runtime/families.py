"""Model-family factories — config-driven servable construction.

The reference publishes a model by baking it into a container image and
writing a Helm values file naming that image (``APIs/Charts/camera-trap/
detection-async/prod-values.yaml``). Here a *family* + kwargs in a worker
config produces a ready ``ServableModel``: the framework owns preprocess
(npy payload decoding), the jittable forward, and postprocess, so a
deployment file can say ``{"family": "unet", "tile": 256}`` and get the
land-cover API.

Families: ``echo`` (the base-py smoke API), ``unet`` (land-cover
segmentation), ``resnet`` (species classification), ``detector``
(camera-trap MegaDetector slot), ``vit`` (classification with
tensor-parallel sharding rules).
"""

from __future__ import annotations

import io

import jax
import numpy as np

from .ladder import DETECTOR_BUCKETS, IMAGE_BUCKETS
from .registry import ServableModel


def _finite_narrow_cast(arr: np.ndarray, dtype: np.dtype) -> np.ndarray:
    """Cast a float payload to a narrower float wire dtype, failing loudly:
    a bare astype maps |x| > dtype-max to inf, which would surface
    downstream as NaN scores instead of an error for this one task."""
    with np.errstate(over="ignore", invalid="ignore"):
        # The guard below is the error surface — the cast's own overflow
        # RuntimeWarning would pre-empt it under -W error and spam logs
        # otherwise.
        out = arr.astype(dtype, copy=False)
    if (np.issubdtype(dtype, np.floating)
            and np.issubdtype(arr.dtype, np.floating)
            and np.dtype(dtype).itemsize < arr.dtype.itemsize
            and not np.isfinite(out).all()):
        if np.isnan(arr).any():
            raise ValueError("payload contains NaN")
        raise ValueError(
            f"payload exceeds {np.dtype(dtype)} range (max |x| "
            f"{float(np.nanmax(np.abs(arr)))})")
    return out


def _npy_preprocess(shape: tuple, dtype=np.float32):
    dtype = np.dtype(dtype)

    def preprocess(body: bytes, content_type: str):
        arr = np.load(io.BytesIO(body))
        if arr.shape != shape:
            raise ValueError(f"expected {shape}, got {arr.shape}")
        return _finite_narrow_cast(arr, dtype)
    return preprocess


def _image_preprocess(shape: tuple, dtype=np.float32):
    """Payload decoder for (H, W, 3) models: ``image/*`` content types are
    decoded + resized with PIL (the reference's camera-trap APIs take camera
    JPEGs, e.g. ``APIs/Charts/camera-trap/detection-async``); anything else
    is treated as a raw npy array of the exact input shape. A broken image
    raises ValueError → fails that one task, never a batch."""
    h, w, _ = shape

    def preprocess(body: bytes, content_type: str):
        if content_type and content_type.startswith("image/"):
            try:
                from PIL import Image
            except ImportError as exc:  # pragma: no cover - PIL is baked in
                raise ValueError("image payloads need Pillow") from exc
            try:
                img = Image.open(io.BytesIO(body))
                img = img.convert("RGB").resize((w, h), Image.BILINEAR)
            except Exception as exc:  # noqa: BLE001 — bad image fails one task
                raise ValueError(f"undecodable image: {exc}") from exc
            arr = np.asarray(img, np.uint8)
            if np.dtype(dtype) == np.uint8:
                return arr
            # Float models get [0, 1] — the conventional image scaling.
            return arr.astype(np.float32) / 255.0
        arr = np.load(io.BytesIO(body))
        if arr.shape != shape:
            raise ValueError(f"expected {shape}, got {arr.shape}")
        return cast_image_payload(arr, dtype)

    return preprocess


def cast_image_payload(arr: np.ndarray, dtype) -> np.ndarray:
    """Cast a decoded payload to the servable's input dtype. Float [0,1]
    arrays headed for a uint8-ingesting model are SCALED, not truncated (a
    bare astype would zero the image); float→narrower-float goes through the
    finite-cast guard — shared by the single-request and batch-stack decode
    paths."""
    if np.dtype(dtype) == np.uint8 and arr.dtype != np.uint8:
        return np.clip(np.round(arr * 255.0), 0, 255).astype(np.uint8)
    return _finite_narrow_cast(arr, np.dtype(dtype))


def encode_classmap_png(classmap: np.ndarray) -> str:
    """(H, W) uint8 class ids → base64 PNG string (grayscale, lossless;
    pixel value == class id) — the classified-tile payload of the
    reference's land-cover API."""
    import base64

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(classmap.astype(np.uint8), mode="L").save(buf, "PNG")
    return base64.b64encode(buf.getvalue()).decode("ascii")


def _classification_postprocess(labels: list | None = None):
    """Softmax + argmax → {class_id, label?, confidence} — shared by every
    classifier family."""
    def postprocess(logits):
        logits = np.asarray(logits, np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = int(np.argmax(probs))
        out = {"class_id": top, "confidence": float(probs[top])}
        if labels:
            out["label"] = labels[top]
        return out
    return postprocess


def build_echo(name: str = "echo", size: int = 16, buckets=(8,),
               **_) -> ServableModel:
    """Identity model — the reference's base-py echo API
    (``APIs/1.0/base-py/runserver.py`` role): proves the full transport
    without model weight."""
    import jax.numpy as jnp

    def apply_fn(params, batch):
        return jnp.asarray(batch) * params["scale"]

    return ServableModel(
        name=name, apply_fn=apply_fn, params={"scale": np.float32(1.0)},
        input_shape=(size,), preprocess=_npy_preprocess((size,)),
        postprocess=lambda out: {"echo": np.asarray(out).tolist()},
        batch_buckets=tuple(buckets))


def build_unet(name: str = "landcover", tile: int = 256,
               widths=(32, 64, 128), num_classes: int = 8, buckets=IMAGE_BUCKETS,
               fused_postprocess: bool = True,
               return_classmap: bool = False,
               wire: str = "rgb8", mesh=None, **_) -> ServableModel:
    """Land-cover segmentation (BASELINE.json config #2).

    ``return_classmap`` adds the classified tile itself to the response as a
    base64 PNG (the reference's land-cover APIs return classified tiles, not
    just statistics). Off by default: the histogram API then fetches only
    B·C int32 counts from the device (~32 bytes/example against H·W for
    the uint8 map).

    ``wire`` selects the host→device batch encoding: ``rgb8`` (raw uint8
    pixels, 3 B/px) or ``yuv420`` (planar JPEG-convention YCbCr with 2×2
    chroma, 1.5 B/px — half the h2d bytes; reconstruction fuses into the
    first conv on device, ``ops/yuv.py``). Clients ship the same payloads either way:
    single requests as image/npy, batch stacks as (N, H, W, 3) — stack
    items convert to planes at ingestion (``stack_adapter``).

    ``mesh``: the serving mesh (``cli.build_worker`` passes the runtime's) —
    on more than one chip the Pallas kernels run per batch shard.
    """
    from ..models import create_unet
    from ..ops.pallas import fused_seg_postprocess, normalize_image

    _check_wire(wire, fused_postprocess, "fused_postprocess")

    model, params = create_unet(tile=tile, widths=tuple(widths),
                                num_classes=num_classes)

    def fused_postprocess_fn(out):
        # One response contract for every fused ingestion wire.
        counts = np.asarray(out["counts"])
        result = {"class_histogram":
                  {int(c): int(n) for c, n in enumerate(counts) if n}}
        if return_classmap:
            result["classmap_png"] = encode_classmap_png(
                np.asarray(out["classmap"]))
        return result

    if wire in ("yuv420", "dct"):
        def on_normalized(p, x):
            return fused_seg_postprocess(model.apply(p, x),
                                         with_classmap=return_classmap,
                                         mesh=mesh)

        build = _yuv_servable if wire == "yuv420" else _dct_servable
        return build(name, params, on_normalized, tile, tile,
                     fused_postprocess_fn, buckets)

    if fused_postprocess:
        def apply_fn(p, batch):
            x = normalize_image(batch, mesh=mesh)
            return fused_seg_postprocess(model.apply(p, x),
                                         with_classmap=return_classmap,
                                         mesh=mesh)

        postprocess = fused_postprocess_fn
        input_dtype = np.uint8
        preprocess = _image_preprocess((tile, tile, 3), np.uint8)
    else:
        from ..models import segment_logits_to_classes

        def apply_fn(p, batch):
            return model.apply(p, batch)

        def postprocess(logits):
            classes = np.asarray(segment_logits_to_classes(logits[None])[0])
            values, counts = np.unique(classes, return_counts=True)
            result = {"class_histogram":
                      {int(v): int(c) for v, c in zip(values, counts)}}
            if return_classmap:  # same response contract as the fused path
                result["classmap_png"] = encode_classmap_png(classes)
            return result

        input_dtype = np.float32
        preprocess = _image_preprocess((tile, tile, 3))

    return ServableModel(
        name=name, apply_fn=apply_fn, params=params,
        input_shape=(tile, tile, 3), input_dtype=input_dtype,
        preprocess=preprocess, postprocess=postprocess,
        batch_buckets=tuple(buckets))


def build_resnet(name: str = "classifier", image_size: int = 224,
                 num_classes: int = 1000, stage_sizes=(3, 4, 6, 3),
                 width: int = 64, labels: list | None = None,
                 buckets=IMAGE_BUCKETS, fused_normalize: bool = True,
                 wire: str = "rgb8", mesh=None, **_) -> ServableModel:
    """Batched species classification (BASELINE.json config #4).

    ``fused_normalize`` (default): clients ship uint8 pixels — 4x less
    transfer + host copy than float32 — and the cast/scale to [0,1] runs
    on-device in one VMEM pass (``ops/pallas/normalize_image``), the same
    ingestion design as the landcover bench path. Weights are unaffected
    (normalization reproduces the float input the model trained on).

    ``wire="yuv420"`` goes further: planar 4:2:0 chroma on the wire (half
    the h2d bytes again; ``ops/yuv.py``). Opt-in; batch stacks and the
    crops handoff keep shipping (N, H, W, 3) — items convert at ingestion.
    """
    from ..models.resnet import ResNet

    model = ResNet(stage_sizes=tuple(stage_sizes), num_classes=num_classes,
                   width=width)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, image_size, image_size, 3),
                                    np.float32))

    def postprocess(logits):
        logits = np.asarray(logits, np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = int(np.argmax(probs))
        return {"class_id": top,
                "label": labels[top] if labels else str(top),
                "confidence": float(probs[top])}

    _check_wire(wire, fused_normalize, "fused_normalize")
    if wire in ("yuv420", "dct"):
        build = _yuv_servable if wire == "yuv420" else _dct_servable
        return build(name, variables, model.apply,
                     image_size, image_size, postprocess, buckets)

    apply_fn, input_dtype = _maybe_fused_uint8(model.apply, fused_normalize,
                                               mesh)
    return ServableModel(
        name=name, apply_fn=apply_fn, params=variables,
        input_shape=(image_size, image_size, 3), input_dtype=input_dtype,
        preprocess=_image_preprocess((image_size, image_size, 3),
                                     input_dtype),
        postprocess=postprocess, batch_buckets=tuple(buckets))


def _maybe_fused_uint8(apply_fn, fused: bool, mesh=None):
    """uint8-ingestion wrapper: on-device normalize to [0,1] before the
    model (ops/pallas/normalize_image, per batch shard of ``mesh``);
    returns (apply_fn, input_dtype)."""
    if not fused:
        return apply_fn, np.float32
    from ..ops.pallas import normalize_image

    def fused_apply(p, batch):
        return apply_fn(p, normalize_image(batch, mesh=mesh))

    return fused_apply, np.uint8


def _check_wire(wire: str, fused: bool, fused_flag: str) -> None:
    """Uniform wire validation for the image families: unknown wire values
    and the compressed-wire-without-fused-ingestion conflict both fail at
    build time (wire reconstruction IS the fused ingestion — disabling it
    while asking for a compressed wire is contradictory, not overridable)."""
    if wire not in ("rgb8", "yuv420", "dct"):
        raise ValueError(f"wire must be rgb8|yuv420|dct, got {wire!r}")
    if wire in ("yuv420", "dct") and not fused:
        raise ValueError(f"wire={wire!r} requires {fused_flag}=True")


def _yuv_servable(name: str, params, apply_on_normalized, h: int, w: int,
                  postprocess, buckets) -> ServableModel:
    """YUV 4:2:0 wire servable for an (H, W, 3) model whose
    ``apply_on_normalized`` consumes [0,1] float RGB: clients ship the usual
    image/npy payloads, the host converts to planar 4:2:0 (half the h2d
    bytes of raw uint8 RGB), the device reconstructs fused into the model's
    first op (``ops/yuv.py``). One construction point for every family."""
    from ..ops.yuv import (rgb_to_yuv420, yuv420_nbytes, yuv420_to_rgb,
                           yuv420_to_rgb_numpy)

    if h % 2 or w % 2:
        # Fail at BUILD time: an odd size would construct fine and then die
        # in preprocess on every request.
        raise ValueError(f"wire='yuv420' needs even dims, got {h}x{w}")
    rgb_pre = _image_preprocess((h, w, 3), np.uint8)

    def preprocess(body: bytes, content_type: str):
        return rgb_to_yuv420(rgb_pre(body, content_type))

    def apply_fn(p, batch):
        return apply_on_normalized(p, yuv420_to_rgb(batch, h, w))

    return ServableModel(
        name=name, apply_fn=apply_fn, params=params,
        input_shape=(yuv420_nbytes(h, w),), input_dtype=np.uint8,
        preprocess=preprocess, postprocess=postprocess,
        batch_buckets=tuple(buckets),
        # Batch stacks keep shipping (N, H, W, 3); each item converts to
        # planes at ingestion (serve_batch).
        stack_item_shape=(h, w, 3), stack_item_dtype=np.uint8,
        stack_adapter=rgb_to_yuv420,
        # Host consumers of the preprocessed example (a crops handoff
        # cropping this stage's input) get the RGB image back.
        example_decoder=lambda flat: yuv420_to_rgb_numpy(flat, h, w))


def _dct_servable(name: str, params, apply_on_normalized, h: int, w: int,
                  postprocess, buckets) -> ServableModel:
    """DCT-truncation wire servable (``ops/dct.py``): clients ship the usual
    image/npy payloads, the host packs quantized K×K DCT coefficients
    (0.375 B/px — 4× less h2d than yuv420, 8× less than raw RGB), the
    device decodes with dequant + per-block IDCT matmuls fused into the
    model's first op. Same construction contract as ``_yuv_servable``."""
    from ..ops.dct import (dct_nbytes, dct_to_rgb, dct_to_rgb_numpy,
                           rgb_to_dct)

    if h % 16 or w % 16:
        # Fail at BUILD time (8-px luma blocks × 2× chroma subsampling).
        raise ValueError(f"wire='dct' needs dims divisible by 16, "
                         f"got {h}x{w}")
    rgb_pre = _image_preprocess((h, w, 3), np.uint8)

    def preprocess(body: bytes, content_type: str):
        return rgb_to_dct(rgb_pre(body, content_type))

    def apply_fn(p, batch):
        return apply_on_normalized(p, dct_to_rgb(batch, h, w))

    return ServableModel(
        name=name, apply_fn=apply_fn, params=params,
        input_shape=(dct_nbytes(h, w),), input_dtype=np.int8,
        preprocess=preprocess, postprocess=postprocess,
        batch_buckets=tuple(buckets),
        stack_item_shape=(h, w, 3), stack_item_dtype=np.uint8,
        stack_adapter=rgb_to_dct,
        example_decoder=lambda flat: dct_to_rgb_numpy(flat, h, w))


def build_detector(name: str = "megadetector", image_size: int = 512,
                   widths=(64, 128, 256), max_detections: int = 64,
                   score_threshold: float = 0.2, buckets=DETECTOR_BUCKETS,
                   fused_normalize: bool = True,
                   wire: str = "rgb8", mesh=None, **_) -> ServableModel:
    """Camera-trap detection (BASELINE.json config #3, MegaDetector slot).

    ``fused_normalize``: uint8 ingestion + on-device [0,1] scaling (see
    ``build_resnet``) — a camera-trap JPEG pipeline ships bytes, not floats.
    ``wire="yuv420"``: planar 4:2:0 on the wire, halving h2d bytes again —
    the detector ships the fattest tiles of any family (H·W·3 at 512²), so
    this is where a bandwidth-bound link gains the most. Opt-in; batch
    stacks keep shipping (N, H, W, 3) — items convert at ingestion.
    """
    from ..models import CenterNetDetector, decode_detections

    model = CenterNetDetector(widths=tuple(widths))
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, image_size, image_size, 3), np.float32))

    def raw_apply(p, batch):
        return decode_detections(model.apply(p, batch),
                                 max_detections=max_detections)

    def postprocess(out):
        scores = np.asarray(out["scores"])
        keep = scores >= score_threshold
        return {"detections": [
            {"box": np.asarray(out["boxes"])[i].tolist(),
             "score": float(scores[i]),
             "class_id": int(np.asarray(out["classes"])[i])}
            for i in np.nonzero(keep)[0]]}

    _check_wire(wire, fused_normalize, "fused_normalize")
    if wire in ("yuv420", "dct"):
        build = _yuv_servable if wire == "yuv420" else _dct_servable
        return build(name, params, raw_apply,
                     image_size, image_size, postprocess, buckets)

    apply_fn, input_dtype = _maybe_fused_uint8(raw_apply, fused_normalize,
                                               mesh)
    return ServableModel(
        name=name, apply_fn=apply_fn, params=params,
        input_shape=(image_size, image_size, 3), input_dtype=input_dtype,
        preprocess=_image_preprocess((image_size, image_size, 3),
                                     input_dtype),
        postprocess=postprocess, batch_buckets=tuple(buckets))


def build_vit(name: str = "vit", image_size: int = 224, patch: int = 16,
              dim: int = 384, depth: int = 12, heads: int = 6,
              num_classes: int = 1000, buckets=IMAGE_BUCKETS, **_
              ) -> ServableModel:
    from ..models import create_vit

    model, params = create_vit(image_size=image_size, patch=patch, dim=dim,
                               depth=depth, heads=heads,
                               num_classes=num_classes)

    def postprocess(logits):
        top = int(np.argmax(np.asarray(logits)))
        return {"class_id": top}

    return ServableModel(
        name=name, apply_fn=model.apply, params=params,
        input_shape=(image_size, image_size, 3),
        preprocess=_image_preprocess((image_size, image_size, 3)),
        postprocess=postprocess, batch_buckets=tuple(buckets))


def _check_token_ids(arr: np.ndarray, vocab_size: int) -> None:
    """THE token-id validation, shared by the single-item and batch-stack
    wires so they cannot drift: integer dtype (floats would silently
    truncate fractional ids) and range (the on-device Embed gather CLAMPS
    out-of-bounds indices — XLA semantics — so an unchecked bad id scores
    silently wrong instead of failing). Must run on the RAW payload,
    before any cast: an int64 id ≥ 2³² wraps into range under int32."""
    if not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(f"token payload must be integer, got {arr.dtype}")
    if arr.size and (int(arr.min()) < 0 or int(arr.max()) >= vocab_size):
        raise ValueError(
            f"token ids must be in [0, {vocab_size}); got "
            f"[{int(arr.min())}, {int(arr.max())}]")


def _token_preprocess(seq_len: int, vocab_size: int):
    """Payload decoder for token-id sequences: any integer npy of shape
    (S,) in ``[0, vocab_size)``. Clients ship the narrowest integer dtype
    they like (uint16 for vocabs ≤64k — 2 bytes/token on the HTTP wire);
    the device batch is int32 either way. Out-of-range ids fail that one
    task at preprocess, never the batch."""

    def preprocess(body: bytes, content_type: str):
        arr = np.load(io.BytesIO(body))
        if arr.shape != (seq_len,):
            raise ValueError(f"expected ({seq_len},), got {arr.shape}")
        _check_token_ids(arr, vocab_size)
        return arr.astype(np.int32)
    return preprocess


def _sequence_input_contract(seq_len: int, input_dim: int,
                             vocab_size: int | None,
                             feature_dtype=np.float32):
    """``(input_shape, input_dtype, preprocess, stack_kwargs)`` for the
    sequence families' shared wire contract: token ids when ``vocab_size``
    is set, float feature sequences otherwise. One helper so seqformer and
    moe cannot drift.

    Token mode's ``stack_kwargs`` install ``_check_token_ids`` as the
    batch-stack validator — it runs on the RAW stack, before the decode
    path's cast to the device dtype (a post-cast check would pass
    wrapped-into-range ids). Value-level stack validation failing the
    whole stack matches the image families' NaN behavior."""
    if vocab_size is not None:
        return ((seq_len,), np.dtype(np.int32),
                _token_preprocess(seq_len, vocab_size),
                {"stack_validator":
                 lambda arr: _check_token_ids(arr, vocab_size)})
    fdt = np.dtype(feature_dtype)
    return ((seq_len, input_dim), fdt,
            _npy_preprocess((seq_len, input_dim), fdt), {})


def build_seqformer(name: str = "longcontext", seq_len: int = 4096,
                    input_dim: int = 64, dim: int = 128, depth: int = 2,
                    heads: int = 8, num_classes: int = 16,
                    attention: str = "auto", causal: bool = False,
                    buckets=(1, 8), mesh=None,
                    wire_dtype: str = "float16",
                    vocab_size: int | None = None, **_) -> ServableModel:
    """Long-context sequence classification (SURVEY.md §5 long-context slot):
    attention over the payload runs ring/Ulysses sequence-parallel over the
    mesh's sp axis when it has one.

    Two input contracts:

    - ``vocab_size=N`` — **token mode, the production wire**: payload is an
      (S,) integer npy of ids, embedded on-device (``nn.Embed``). 2
      bytes/token on the wire vs 128 bytes/token of pre-embedded f16
      features at D=64 (524 kB/request at S=4096).
    - ``vocab_size=None`` — feature mode: (S, input_dim) float sequences,
      e.g. embedded acoustic/satellite time series produced upstream.
      ``wire_dtype`` (float16 default, float32 accepted) carries the batch:
      the model computes bf16 regardless and f16's 10 mantissa bits exceed
      bf16's 7, so the half wire halves bytes without touching the math.
      Payloads outside f16 range fail that task at preprocess."""
    from ..models.seqformer import create_seqformer

    wdt = np.dtype(wire_dtype)
    if wdt not in (np.dtype(np.float16), np.dtype(np.float32)):
        raise ValueError(f"wire_dtype must be float16/float32, got {wire_dtype}")

    model, params = create_seqformer(
        seq_len=seq_len, input_dim=input_dim, dim=dim, depth=depth,
        heads=heads, num_classes=num_classes, mesh=mesh, attention=attention,
        causal=causal, vocab_size=vocab_size)

    def postprocess(logits):
        logits = np.asarray(logits, np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = int(np.argmax(probs))
        return {"class_id": top, "confidence": float(probs[top])}

    input_shape, input_dtype, preprocess, stack_kwargs = (
        _sequence_input_contract(seq_len, input_dim, vocab_size,
                                 feature_dtype=wdt))
    return ServableModel(
        name=name, apply_fn=model.apply, params=params,
        input_shape=input_shape, input_dtype=input_dtype,
        preprocess=preprocess,
        postprocess=postprocess, batch_buckets=tuple(buckets),
        **stack_kwargs)


def build_moe(name: str = "moe", seq_len: int = 1024, input_dim: int = 64,
              dim: int = 128, depth: int = 2, heads: int = 8,
              num_experts: int = 8, num_classes: int = 16,
              attention: str = "flash", dispatch: str = "dense",
              capacity_factor: float = 1.25, buckets=(1, 8), mesh=None,
              vocab_size: int | None = None, **_) -> ServableModel:
    """Mixture-of-Experts sequence classification — the expert-parallel
    family: expert tensors shard over the mesh's ``ep`` axis
    (``models/moe.py``), composing with dp/fsdp exactly like seqformer's sp.
    ``dispatch="capacity"`` serves the GShard-style static-capacity path.
    ``vocab_size`` switches to the token-id wire (same contract as the
    seqformer family: (S,) integer npy, embedded on-device)."""
    from ..models.moe import MOE_EP_RULES, create_moe

    model, params = create_moe(
        seq_len=seq_len, input_dim=input_dim, dim=dim, depth=depth,
        heads=heads, num_experts=num_experts, num_classes=num_classes,
        mesh=mesh, attention=attention, dispatch=dispatch,
        capacity_factor=capacity_factor, vocab_size=vocab_size)

    input_shape, input_dtype, preprocess, stack_kwargs = (
        _sequence_input_contract(seq_len, input_dim, vocab_size))
    return ServableModel(
        name=name, apply_fn=model.apply, params=params,
        input_shape=input_shape, input_dtype=input_dtype,
        preprocess=preprocess,
        postprocess=_classification_postprocess(),
        batch_buckets=tuple(buckets),
        # ModelRuntime.register re-places every param on its mesh; the rules
        # ride along so expert sharding survives registration.
        param_sharding_rules=MOE_EP_RULES, **stack_kwargs)


FAMILIES = {
    "echo": build_echo,
    "unet": build_unet,
    "resnet": build_resnet,
    "detector": build_detector,
    "vit": build_vit,
    "seqformer": build_seqformer,
    "moe": build_moe,
}


def build_seqformer_lm(name: str = "lm", vocab_size: int = 512,
                       max_len: int = 256, dim: int = 64, depth: int = 2,
                       heads: int = 4, eos_id: int | None = None, rng=None,
                       **_):
    """The GPT-2-shaped LM (``models/seqformer.py`` ``SeqFormerLM``):
    LayerNorm, learned positions, GELU, tied head, float32."""
    from ..models.seqformer import create_seqformer_lm
    from .kvcache import LMServable
    model, params = create_seqformer_lm(
        rng=rng, vocab_size=vocab_size, max_len=max_len, dim=dim,
        depth=depth, heads=heads)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_olmoe_lm(name: str = "lm", vocab_size: int = 512,
                   max_len: int = 256, eos_id: int | None = None, rng=None,
                   dtype: str = "bfloat16", **dims):
    """The sparse-expert decoder (``models/olmoe.py`` ``OlmoeLM``): RoPE,
    RMSNorm, q/k norms, top-K of E SwiGLU experts, untied head, bfloat16
    weights and cache. ``dims``: ``dim``, ``depth``, ``heads``,
    ``experts``, ``experts_per_token``, ``expert_dim``, ``rms_eps``,
    ``rope_theta``; a key the family does not know is an error, not a
    default."""
    from ..models.olmoe import create_olmoe_lm
    from .kvcache import LMServable
    model, params = create_olmoe_lm(rng=rng, vocab_size=vocab_size,
                                    dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_qwen3_next_lm(name: str = "lm", vocab_size: int = 512,
                        max_len: int = 256, eos_id: int | None = None,
                        rng=None, dtype: str = "bfloat16", **dims):
    """The hybrid decoder (``models/qwen3_next.py`` ``Qwen3NextLM``): Gated
    DeltaNet layers with a recurrent state a slot, a gated grouped-query
    attention layer every ``full_interval``-th, top-K of E experts of which
    this process holds ``experts_held`` from ``first_expert``, a shared
    expert, untied head, bfloat16 weights and K/V. ``dims``: the model's
    fields (``dim``, ``depth``, ``heads``, ``kv_heads``, ``head_dim``,
    ``rotary_dim``, ``lin_k_heads``, ``lin_v_heads``, ``lin_dim``, ``conv``,
    ``experts``, ...); a key the family does not know is an error, not a
    default."""
    from ..models.qwen3_next import create_qwen3_next_lm
    from .kvcache import LMServable
    model, params = create_qwen3_next_lm(rng=rng, vocab_size=vocab_size,
                                         dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_granite_hybrid_lm(name: str = "lm", vocab_size: int = 512,
                            max_len: int = 256, eos_id: int | None = None,
                            rng=None, dtype: str = "bfloat16", **dims):
    """The state-space hybrid (``models/granite_hybrid.py``
    ``GraniteHybridLM``): Mamba-2 layers with a recurrent state a slot,
    grouped-query attention without positions at ``attention_layers``, a
    dense gated MLP, four scalar multipliers, the head tied to the
    embedding, bfloat16 weights and K/V. ``dims``: the model's fields
    (``dim``, ``depth``, ``attention_layers``, ``heads``, ``kv_heads``,
    ``head_dim``, ``mlp_dim``, ``ssm_heads``, ``ssm_head_dim``,
    ``ssm_state``, ``conv``, ``chunk``, the multipliers, ...); a key the
    family does not know is an error, not a default."""
    from ..models.granite_hybrid import create_granite_hybrid_lm
    from .kvcache import LMServable
    model, params = create_granite_hybrid_lm(rng=rng, vocab_size=vocab_size,
                                             dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_dots3_lm(name: str = "lm", vocab_size: int = 512,
                   max_len: int = 256, eos_id: int | None = None,
                   rng=None, dtype: str = "bfloat16", **dims):
    """The latent-attention decoder (``models/dots3.py`` ``Dots3LM``): a
    latent row a position that every head shares, on the ``full`` layers of
    ``layer_types`` behind an indexer's exact top-``index_topk`` selection
    (its key cached beside the row), on the ``sliding`` ones a ring of the
    window with ranks and heads of its own (``swa_*``); a head-wise gate;
    ``dense_layers`` leading dense MLPs, then sigmoid-routed experts of which
    this process holds ``experts_held`` from ``first_expert``, with an
    ungated shared expert; untied head, bfloat16 weights and cache.
    ``dims``: the model's fields; a key the family does not know is an
    error, not a default."""
    from ..models.dots3 import create_dots3_lm
    from .kvcache import LMServable
    model, params = create_dots3_lm(rng=rng, vocab_size=vocab_size,
                                    dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_xing4_lm(name: str = "lm", vocab_size: int = 512,
                   max_len: int = 256, eos_id: int | None = None,
                   rng=None, dtype: str = "bfloat16", **dims):
    """The hyper-connected latent-attention decoder (``models/xing4.py``
    ``Xing4LM``): ``streams`` residual streams a token, mixed around every
    sublayer through a Sinkhorn-normalised matrix (``ops/mhc.py``); dense
    latent attention under YaRN, one latent row a position; ``dense_layers``
    leading dense MLPs, then sigmoid-routed experts, all held, with an
    ungated shared expert; untied head, bfloat16 weights and cache.
    ``dims``: the model's fields; a key the family does not know is an
    error, not a default."""
    from ..models.xing4 import create_xing4_lm
    from .kvcache import LMServable
    model, params = create_xing4_lm(rng=rng, vocab_size=vocab_size,
                                    dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_ling3_lm(name: str = "lm", vocab_size: int = 512,
                   max_len: int = 256, eos_id: int | None = None,
                   rng=None, dtype: str = "bfloat16", **dims):
    """The KDA / latent-attention hybrid (``models/ling3.py`` ``Ling3LM``):
    Kimi Delta Attention layers — a recurrent state a slot that decays by a
    bounded gate a key channel — with a latent-attention layer behind a
    head-wise gate every ``group``-th, one latent row a position;
    ``dense_layers`` leading dense MLPs, then sigmoid-routed experts chosen
    inside the best ``route_groups[1]`` of ``route_groups[0]`` groups, of
    which this process holds ``experts_held`` from ``first_expert``, with an
    ungated shared expert; untied head, bfloat16 weights and cache.
    ``dims``: the model's fields and the published SwiGLU limits of the held
    layers (all 0, or an error); a key the family does not know is an
    error, not a default."""
    from ..models.ling3 import create_ling3_lm
    from .kvcache import LMServable
    model, params = create_ling3_lm(rng=rng, vocab_size=vocab_size,
                                    dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_glm5_lm(name: str = "lm", vocab_size: int = 512,
                  max_len: int = 256, eos_id: int | None = None,
                  rng=None, dtype: str = "bfloat16", **dims):
    """The hyper-connected KDA / sparse-latent hybrid (``models/glm5.py``
    ``Glm5LM``): ``streams`` residual streams a token around every sublayer
    (``ops/mhc.py``); a layer mixes by Kimi Delta Attention (``layer_types``
    ``kda``: ``models/ling3.py``'s recurrence, the decay and the output gate
    through a rank of ``kda_lora``) or by latent attention without positions
    over a learned selection (``sparse``: a row a position that is all value;
    the indexer's keys pooled ``index_pool`` at a time, one cached row a
    block, ``index_topk`` positions kept as whole blocks, the query's own
    always); ``mlp_types`` says which FFNs are a dense SwiGLU and which
    sigmoid-routed experts, of which this process holds ``experts_held``
    from ``first_expert``, with an ungated shared expert; every SwiGLU
    clamped at ``swiglu_limit``; untied head, bfloat16 weights and cache.
    ``dims``: the model's fields; a key the family does not know is an
    error, not a default."""
    from ..models.glm5 import create_glm5_lm
    from .kvcache import LMServable
    model, params = create_glm5_lm(rng=rng, vocab_size=vocab_size,
                                   dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


def build_axk1_lm(name: str = "lm", vocab_size: int = 512,
                  max_len: int = 256, eos_id: int | None = None,
                  rng=None, dtype: str = "bfloat16", **dims):
    """The dense latent-attention decoder (``models/axk1.py`` ``Axk1LM``): a
    plain pre-norm residual around ``models/latent.py``'s mixer — the one
    ``xing4`` runs inside its hyper-connections — under YaRN, one latent row
    a position on every layer; ``dense_layers`` leading dense MLPs, then
    sigmoid-routed experts chosen without a bias (inside ``route_groups``
    where given), of which this process holds ``experts_held`` from
    ``first_expert``, with an ungated shared expert; untied head, bfloat16
    weights and cache. ``dims``: the model's fields; a key the family does
    not know is an error, not a default."""
    from ..models.axk1 import create_axk1_lm
    from .kvcache import LMServable
    model, params = create_axk1_lm(rng=rng, vocab_size=vocab_size,
                                   dtype=dtype, **dims)
    return LMServable(name=name, model=model, params=params,
                      vocab_size=vocab_size, max_len=max_len, eos_id=eos_id)


# LM families ride the decode engine (``runtime/decode.py``), never the
# MicroBatcher: ``cli`` tells them from the batch families by this table.
LM_FAMILIES = {
    "seqformer-lm": build_seqformer_lm,
    "olmoe": build_olmoe_lm,
    "qwen3-next": build_qwen3_next_lm,
    "granite-hybrid": build_granite_hybrid_lm,
    "dots3": build_dots3_lm,
    "xing4": build_xing4_lm,
    "ling3": build_ling3_lm,
    "glm5": build_glm5_lm,
    "axk1": build_axk1_lm,
}


def build_servable(family: str, **kwargs) -> ServableModel:
    if family not in FAMILIES:
        raise ValueError(
            f"unknown model family {family!r}; valid: {sorted(FAMILIES)}")
    return FAMILIES[family](**kwargs)
