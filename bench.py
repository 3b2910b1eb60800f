"""Platform benchmark — async inference through the full stack.

Measures BASELINE.json's north-star metric: async inference requests/second
(+ p50 task latency), end-to-end through gateway → task store → broker →
dispatcher → worker → micro-batcher → device, in this process, on the
locally attached TPU. A run that finds no TPU exits non-zero naming the
platform it found; ``--cpu`` is the explicit test mode (correctness and
counts only — a CPU number is never a device metric). Every result carries
``platform``, ``device_kind`` and ``device_count`` as JAX reports them.

``--model`` selects the measurement config (BASELINE.json `configs`):
- ``landcover`` (default, the headline metric): land-cover segmentation
  tiles, config #2;
- ``megadetector``: camera-trap detection, config #3;
- ``species``: species classification, config #4.
The detector/classifier configs serve REAL trained weights: checkpoints from
``ai4e_tpu.train.make_checkpoints`` under ``--checkpoint-dir`` (trained
in-process first if absent — the run says so in ``trained_at_bench``).
``landcover`` also loads a checkpoint when one exists.

Baseline anchors: the reference publishes no numbers (BASELINE.md), so each
anchor is an NC6s_v3 (1× V100) estimate for the equivalent model container
served one-request-per-POST (the reference's dispatch model — no
cross-request batching; ``BackendQueueProcessor.cs:27-81`` POSTs one task at
a time): ~40 tiles/s for the UNet, ~10 img/s for a MegaDetector-class
detector, ~100 img/s for the classifier. ``vs_baseline`` = measured / anchor;
the BASELINE.md target (≥4× NC6s_v3) is met when vs_baseline ≥ 4.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "req/s", "vs_baseline": N, ...extras}
"""

from __future__ import annotations

import argparse
import asyncio
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

TILE = 256

# NC6s_v3 one-request-per-POST anchors (see module docstring) and the
# request payload dtype per measurement config.
CONFIGS = {
    # base-py echo (BASELINE config #1, the CPU transport smoke): no model
    # weight — measures the platform path itself. Anchor: the reference's
    # Flask dev-server echo served one-request-per-POST on a DS2_v2,
    # ~200 req/s.
    "echo": {"anchor": 200.0, "metric": "async_echo_throughput"},
    "landcover": {"anchor": 40.0, "metric": "async_landcover_seg_throughput"},
    "megadetector": {"anchor": 10.0,
                     "metric": "async_megadetector_throughput"},
    "species": {"anchor": 100.0, "metric": "async_species_cls_throughput"},
    # Composite detector→classifier ensemble (BASELINE config #5): one
    # JPEG, two model stages under one TaskId via original-body replay.
    # Anchor: the reference's serial two-stage dispatch of a V100 detector
    # (~10/s) then classifier — the detector dominates, ~8 composite/s.
    "pipeline": {"anchor": 8.0, "metric": "async_pipeline_throughput"},
    # Long-context sequence classification (SURVEY.md §5 long-context slot,
    # no reference analogue): SeqFormer with the fused flash-attention
    # Pallas kernel on the serving path. Anchor: a V100 transformer encoder
    # at S=4k served one-per-POST, ~50 seq/s.
    "longcontext": {"anchor": 50.0, "metric": "async_longcontext_throughput"},
    # Mixed multi-API serving (VERDICT r3 #7): ALL FIVE model families on
    # ONE worker/chip — interactive landcover + species + longcontext + moe
    # loops with a background megadetector batch stack saturating the
    # device. The reference's whole point is many APIs per cluster
    # (APIs/Charts/camera-trap side-by-side), which it achieves with
    # separate container pools; here priority classes share one chip.
    # Value = summed INTERACTIVE req/s while the stack runs; anchor = the
    # interactive families' one-per-POST anchors summed (40 + 100 + 50).
    "mixed": {"anchor": 190.0, "metric": "mixed_workload_throughput"},
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Per-chip peak bf16 FLOP/s (every family computes bf16, models/*.py) — the
# MFU denominator — keyed by the exact ``device_kind`` JAX reports, each with
# the source of its figure. A TPU that is not in the table is an error, not a
# default: add the row with its source before benching on it.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (/opt/skills/guides/on-chip-measurement §3).
    "TPU v5 lite": 197e12,
}


def _device_fields() -> dict:
    """The device behind a result, as JAX reports it — on every result."""
    from ai4e_tpu.runtime.registry import device_report
    report = device_report()
    return {key: report[key]
            for key in ("platform", "device_kind", "device_count")}


def _require_tpu() -> None:
    """The measurement path runs on the chip or not at all."""
    device = _device_fields()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"bench: no TPU — JAX found platform {device['platform']!r} "
            f"({device['device_count']} x {device['device_kind']!r}). "
            "Nothing was measured. --cpu is the explicit CPU test mode.")


def _peak_flops_per_chip() -> float | None:
    """bf16 peak of the chip under measurement; None in ``--cpu`` test
    mode (no MFU is claimed off the chip)."""
    device = _device_fields()
    if device["platform"] != "tpu":
        return None
    if device["device_kind"] not in PEAK_BF16_FLOPS:
        raise SystemExit(
            f"bench: no peak FLOP/s on record for device_kind "
            f"{device['device_kind']!r}; add it to PEAK_BF16_FLOPS with "
            "its source")
    return PEAK_BF16_FLOPS[device["device_kind"]]


def _validated_kernels() -> dict:
    """On the chip the bench doubles as the Pallas kernel-validation
    artifact: Mosaic-compiled (interpret=False) kernels vs XLA oracles +
    VMEM-budget assertions (ops/pallas/validate.py). A kernel that fails
    fails the run."""
    from ai4e_tpu.ops.pallas.validate import validate_kernels
    result = validate_kernels(interpret=False)
    if not result["all_ok"]:
        raise SystemExit(f"bench: Pallas kernel validation failed on the "
                         f"chip: {result}")
    return result


def _model_flops_per_batch(servable, bucket: int) -> float | None:
    """FLOPs of one compiled batch execution, from XLA's own cost model
    (``Compiled.cost_analysis()``) — the numerator for MFU. None when the
    backend reports no flops for the program."""
    import jax
    dummy = jax.ShapeDtypeStruct((bucket, *servable.input_shape),
                                 np.dtype(servable.input_dtype))
    compiled = servable._compiled.lower(servable.params, dummy).compile()
    flops = float((compiled.cost_analysis() or {}).get("flops", 0.0))
    return flops if flops > 0 else None


def _load_or_train_checkpoint(name: str, ckpt_dir: str, like,
                              required: bool) -> tuple[object, dict]:
    """Restore trained weights for ``name`` from ``ckpt_dir`` (producing them
    first when ``required`` and absent — configs #3/#4 must never serve
    random init)."""
    import os

    from ai4e_tpu.checkpoint import load_params

    path = os.path.abspath(os.path.join(ckpt_dir, name))
    meta: dict = {}
    if not os.path.isdir(path):
        if not required:
            return like, {"checkpoint": "none"}
        # train_full (not bare make_checkpoint): trains at the production
        # serving size AND records it in the manifest — a recipe-default
        # 64px training served at 224 would score chance.
        from ai4e_tpu.train.make_checkpoints import train_full
        log(f"no checkpoint at {path}; training {name} now")
        t0 = time.perf_counter()
        train_full(name, ckpt_dir)
        meta["trained_at_bench_s"] = round(time.perf_counter() - t0, 1)
    params = load_params(path, like=like)
    meta["checkpoint"] = path
    return params, meta


def _manifest_kwargs(ckpt_dir: str, name: str) -> tuple[dict, bool]:
    """``(kwargs, from_manifest)`` for ``name``: the factory's recorded
    servable kwargs, or recipe defaults when no manifest entry exists."""
    import os

    path = os.path.join(ckpt_dir, "MANIFEST.json")
    if os.path.exists(path):
        with open(path) as f:
            manifest = json.load(f)
        if name in manifest:
            return dict(manifest[name].get("kwargs", {})), True
    from ai4e_tpu.train.make_checkpoints import SPECIES_LABELS
    return {"megadetector": {"widths": [64, 128, 256]},
            "landcover": {"widths": [64, 128, 256, 512], "num_classes": 4},
            "species": {"stage_sizes": [2, 2, 2], "width": 32,
                        "num_classes": 8, "labels": SPECIES_LABELS},
            "longcontext": {}}[name], False


def _serving_size(kwargs: dict, from_manifest: bool, name: str) -> int:
    """The size to BUILD and SERVE at — always the size the weights were
    (or will be) trained at:
    - manifest records image_size → that;
    - manifest entry predates the record → the old factory's training size
      (serving 128-trained detector weights at 512 scores ~chance);
    - no manifest at all → the production size train_full is about to
      train at."""
    migration_fallback = {"megadetector": 128, "species": 64}
    production = {"megadetector": 512, "species": 224}
    if "image_size" in kwargs:
        return kwargs.pop("image_size")
    return (migration_fallback if from_manifest else production)[name]


def _servable_wire(args) -> str:
    """The h2d wire the servable is BUILT with. ``--wire jpeg`` is a CLIENT
    wire (camera-trap clients have JPEGs, ``families._image_preprocess``
    decodes them host-side); the host→device leg then uses the best
    compressed wire (yuv420 — JPEG's own chroma layout). h2d bytes are
    reported separately from client wire bytes so the two links never get
    conflated."""
    return {"jpeg": "yuv420"}.get(args.wire, args.wire)


def _encode_jpeg(arr: np.ndarray, quality: int = 85) -> bytes:
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, "JPEG", quality=quality)
    return buf.getvalue()


def _build_servable(args):
    """The measured servable + its request payload builder."""
    import os

    if args.model == "echo":
        from ai4e_tpu.runtime import build_servable
        servable = build_servable("echo", name="echo", size=16,
                                  buckets=tuple(args.buckets))
        buf = io.BytesIO()
        np.save(buf, np.arange(16, dtype=np.float32))
        return servable, buf.getvalue(), {}
    if args.model == "landcover":
        servable = _build_landcover(args)
        # Headline config serves trained weights AT THE PRODUCTION TILE;
        # a non-default --tile serves random init unless a tile-specific
        # checkpoint exists — the UNet is fully convolutional so weights
        # would restore, but a resized run must not imply trained fidelity.
        if args.tile == TILE:
            servable.params, meta = _load_or_train_checkpoint(
                "landcover", args.checkpoint_dir, servable.params,
                required=False)
        else:
            # Tile-specific checkpoint (the factory's landcover128
            # recipe). Absent one, the result says so.
            servable.params, meta = _load_or_train_checkpoint(
                f"landcover{args.tile}", args.checkpoint_dir,
                servable.params, required=False)
            if meta.get("checkpoint") == "none":
                meta = {"checkpoint":
                        f"none (no landcover{args.tile} checkpoint)"}
        meta["wire"] = args.wire
        meta["tile"] = args.tile
        rng = np.random.default_rng(0)
        payload_arr = rng.integers(0, 256, size=(args.tile, args.tile, 3),
                                   dtype=np.uint8)
        if args.wire == "jpeg":
            return (servable, _encode_jpeg(payload_arr),
                    dict(meta, content_type="image/jpeg"))
    elif args.model == "longcontext":
        from ai4e_tpu.runtime import build_servable
        tokens = args.seq_input == "tokens"
        vocab = 32768 if tokens else None
        # heads=2 -> head_dim 128 = the MXU's lane width: measured 3.4x the
        # heads=8/head_dim=32 geometry on v5e (52 -> 180 seq/s at depth 4,
        # batch 64) — attention FLOPs are identical, only the matmul tiling
        # changes. TPU-first model geometry, not a capacity change.
        sf_kwargs = dict(seq_len=args.seq_len, input_dim=64, dim=256,
                         depth=4, heads=2, num_classes=16,
                         attention="flash", vocab_size=vocab)
        ckpt_meta: dict = {"checkpoint": "none"}
        use_ckpt = False
        if tokens:
            # Serve trained weights when the factory produced them AT THIS
            # geometry: the token tree's seq_len/vocab are STRUCTURAL
            # (pos_emb/Embed shapes), so a manifest whose seq_len differs
            # from --seq-len (e.g. a --fast CI manifest at 256) must NOT
            # silently shrink the measured config — the anchor is for the
            # headline sequence length. Mismatch → random init, logged.
            mf_kwargs, from_manifest = _manifest_kwargs(
                args.checkpoint_dir, "longcontext")
            if from_manifest and mf_kwargs.get("seq_len") == args.seq_len:
                sf_kwargs.update(mf_kwargs)
                vocab = sf_kwargs["vocab_size"]
                use_ckpt = True
            elif from_manifest:
                log(f"longcontext manifest geometry (seq_len="
                    f"{mf_kwargs.get('seq_len')}) != --seq-len "
                    f"{args.seq_len}; serving random init at the CLI "
                    "geometry")
        servable = build_servable(
            "seqformer", name="longcontext", buckets=tuple(args.buckets),
            **sf_kwargs)
        if use_ckpt:
            # Gated on the manifest entry (not bare dir existence): a
            # checkpoint dir without its manifest record has unknown
            # geometry, and for this family any drift is a shape mismatch
            # at restore.
            servable.params, ckpt_meta = _load_or_train_checkpoint(
                "longcontext", args.checkpoint_dir, servable.params,
                required=False)
        rng = np.random.default_rng(0)
        if tokens:
            # Production wire: (S,) narrow integer token ids, embedded
            # on-device — 2 bytes/token (uint16, vocabs ≤64k) vs the
            # feature wire's 128 (f16 D=64).
            wire_dt = np.uint16 if vocab <= 2**16 else np.uint32
            payload_arr = rng.integers(0, vocab, size=(args.seq_len,),
                                       dtype=wire_dt)
            meta = {"seq_len": args.seq_len,
                    "attention": sf_kwargs["attention"],
                    "wire": f"tokens-{np.dtype(wire_dt).name}",
                    "vocab_size": vocab, **ckpt_meta}
        else:
            # f16 feature wire (the family's default wire_dtype): halves
            # both the client payload and the host→device transfer vs f32;
            # the model computes in bf16 either way.
            payload_arr = rng.standard_normal(
                (args.seq_len, 64)).astype(np.float16)
            meta = {"seq_len": args.seq_len, "attention": "flash",
                    "wire_dtype": "float16"}
    else:
        from ai4e_tpu.runtime import build_servable

        # Servable kwargs come from the checkpoint factory's MANIFEST (the
        # exact tree the weights restore into); fall back to the factory's
        # recipe defaults when no manifest exists yet (it will be written by
        # the required=True training below).
        family = "detector" if args.model == "megadetector" else "resnet"
        kwargs, from_manifest = _manifest_kwargs(args.checkpoint_dir,
                                                 args.model)
        # Serving size = TRAINED size: accuracy does not transfer across
        # input sizes for these families — a 64-trained classifier scores
        # chance at 224 (_serving_size resolves every manifest state).
        image_size = _serving_size(kwargs, from_manifest, args.model)
        servable = build_servable(
            family, name=args.model, image_size=image_size,
            buckets=tuple(args.buckets), wire=_servable_wire(args), **kwargs)
        shape = (image_size, image_size, 3)
        servable.params, meta = _load_or_train_checkpoint(
            args.model, args.checkpoint_dir, servable.params, required=True)
        meta["wire"] = args.wire
        meta["image_size"] = image_size
        rng = np.random.default_rng(0)
        # uint8 wire format (families' fused_normalize ingestion): 4x less
        # payload than float32, normalized on-device.
        payload_arr = rng.integers(0, 256, size=shape, dtype=np.uint8)
        if args.wire == "jpeg":
            return (servable, _encode_jpeg(payload_arr),
                    dict(meta, content_type="image/jpeg"))
    buf = io.BytesIO()
    np.save(buf, payload_arr)
    return servable, buf.getvalue(), meta


def _build_pipeline_servables(args):
    """Detector→classifier composite (config #5): trained detector at its
    training resolution (so the synthetic scenes actually trigger the
    handoff gate) feeding the species classifier via original-body replay.
    The wire format is JPEG — the only payload both stages can consume at
    their own resolutions (families' image/* path decodes + resizes)."""
    from ai4e_tpu.runtime import build_servable
    from ai4e_tpu.train.make_checkpoints import detector_batch

    det_kwargs, det_mf = _manifest_kwargs(args.checkpoint_dir, "megadetector")
    det_size = _serving_size(det_kwargs, det_mf, "megadetector")
    det = build_servable(
        "detector", name="megadetector", image_size=det_size,
        score_threshold=0.15, buckets=tuple(args.buckets),
        wire=_servable_wire(args), **det_kwargs)
    det.params, m1 = _load_or_train_checkpoint(
        "megadetector", args.checkpoint_dir, det.params, required=True)
    sp_kwargs, sp_mf = _manifest_kwargs(args.checkpoint_dir, "species")
    sp_size = _serving_size(sp_kwargs, sp_mf, "species")
    sp = build_servable(
        "resnet", name="species", image_size=sp_size,
        buckets=tuple(args.buckets), wire=_servable_wire(args), **sp_kwargs)
    sp.params, m2 = _load_or_train_checkpoint(
        "species", args.checkpoint_dir, sp.params, required=True)

    # Probe scene at the detector's trained size (the handoff gate fires at
    # the resolution the weights know).
    img, _ = detector_batch(np.random.default_rng(0), 1, det_size)
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(
        np.clip(np.round(img[0] * 255), 0, 255).astype(np.uint8)
    ).save(buf, "JPEG", quality=92)
    meta = {"detector_checkpoint": m1.get("checkpoint"),
            "species_checkpoint": m2.get("checkpoint"),
            "wire": args.wire}
    return det, sp, buf.getvalue(), meta


# --mix: named traffic profiles bundling the deadline/priority/fault
# knobs (docs/orchestration.md). A preset only fills knobs the caller
# left at their defaults — an explicit --deadline-ms beside --mix wins.
MIX_PRESETS = {
    "interactive-heavy": {
        "priority_mix": "interactive:7,default:2,background:1",
        "deadline_ms": 2000.0,
    },
    "batch-heavy": {
        "priority_mix": "interactive:1,default:2,background:7",
        "deadline_ms": 8000.0,
    },
    "faulty-mixed": {
        "priority_mix": "interactive:5,default:3,background:2",
        "deadline_ms": 2000.0,
        "fault_rate": 0.1,
        "resilience": True,
    },
}

_MIX_DEFAULTS = {"priority_mix": "", "deadline_ms": 0.0, "fault_rate": 0.0,
                 "resilience": False}


def apply_mix_preset(args) -> None:
    """Expand ``--mix`` into its concrete knobs (defaults-only — explicit
    flags win). Idempotent, so the orchestrator and its boxed inner
    subprocess can both call it."""
    name = getattr(args, "mix", "") or ""
    if not name:
        return
    preset = MIX_PRESETS.get(name)
    if preset is None:
        raise SystemExit(
            f"unknown --mix {name!r}; available: {sorted(MIX_PRESETS)}")
    for knob, value in preset.items():
        if getattr(args, knob) == _MIX_DEFAULTS[knob]:
            setattr(args, knob, value)


def _admission_enabled(args) -> bool:
    return (getattr(args, "deadline_ms", 0.0) > 0
            or bool(getattr(args, "priority_mix", ""))
            or bool(getattr(args, "orchestration", False)))


def _parse_priority_mix(spec: str) -> list[tuple[str, float]]:
    """``"interactive:6,default:3,background:1"`` → weighted classes.
    Bare class names weight 1 (``"interactive,background"``)."""
    mix = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, _, w = part.partition(":")
            mix.append((name.strip(), float(w)))
        else:
            mix.append((part, 1.0))
    if not mix:
        raise ValueError(f"empty --priority-mix {spec!r}")
    return mix


def _admission_drivers(args):
    """``(headers_for, deadline_s)`` for the load client: per-request
    X-Deadline-Ms plus a weighted X-Priority draw (seeded — runs are
    reproducible)."""
    if not _admission_enabled(args):
        return None, None
    import random as _random
    rng = _random.Random(2)
    mix = _parse_priority_mix(args.priority_mix) if args.priority_mix else None
    base = ({"X-Deadline-Ms": str(int(args.deadline_ms))}
            if args.deadline_ms > 0 else {})
    if mix:
        names = [n for n, _ in mix]
        weights = [w for _, w in mix]

        def headers_for():
            return {**base,
                    "X-Priority": rng.choices(names, weights=weights)[0]}
    else:
        def headers_for():
            return dict(base)

    deadline_s = args.deadline_ms / 1000.0 if args.deadline_ms > 0 else None
    return headers_for, deadline_s


def _admission_report(args, platform) -> dict:
    """The bench artifact's admission block: knobs + the ai4e_admission_*
    counters/gauges accumulated over the run (shed/expired by hop and
    priority, adaptive limits by scope, goodput outcomes)."""
    adm = getattr(platform, "admission", None)
    if adm is None:
        return {}
    reg = platform.metrics

    def counter_by_labels(name, keys):
        out = {}
        for _, _, labels, v in reg.counter(name, "").collect():
            out["/".join(labels.get(k, "") for k in keys)] = int(v)
        return out

    limits = {}
    for _, _, labels, v in reg.gauge("ai4e_admission_limit", "").collect():
        limits[labels.get("scope", "")] = int(v)
    return {"admission": {
        "deadline_ms": args.deadline_ms,
        "priority_mix": args.priority_mix or None,
        # *_by_hop: server-side counters; the client-observed window counts
        # (goodput/late/expired) are merged in by the caller under their
        # own keys.
        "shed_by_hop": counter_by_labels("ai4e_admission_shed_total",
                                         ("hop", "priority")),
        "expired_by_hop": counter_by_labels("ai4e_admission_expired_total",
                                            ("hop", "priority")),
        "limits": limits,
        "goodput_outcomes": counter_by_labels(
            "ai4e_admission_goodput_total", ("outcome",)),
    }}


def _parse_tenant_mix(spec: str) -> list[tuple[str, float, float, float]]:
    """``"paid=3:50,trial=1:5"`` → ``[(name, weight, rps, share)]``.

    ``name=weight:rps[:share]`` — *weight* is the tenant's fair-share
    weight AND its declared quota shape (burst defaults inside the
    registry), *rps* its token-bucket rate, *share* its fraction of the
    offered traffic draw (defaults to *weight*, so a 3:1 weight split is
    also a 3:1 traffic split unless overridden). Subscription keys are
    synthesized as ``key-<name>``."""
    mix = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, rest = part.partition("=")
        name = name.strip()
        if not sep or not name:
            raise ValueError(
                f"--tenant-mix entry {part!r}: expected name=weight:rps")
        fields = [f.strip() for f in rest.split(":")]
        if len(fields) not in (2, 3):
            raise ValueError(
                f"--tenant-mix entry {part!r}: expected name=weight:rps"
                f"[:share], got {len(fields)} field(s)")
        try:
            weight, rps = float(fields[0]), float(fields[1])
            share = float(fields[2]) if len(fields) == 3 else weight
        except ValueError:
            raise ValueError(
                f"--tenant-mix entry {part!r}: weight/rps/share must be "
                f"numbers") from None
        if any(n == name for n, *_ in mix):
            raise ValueError(f"--tenant-mix tenant {name!r} declared twice")
        mix.append((name, weight, rps, share))
    if not mix:
        raise ValueError(f"empty --tenant-mix {spec!r}")
    return mix


def _tenant_spec(args) -> str | None:
    """The registry spec (``name=key:weight:rps``) the platform assembles
    from, derived from ``--tenant-mix``."""
    if not getattr(args, "tenant_mix", ""):
        return None
    return ",".join(f"{name}=key-{name}:{weight:g}:{rps:g}"
                    for name, weight, rps, _ in
                    _parse_tenant_mix(args.tenant_mix))


def _tenant_drivers(args):
    """``(tenant_headers_for, tenant_names)`` for the load client: each
    POST draws a subscription key by the mix's share weights (seeded —
    runs are reproducible); ``tenant_names`` maps key → tenant so the
    client buckets its window per tenant."""
    if not getattr(args, "tenant_mix", ""):
        return None, None
    import random as _random
    rng = _random.Random(3)
    mix = _parse_tenant_mix(args.tenant_mix)
    names = [name for name, *_ in mix]
    shares = [share for *_, share in mix]
    keys = {name: f"key-{name}" for name in names}

    def tenant_headers_for():
        return {"Ocp-Apim-Subscription-Key":
                keys[rng.choices(names, weights=shares)[0]]}

    return tenant_headers_for, {keys[n]: n for n in names}


def _tenancy_report(args, platform) -> dict:
    """The bench artifact's tenancy block: the mix + the ai4e_tenant_*
    series accumulated over the run (edge admissions/quota sheds, terminal
    outcomes, charged cost, SLO burn) keyed per tenant."""
    ten = getattr(platform, "tenancy", None)
    if ten is None:
        return {}
    reg = platform.metrics

    def counter_by_labels(name, keys, cast=int):
        out = {}
        for _, _, labels, v in reg.counter(name, "").collect():
            out["/".join(labels.get(k, "") for k in keys)] = cast(v)
        return out

    names = [name for name, *_ in _parse_tenant_mix(args.tenant_mix)]
    return {"tenancy": {
        "tenant_mix": args.tenant_mix,
        # Edge decisions and terminal outcomes by tenant; labels are the
        # registry's bounded set (frozen top-N + "other"), never raw keys.
        "admissions": counter_by_labels(
            "ai4e_tenant_admissions_total", ("tenant", "decision")),
        "outcomes": counter_by_labels(
            "ai4e_tenant_outcomes_total", ("tenant", "outcome")),
        "cost": counter_by_labels(
            "ai4e_tenant_cost_total", ("tenant",),
            cast=lambda v: round(float(v), 3)),
        "slo_burn": {n: round(ten.accounting.burn_rate(n), 3)
                     for n in names},
    }}


def _orchestration_report(args, platform) -> dict:
    """The bench artifact's orchestration block: placement outcomes,
    ladder posture, and brownout refusals accumulated over the run."""
    orch = getattr(platform, "orchestration", None)
    if orch is None:
        return {}
    reg = platform.metrics
    placements: dict[str, int] = {}
    for _, _, labels, v in reg.counter(
            "ai4e_orchestration_placements_total", "").collect():
        key = labels.get("outcome", "")
        placements[key] = placements.get(key, 0) + int(v)
    transitions = int(sum(v for *_, v in reg.counter(
        "ai4e_orchestration_ladder_transitions_total", "").collect()))
    refusals = int(sum(v for *_, v in reg.counter(
        "ai4e_orchestration_brownout_refusals_total", "").collect()))
    return {"orchestration": {
        "enabled": True,
        "mix": getattr(args, "mix", "") or None,
        "placements": placements,
        "ladder_level_final": orch.ladder.level,
        "ladder_transitions": transitions,
        "brownout_refusals": refusals,
    }}


def build_platform(args):
    from aiohttp import web  # noqa: F401 — ensure aiohttp present early

    from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
    from ai4e_tpu.runtime import (
        InferenceWorker,
        MicroBatcher,
        ModelRuntime,
        enable_compilation_cache,
    )

    enable_compilation_cache()
    fsync_policy = getattr(args, "fsync_policy", "")
    journal_dir = None
    if fsync_policy:
        journal_dir = tempfile.mkdtemp(prefix="ai4e-bench-journal")
        # The journal holds the whole run's append volume — reap it at
        # process exit or repeated runs fill the bench box's temp dir.
        import atexit
        import shutil
        atexit.register(shutil.rmtree, journal_dir, True)
    platform = LocalPlatform(PlatformConfig(
        transport=args.transport,
        native_store=args.fabric == "native",
        native_broker=(args.fabric == "native"
                       and args.transport == "queue"),
        # --fsync-policy: journal the task store under the given policy
        # (docs/durability.md) so the run pays the real append(+fsync)
        # cost on the task hot path; the result JSON gains a `journal`
        # block (bytes appended, fsyncs, compactions, append p99).
        # Without the flag the bench stays journal-less as before.
        journal_path=(os.path.join(journal_dir, "journal")
                      if journal_dir else None),
        taskstore_fsync=fsync_policy or None,
        retry_delay=0.05, dispatcher_concurrency=args.dispatcher_concurrency,
        # --cache-hit-ratio > 0 enables the inference result cache +
        # single-flight coalescing (rescache/) for the duplicate-mix run.
        result_cache=getattr(args, "cache_hit_ratio", 0.0) > 0,
        # --deadline-ms / --priority-mix enable admission control
        # (ai4e_tpu/admission/): deadline-aware shedding at every hop +
        # adaptive dispatcher/sync concurrency. Sized for the bench: the
        # limiter starts near the configured fan-out instead of probing up
        # from cold inside the measured window.
        admission=_admission_enabled(args),
        admission_initial_limit=max(8, args.dispatcher_concurrency // 8),
        admission_max_limit=max(256, args.dispatcher_concurrency),
        admission_max_backlog=max(256, args.concurrency * 4),
        # --resilience enables per-backend breakers + budget-bounded
        # retries (ai4e_tpu/resilience/) — the A/B lever for the
        # --fault-rate goodput-under-failure runs.
        resilience=(getattr(args, "resilience", False)
                    or getattr(args, "orchestration", False)),
        # --orchestration enables deadline/cost-aware placement, the
        # brownout ladder, and predictive scaling (ai4e_tpu/
        # orchestration/) — it composes admission + resilience, so both
        # are forced on with it (docs/orchestration.md).
        orchestration=getattr(args, "orchestration", False),
        # --task-shards N shards the task keyspace (taskstore/sharding.py,
        # docs/sharding.md): N store shards + per-shard dispatcher
        # sub-queues; the control-plane-headroom lever. Journal-less here
        # (no per-append fsync): the run measures keyspace partitioning,
        # not disk.
        task_shards=getattr(args, "task_shards", 1),
        # --tenant-mix declares tenants (tenancy/, docs/tenancy.md):
        # subscription keys resolve at the gateway edge, token-bucket
        # quotas shed over-rate tenants with 429 + Retry-After, and the
        # broker dequeues weighted-fair across per-tenant lanes. The
        # result JSON gains a `tenancy` block (per-tenant admissions/
        # outcomes/cost/burn) beside the client's by_tenant window.
        tenancy=bool(getattr(args, "tenant_mix", "")),
        tenancy_tenants=_tenant_spec(args),
        # --observability enables the hop ledger + flight recorder on
        # the control plane (observability/, docs/observability.md); the
        # batcher's device-phase decomposition + worker ledger flushes
        # ride the same flag, so the result JSON gains the ``phases``
        # block (queue-wait/h2d/execute/d2h percentiles + overlap
        # ratio).
        observability=getattr(args, "observability", False)))
    # --mesh dp=N[,tp=M[,sp=K]] serves through the mesh plane
    # (runtime/mesh/, docs/mesh_serving.md): the layout is validated
    # against the visible devices, batches/params placed by NamedSharding,
    # and the worker wrapped in a MeshEndpoint below so failure semantics
    # (poisoned rows, health gating) match production. On --cpu the
    # substrate is a host-device mesh — main() forces
    # jax_num_cpu_devices to the layout size before backend init.
    mesh_layout = None
    if getattr(args, "mesh", ""):
        from ai4e_tpu.runtime.mesh import parse_mesh_spec
        from ai4e_tpu.runtime.mesh.placement import mesh_for_layout
        mesh_layout = parse_mesh_spec(args.mesh)
    if mesh_layout is not None:
        runtime = ModelRuntime(mesh=mesh_for_layout(mesh_layout),
                               donate_batch=args.donate_batch)
    else:
        runtime = ModelRuntime(donate_batch=args.donate_batch)
    content_type = "application/octet-stream"
    # Routes the gateway/dispatchers must know: [(public?, path)] — the
    # first is the API clients POST; the rest are internal stage backends.
    api_path = f"/v1/{args.model}/classify-async"
    extra_paths: list[str] = []

    # Build + register every servable BEFORE the batcher: with
    # --ladder-derive, the ai4e_batch_size exposition buckets come from
    # the servables' (possibly restored) ladders at batcher construction
    # and the persisted-ladder restore must precede warmup
    # (docs/device_path.md).
    serve_calls: list[tuple] = []  # (servable, serve_model kwargs)
    if args.model == "pipeline":
        det, sp, payload, ckpt_meta = _build_pipeline_servables(args)
        runtime.register(det)
        runtime.register(sp)
        api_path = "/v1/pipeline/detect-async"
        stage2 = "/v1/pipeline/classify-species-async"
        extra_paths = [stage2]
        content_type = "image/jpeg"

        def handoff(result):
            if result.get("detections"):
                return stage2, b""  # empty body → ORIG replay downstream
            return None

        serve_calls.append((det, dict(
            async_path="/detect-async", pipeline_to=handoff,
            maximum_concurrent_requests=args.concurrency * 4)))
        serve_calls.append((sp, dict(
            async_path="/classify-species-async",
            maximum_concurrent_requests=args.concurrency * 4)))
    else:
        servable, payload, ckpt_meta = _build_servable(args)
        content_type = ckpt_meta.pop("content_type", content_type)
        runtime.register(servable)
        serve_calls.append((servable, dict(
            sync_path="/classify", async_path="/classify-async",
            maximum_concurrent_requests=args.concurrency * 4)))

    ladders = None
    if getattr(args, "ladder_derive", False):
        # Traffic-tuned ladders at a bench-sized cadence: the 20 s
        # measured window must hold observe → derive → background
        # compile → swap, so period/dwell shrink from the production
        # defaults (docs/config.md) to 2 s / 1 s.
        from ai4e_tpu.runtime.ladder import LadderManager
        persist = getattr(args, "ladder_path", "") or None
        if persist is None:
            ladder_dir = tempfile.mkdtemp(prefix="ai4e-bench-ladder")
            import atexit
            import shutil
            atexit.register(shutil.rmtree, ladder_dir, True)
            persist = os.path.join(ladder_dir, "ladders.json")
        ladders = LadderManager(runtime, window_s=60.0, max_programs=16,
                                period_s=2.0, dwell_s=1.0,
                                min_observations=8, persist_path=persist)
        restored = ladders.restore()
        if restored:
            log(f"ladder restore: {restored}")
    batcher = MicroBatcher(runtime, max_wait_ms=args.max_wait_ms,
                           max_pending=args.concurrency * 4,
                           pipeline_depth=args.pipeline_depth,
                           measure_phases=getattr(args, "observability",
                                                  False),
                           ladder_manager=ladders,
                           double_buffer=getattr(args, "double_buffer",
                                                 False))
    worker = InferenceWorker(f"{args.model}-svc", runtime, batcher,
                             task_manager=platform.task_manager,
                             prefix=f"v1/{args.model}", store=platform.store,
                             result_cache=platform.result_cache,
                             hop_ledger=getattr(args, "observability",
                                                False),
                             # The platform gateway fronts this worker with
                             # the SAME cache — its proxy layer answers and
                             # fills; a worker-keyed duplicate per request
                             # would double-count every payload against the
                             # byte budget (reload invalidation still works).
                             cache_sync_path=False,
                             checkpoint_root=args.checkpoint_dir)
    for srv, kwargs in serve_calls:
        worker.serve_model(srv, **kwargs)

    if mesh_layout is not None:
        # Same wrapping as cli.build_worker: the endpoint is the
        # outermost runtime facade, so worker AND batcher route every
        # batch through its health gate and poison accounting.
        from ai4e_tpu.runtime.mesh import (EndpointHealth, MeshCoordinator,
                                           MeshEndpoint)
        health = EndpointHealth()
        endpoint = MeshEndpoint(runtime, mesh_layout, health=health,
                                coordinator=MeshCoordinator(mesh_layout,
                                                            health=health))
        worker.runtime = endpoint
        batcher.runtime = endpoint
        log(f"mesh serving plane ON: {args.mesh} "
            f"(tier {mesh_layout.tier_label}, {mesh_layout.size} devices)")

    t0 = time.perf_counter()
    runtime.warmup()
    warmup_s = round(time.perf_counter() - t0, 1)
    log(f"warmup (compile) took {warmup_s}s for "
        f"{[(n, m.batch_buckets) for n, m in runtime.models.items()]}")
    return (platform, worker, batcher, payload,
            {"warmup_s": warmup_s, **ckpt_meta,
             **({"mesh": worker.runtime.describe()}
                if mesh_layout is not None else {})},
            api_path, extra_paths, content_type)


def _build_landcover(args):
    # The production family, not a bench-local fork: uint8 tile ingestion
    # with fused on-device normalize + argmax + histogram, counts-only
    # device outputs (return_classmap defaults False — the response is the
    # histogram; the H·W map would be 64 KiB per tile of device→host
    # traffic nobody reads).
    from ai4e_tpu.runtime import build_servable

    kwargs, _from_manifest = _manifest_kwargs(args.checkpoint_dir, "landcover")
    return build_servable("unet", name="landcover", tile=args.tile,
                          buckets=tuple(args.buckets),
                          wire=_servable_wire(args), **kwargs)


def _args_for(args, model: str, **overrides):
    """A per-model view of the CLI args (the mixed config builds several
    servables, each at its own per-model bucket defaults, capped at the
    top-level bucket bound so the CPU clamp propagates)."""
    import argparse
    defaults = {"landcover": [1, 16, 64], "megadetector": [1, 8],
                "species": [1, 16, 64], "longcontext": [1, 16, 64],
                "moe": [1, 16]}[model]
    cap = max(args.buckets) if args.buckets else 64
    buckets = [b for b in defaults if b <= cap] or [1]
    return argparse.Namespace(**{**vars(args), "model": model,
                                 "buckets": buckets, **overrides})


def _build_moe(args):
    """MoE token servable for the mixed config — manifest-geometry kwargs +
    trained weights when present (same gating as the longcontext family:
    token trees have structural seq_len/vocab shapes)."""
    from ai4e_tpu.runtime import build_servable

    mf_kwargs, from_manifest = _manifest_kwargs(args.checkpoint_dir, "moe")
    if not from_manifest:
        mf_kwargs = dict(seq_len=1024, input_dim=64, dim=128, depth=2,
                         heads=2, num_experts=8, num_classes=16,
                         vocab_size=32768)
    servable = build_servable("moe", name="moe",
                              buckets=tuple(args.buckets), **mf_kwargs)
    meta: dict = {"checkpoint": "none"}
    if from_manifest:
        servable.params, meta = _load_or_train_checkpoint(
            "moe", args.checkpoint_dir, servable.params, required=False)
    vocab = mf_kwargs.get("vocab_size") or 32768
    seq_len = mf_kwargs.get("seq_len", 1024)
    rng = np.random.default_rng(0)
    wire_dt = np.uint16 if vocab <= 2**16 else np.uint32
    payload_arr = rng.integers(0, vocab, size=(seq_len,), dtype=wire_dt)
    buf = io.BytesIO()
    np.save(buf, payload_arr)
    return servable, buf.getvalue(), meta


def _build_mixed(args):
    """Platform + all five families on one worker, warmed — shared by the
    mixed bench and the orchestrator's prewarm stage (which must compile
    the same programs into the persistent cache)."""
    from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
    from ai4e_tpu.runtime import (InferenceWorker, MicroBatcher,
                                  ModelRuntime, enable_compilation_cache)

    enable_compilation_cache()
    platform = LocalPlatform(PlatformConfig(
        transport=args.transport,
        native_store=args.fabric == "native",
        native_broker=(args.fabric == "native"
                       and args.transport == "queue"),
        retry_delay=0.05,
        dispatcher_concurrency=args.dispatcher_concurrency))
    runtime = ModelRuntime(donate_batch=args.donate_batch)
    batcher = MicroBatcher(runtime, max_wait_ms=args.max_wait_ms,
                           max_pending=args.concurrency * 4,
                           pipeline_depth=args.pipeline_depth)
    worker = InferenceWorker("mixed-svc", runtime, batcher,
                             task_manager=platform.task_manager,
                             prefix="v1/models", store=platform.store)

    interactive = ["landcover", "species", "longcontext", "moe"]
    payloads: dict[str, bytes] = {}
    content_types: dict[str, str] = {}
    build_meta: dict = {}
    for name in interactive:
        if name == "moe":
            servable, payloads[name], meta = _build_moe(_args_for(args, name))
        else:
            servable, payloads[name], meta = _build_servable(
                _args_for(args, name))
        content_types[name] = meta.pop("content_type",
                                       "application/octet-stream")
        runtime.register(servable)
        worker.serve_model(servable, async_path=f"/{name}-async",
                           maximum_concurrent_requests=args.concurrency * 4)
        build_meta[name] = {k: meta[k] for k in ("checkpoint", "wire")
                           if k in meta}
    det, _det_payload, det_meta = _build_servable(
        _args_for(args, "megadetector"))
    det_meta.pop("content_type", None)  # stacks always ship as npy
    runtime.register(det)
    worker.serve_batch(det, async_path="/megadetector-batch-async",
                       maximum_concurrent_requests=8)
    build_meta["megadetector"] = {k: det_meta[k]
                                  for k in ("checkpoint", "wire")
                                  if k in det_meta}
    # Background stack payload: (N, H, W, 3) image stack (the batch API's
    # natural shape on every wire).
    det_size = det_meta.get("image_size", 512)
    rng = np.random.default_rng(1)
    stack = rng.integers(0, 256, size=(args.stack_size, det_size,
                                       det_size, 3), dtype=np.uint8)
    buf = io.BytesIO()
    np.save(buf, stack)

    t0 = time.perf_counter()
    runtime.warmup()
    warmup_s = round(time.perf_counter() - t0, 1)
    log(f"mixed warmup took {warmup_s}s for {list(runtime.models)}")
    return (platform, runtime, batcher, worker, interactive, payloads,
            content_types, build_meta, buf.getvalue(), warmup_s)


async def run_mixed_bench(args) -> dict:
    """Mixed-workload serving proof (VERDICT r3 #7): five families on one
    worker/chip; two measured phases — A: interactive loops alone; B: the
    same loops while a background megadetector batch stack saturates the
    device (priority 1 via serve_batch). The artifact carries per-model
    req/s + latency for both phases, per-model batch-size histograms, and
    the isolation ratio (interactive p95 B/A — flat means the priority
    classes actually protect interactive latency)."""
    import aiohttp
    from aiohttp import ClientSession, web

    from ai4e_tpu.utils.loadclient import run_closed_loop

    (platform, runtime, batcher, worker, interactive, payloads,
     content_types, build_meta, stack_payload, warmup_s) = _build_mixed(args)

    be_runner = web.AppRunner(worker.service.app)
    await be_runner.setup()
    be_site = web.TCPSite(be_runner, "127.0.0.1", 0)
    await be_site.start()
    be_port = be_runner.addresses[0][1]
    for name in interactive:
        path = f"/v1/models/{name}-async"
        platform.publish_async_api(path, f"http://127.0.0.1:{be_port}{path}")
    stack_path = "/v1/models/megadetector-batch-async"
    platform.publish_async_api(stack_path,
                               f"http://127.0.0.1:{be_port}{stack_path}")

    gw_runner = web.AppRunner(platform.gateway.app)
    await gw_runner.setup()
    gw_site = web.TCPSite(gw_runner, "127.0.0.1", 0)
    await gw_site.start()
    gw = f"http://127.0.0.1:{gw_runner.addresses[0][1]}"

    await batcher.start()
    await platform.start()

    # Interactive concurrency split: the image families carry the load
    # story; the sequence families ride along at lower client counts.
    conc = {"landcover": max(8, args.concurrency * 3 // 8),
            "species": max(8, args.concurrency * 3 // 8),
            "longcontext": max(4, args.concurrency // 8),
            "moe": max(4, args.concurrency // 16)}

    async def drive_interactive(session) -> dict:
        async def one(name):
            return name, await run_closed_loop(
                session,
                post_url=f"{gw}/v1/models/{name}-async",
                payload=payloads[name],
                headers={"Content-Type": content_types[name]},
                mode="async",
                status_url_for=lambda tid:
                    f"{gw}/v1/taskmanagement/task/{tid}",
                concurrency=conc[name], duration=args.duration,
                ramp=args.ramp)
        results = await asyncio.gather(*(one(n) for n in interactive))
        return dict(results)

    stack_stats = {"stacks": 0, "images": 0}

    async def stack_loop(session, stop: asyncio.Event) -> None:
        """Background megadetector stacks, back to back (each submits its
        items at priority 1 inside serve_batch)."""
        while not stop.is_set():
            try:
                async with session.post(
                        f"{gw}{stack_path}", data=stack_payload,
                        headers={"Content-Type":
                                 "application/octet-stream"}) as resp:
                    if resp.status in (503, 429):
                        await asyncio.sleep(0.1)
                        continue
                    rec = await resp.json()
                tid = rec["TaskId"]
                while not stop.is_set():
                    async with session.get(
                            f"{gw}/v1/taskmanagement/task/{tid}",
                            params={"wait": "10"}) as resp:
                        status = (await resp.json())["Status"]
                    if "completed" in status or "failed" in status:
                        if "completed" in status:
                            stack_stats["stacks"] += 1
                            stack_stats["images"] += args.stack_size
                        break
            except (aiohttp.ClientError, asyncio.TimeoutError, KeyError,
                    ValueError):
                await asyncio.sleep(0.2)

    async with ClientSession(
            connector=aiohttp.TCPConnector(limit=0)) as session:
        # Warm every route to a terminal state first.
        for name in interactive:
            async with session.post(
                    f"{gw}/v1/models/{name}-async", data=payloads[name],
                    headers={"Content-Type": content_types[name]}) as resp:
                tid = (await resp.json())["TaskId"]
            deadline = time.perf_counter() + 300
            while time.perf_counter() < deadline:
                async with session.get(
                        f"{gw}/v1/taskmanagement/task/{tid}",
                        params={"wait": "30"}) as resp:
                    rec = await resp.json()
                if "completed" in rec["Status"] or "failed" in rec["Status"]:
                    break

        log("mixed phase A: interactive only")
        phase_a = await drive_interactive(session)

        log("mixed phase B: interactive + background megadetector stack")
        stop = asyncio.Event()
        t_b0 = time.perf_counter()
        stackers = [asyncio.get_running_loop().create_task(
            stack_loop(session, stop)) for _ in range(args.stack_streams)]
        phase_b = await drive_interactive(session)
        stack_elapsed = time.perf_counter() - t_b0
        stop.set()
        for t in stackers:
            t.cancel()
        await asyncio.gather(*stackers, return_exceptions=True)

    await platform.stop()
    await batcher.stop()
    await gw_runner.cleanup()
    await be_runner.cleanup()

    # Per-model device batch sizes (the multi-API batching evidence).
    batch_sizes: dict[str, dict] = {}
    for _, _, labels, data in batcher.metrics.histogram(
            "ai4e_batch_size", "").collect():
        model = labels.get("model", "?")
        agg = batch_sizes.setdefault(model, {"batches": 0, "examples": 0.0})
        agg["batches"] += int(data["count"])
        agg["examples"] += float(data["sum"])
    for model, agg in batch_sizes.items():
        agg["avg_batch_size"] = round(
            agg.pop("examples") / max(1, agg["batches"]), 2)

    isolation = {
        name: round(phase_b[name]["p95_latency_ms"]
                    / max(phase_a[name]["p95_latency_ms"], 1e-9), 2)
        for name in interactive}
    value = round(sum(phase_b[n]["value"] for n in interactive), 2)
    cfg = CONFIGS["mixed"]

    # Same accounting surface as the single-model configs: per-model FLOPs
    # + MFU (VERDICT r3 #1 applies to every artifact), delivered MFU over
    # the WHOLE phase-B workload (interactive + background images), and the
    # Mosaic kernel validation on real hardware.
    peak = _peak_flops_per_chip()
    flops_meta: dict = {}
    per_model_flops: dict[str, float] = {}
    for name, servable in runtime.models.items():
        flops = _model_flops_per_batch(servable, servable.max_bucket)
        if flops is not None:
            per_model_flops[name] = flops / servable.max_bucket
    if per_model_flops:
        flops_meta["model_flops_per_req"] = {
            name: round(v) for name, v in per_model_flops.items()}
        delivered = sum(
            phase_b[n]["value"] * per_model_flops.get(n, 0.0)
            for n in interactive)
        delivered += (stack_stats["images"] / max(stack_elapsed, 1e-9)
                      ) * per_model_flops.get("megadetector", 0.0)
        flops_meta["delivered_flops_per_s"] = round(delivered)
        if peak:
            flops_meta["device_peak_bf16_flops"] = peak
            flops_meta["mfu_delivered"] = round(delivered / peak, 4)
    if _device_fields()["platform"] == "tpu":
        flops_meta["pallas_tpu"] = _validated_kernels()

    return {
        "metric": cfg["metric"],
        "value": value,
        "unit": "req/s",
        "mode": "async",
        "transport": args.transport,
        "fabric": args.fabric,
        "vs_baseline": round(value / cfg["anchor"], 2),
        "baseline_anchor": cfg["anchor"],
        **_device_fields(),
        "warmup_s": warmup_s,
        "families": build_meta,
        "phase_a_interactive": phase_a,
        "phase_b_interactive": phase_b,
        "background_stack": {
            "stacks_completed": stack_stats["stacks"],
            "images_per_s": round(stack_stats["images"]
                                  / max(stack_elapsed, 1e-9), 2),
            "stack_size": args.stack_size,
            "streams": args.stack_streams},
        "isolation_p95_b_over_a": isolation,
        "batch_sizes": batch_sizes,
        **flops_meta,
    }


async def run_bench(args) -> dict:
    from aiohttp import ClientSession, web

    if args.model == "mixed":
        return await run_mixed_bench(args)

    (platform, worker, batcher, payload, build_meta,
     api_path, extra_paths, content_type) = build_platform(args)

    be_runner = web.AppRunner(worker.service.app)
    await be_runner.setup()
    be_site = web.TCPSite(be_runner, "127.0.0.1", 0)
    await be_site.start()
    be_port = be_runner.addresses[0][1]

    platform.publish_async_api(
        api_path, f"http://127.0.0.1:{be_port}{api_path}")
    if args.model != "pipeline":
        # Sync mode (BASELINE configs #1/#2): gateway reverse-proxies the
        # worker's sync endpoint; same batcher underneath.
        sync_public = f"/v1/{args.model}/classify"
        platform.publish_sync_api(
            sync_public, f"http://127.0.0.1:{be_port}{sync_public}")
    for path in extra_paths:  # internal pipeline stages: transport consumer only
        platform.register_internal_route(f"http://127.0.0.1:{be_port}{path}")

    gw_runner = web.AppRunner(platform.gateway.app)
    await gw_runner.setup()
    gw_site = web.TCPSite(gw_runner, "127.0.0.1", 0)
    await gw_site.start()
    gw_port = gw_runner.addresses[0][1]

    # --fault-rate: seeded chaos on the backend-POST hop (dispatcher
    # deliveries + sync proxy) — injected 5xx at the given rate, so the
    # run measures goodput under failure. Wrapped AFTER routes registered
    # (each dispatcher's session holder exists), BEFORE traffic starts.
    injector = None
    fault_rate = getattr(args, "fault_rate", 0.0) or 0.0
    if fault_rate > 0:
        from ai4e_tpu.chaos import FaultInjector, wrap_platform_http
        injector = FaultInjector(seed=getattr(args, "fault_seed", 0))
        injector.add_rule(error_rate=fault_rate, error_status=500)
        wrap_platform_http(platform, injector)
        log(f"chaos: injecting 5xx at rate {fault_rate} "
            f"(seed {injector.seed}, resilience="
            f"{getattr(args, 'resilience', False)})")

    await batcher.start()
    await platform.start()

    gw = f"http://127.0.0.1:{gw_port}"
    sync_public = f"/v1/{args.model}/classify"
    post_url = (f"{gw}{sync_public}" if args.mode == "sync"
                else f"{gw}{api_path}")
    headers = {"Content-Type": content_type}

    from ai4e_tpu.utils.loadclient import run_closed_loop

    # The client pool must admit every in-flight request (aiohttp's default
    # connector caps at 100 connections — below --concurrency — and sync
    # mode holds a connection for the whole inference).
    import aiohttp
    async with ClientSession(
            connector=aiohttp.TCPConnector(limit=0)) as session:
        # warm the full path once — to a TERMINAL state on the async route
        # (first inference can out-wait a single 30 s long-poll on cold
        # hardware, and a "warm" run that is still compiling would land the
        # stall inside the measured window).
        async with session.post(post_url, data=payload,
                                headers=headers) as resp:
            warm = await resp.json() if args.mode == "async" else None
        if args.mode == "async":
            warm_deadline = time.perf_counter() + 300
            while time.perf_counter() < warm_deadline:
                async with session.get(
                        f"{gw}/v1/taskmanagement/task/{warm['TaskId']}",
                        params={"wait": "30"}) as resp:
                    record = await resp.json()
                if ("completed" in record["Status"]
                        or "failed" in record["Status"]):
                    break
        if args.model == "pipeline":
            # The composite must have traversed BOTH stages — a gate that
            # never fires would silently measure a one-stage task. Stage-1's
            # intermediate result is stored under the detector's name.
            async with session.post(f"{gw}{api_path}", data=payload,
                                    headers=headers) as resp:
                probe_tid = (await resp.json())["TaskId"]
            async with session.get(
                    f"{gw}/v1/taskmanagement/task/{probe_tid}",
                    params={"wait": "30"}) as resp:
                record = await resp.json()
            assert "completed" in record["Status"], record
            staged = platform.store.get_result(probe_tid,
                                               stage="megadetector")
            assert staged is not None, (
                "pipeline handoff never fired — bench would measure a "
                "single-stage task")

        # Duplicate-request mix for the result cache (--cache-hit-ratio r):
        # a share r of POSTs repeat the identical hot request (cacheable —
        # first execution, then hits/coalesces), the rest carry a
        # never-repeating query param, which the canonical request key
        # includes — they always execute on device. Cache stats are
        # snapshotted when the measured window opens so the cold ramp
        # doesn't dilute the reported hit ratio.
        cache = getattr(platform, "result_cache", None)
        requested_ratio = getattr(args, "cache_hit_ratio", 0.0) or 0.0
        post_url_for = None
        if cache is not None and requested_ratio > 0:
            import itertools
            import random as _random
            _rng = _random.Random(0)
            _uniq = itertools.count()

            def post_url_for():
                if _rng.random() < requested_ratio:
                    return post_url
                return f"{post_url}?uniq={next(_uniq)}"

        cache_mark: dict = {}

        # --task-shards: per-shard goodput + long-poll watcher accounting.
        # A facade listener counts terminal completions per shard; marks
        # taken at window open subtract the ramp. Watchers are sampled off
        # the shard feeds (every long-poller parks there) — the peak is
        # the concurrent-watcher figure the feed fan-out design carries.
        shards = getattr(args, "task_shards", 1) or 1
        shard_counts: dict[int, int] = {}
        shard_mark: dict[int, int] = {}
        watcher_peak = [0]
        if shards > 1:
            from ai4e_tpu.taskstore import TaskStatus as _TS

            def _count_terminal(task, _store=platform.store):
                if task.canonical_status in _TS.TERMINAL:
                    s = _store.shard_for(task.task_id)
                    shard_counts[s] = shard_counts.get(s, 0) + 1

            platform.store.add_listener(_count_terminal)

            async def _sample_watchers():
                while True:
                    live = sum(f.watcher_count
                               for f in platform.store.feeds)
                    watcher_peak[0] = max(watcher_peak[0], live)
                    await asyncio.sleep(0.25)

            watcher_task = asyncio.get_running_loop().create_task(
                _sample_watchers())

        async def _snap_cache_at_window_open():
            await asyncio.sleep(args.ramp)
            if cache is not None:
                cache_mark.update(cache.stats())
            shard_mark.update(shard_counts)

        # Admission-mix drivers (--deadline-ms / --priority-mix): each POST
        # carries its budget + class; completions score goodput.
        headers_for, deadline_s = _admission_drivers(args)

        # Tenant-mix drivers (--tenant-mix): each POST draws a
        # subscription key by share; composes with the admission headers.
        tenant_headers_for, tenant_names = _tenant_drivers(args)
        if tenant_headers_for is not None:
            def headers_for(_adm=headers_for, _ten=tenant_headers_for):
                hdrs = _adm() if _adm is not None else {}
                hdrs.update(_ten())
                return hdrs

        # Closed loop with a steady-state ramp before the measured window
        # (shared with examples/loadgen.py — ai4e_tpu/utils/loadclient.py).
        window, _ = await asyncio.gather(run_closed_loop(
            session,
            post_url=post_url, payload=payload, headers=headers,
            mode=args.mode,
            status_url_for=lambda tid: f"{gw}/v1/taskmanagement/task/{tid}",
            concurrency=args.concurrency, duration=args.duration,
            ramp=args.ramp, post_url_for=post_url_for,
            headers_for=headers_for, deadline_s=deadline_s,
            tenant_names=tenant_names),
            _snap_cache_at_window_open())
        if shards > 1:
            watcher_task.cancel()

    shard_meta = {}
    if shards > 1:
        elapsed = max(window["duration_s"], 1e-9)
        per_shard = {}
        for s in range(shards):
            done = shard_counts.get(s, 0) - shard_mark.get(s, 0)
            per_shard[str(s)] = {
                "completed": int(done),
                "goodput_req_s": round(done / elapsed, 2)}
        shard_meta["shards"] = {
            "task_shards": shards,
            "slots": platform.store.ring.slots,
            "per_shard": per_shard,
            # Peak concurrent long-poll watchers parked on the N shard
            # feeds during the run — the population that would otherwise
            # be per-request store polls.
            "longpoll_watchers_peak": int(watcher_peak[0]),
        }

    journal_meta = {}
    if getattr(args, "fsync_policy", ""):
        stats_fn = getattr(platform.store, "journal_stats", None)
        if stats_fn is not None:
            js = stats_fn()
            if js:
                # The append-path cost of the chosen durability policy
                # (docs/durability.md): volume, fsync count, and the
                # p99 a task's journaled transition paid under the
                # store lock.
                journal_meta["journal"] = {
                    "fsync_policy": js["fsync_policy"],
                    "bytes_appended": js["bytes_appended"],
                    "fsyncs": js["fsyncs"],
                    "compactions": js["compactions"],
                    "salvages": js["salvages"],
                    "append_p99_ms": js["append_p99_ms"],
                }

    fault_meta = {}
    if injector is not None:
        # Goodput under failure: completions/s inside the window (failures
        # and client slots burned on failed tasks excluded by
        # construction) — the resilience=on/off A/B figure, beside the
        # injected-fault accounting and the resilience counters.
        reg = platform.metrics
        fault_meta["fault"] = {
            "rate": fault_rate,
            "seed": injector.seed,
            "resilience": bool(getattr(args, "resilience", False)),
            "injected": injector.counts(),
            "goodput_req_s": window["value"],
            "failed": window["failed"],
            "retries": int(sum(v for *_, v in reg.counter(
                "ai4e_resilience_retries_total", "").collect())),
            "redeliveries": int(sum(
                v for _, _, labels, v in reg.counter(
                    "ai4e_dispatch_total", "").collect()
                if labels.get("outcome") == "backpressure")),
        }

    admission_meta = _admission_report(args, platform)
    if admission_meta:
        # Goodput rides beside raw req/s: under offered load > capacity the
        # headline number alone rewards completing dead work. by_priority
        # carries the per-class goodput + deadline-miss rate the --mix
        # profiles exist to compare.
        for key in ("goodput", "late", "expired", "deadline_miss_rate",
                    "by_priority"):
            if key in window:
                admission_meta["admission"][key] = window[key]
    orchestration_meta = _orchestration_report(args, platform)

    tenancy_meta = _tenancy_report(args, platform)
    if tenancy_meta and "by_tenant" in window:
        # The client-observed window per tenant (offered/goodput/sheds as
        # the load client scored them) rides beside the server counters.
        tenancy_meta["tenancy"]["by_tenant_window"] = window["by_tenant"]

    cache_meta = {}
    if cache is not None:
        stats = cache.stats()
        hits = stats["hits"] - cache_mark.get("hits", 0)
        misses = stats["misses"] - cache_mark.get("misses", 0)
        coalesced = stats["coalesced"] - cache_mark.get("coalesced", 0)
        lookups = hits + misses
        elapsed = max(window["duration_s"], 1e-9)
        cache_meta["cache"] = {
            "requested_hit_ratio": requested_ratio,
            "hit_ratio": round(hits / lookups, 3) if lookups else 0.0,
            "hits": int(hits),
            "misses": int(misses),
            "coalesced": int(coalesced),
            # Requests answered without touching the device, per second of
            # the measured window — read next to "value" (total req/s) and
            # the device-side avg_batch_size/batch_exec figures.
            "served_from_cache_req_s": round((hits + coalesced) / elapsed, 2),
            "entries": stats["entries"],
            "resident_bytes": stats["bytes"],
        }

    await platform.stop()
    await batcher.stop()
    await gw_runner.cleanup()
    await be_runner.cleanup()

    throughput = window["value"]
    cfg = CONFIGS[args.model]

    # Batching efficiency — THE design thesis vs the reference's
    # one-request-per-POST dispatch: average examples per device batch,
    # aggregated across every model the batcher fed (pipeline runs feed two).
    def _hist_totals(name: str) -> tuple[int, float]:
        count, total = 0, 0.0
        for _, _, _labels, data in batcher.metrics.histogram(
                name, "").collect():
            count += int(data["count"])
            total += float(data["sum"])
        return count, total

    def _counter_total(name: str) -> float:
        return sum(v for _, _, _labels, v in
                   batcher.metrics.counter(name, "").collect())

    batch_meta = {}
    n_batches, n_examples = _hist_totals("ai4e_batch_size")
    if n_batches:
        batch_meta = {"device_batches": n_batches,
                      "avg_batch_size": round(n_examples / n_batches, 2)}
        # Per-batch wall time as seen by run_batch (h2d + compute + result
        # fetch), aggregated across every served model. Together with
        # avg_batch_size this separates "what the device+link can do" from
        # end-to-end task throughput.
        ex_n, ex_sum = _hist_totals("ai4e_batch_exec_seconds")
        if ex_n:
            batch_meta["batch_exec_avg_ms"] = round(1000 * ex_sum / ex_n, 1)
        # Tail decomposition (VERDICT r2 #6): a p95/p99 task latency far
        # above Little's-law mean is either device/link stalls (exec p99
        # blows up) or admission/queueing inequity (queue wait p99 blows
        # up, exec steady). Bucket upper-edge quantiles,
        # worst across served models.
        def _hist_p99_ms(name: str) -> float | None:
            hist = batcher.metrics.histogram(name, "")
            worst = max((hist.quantile(0.99, model=m)
                         for m in batcher.runtime.models), default=0.0)
            return round(1000 * worst, 1) if worst else None

        for key, hist_name in (
                ("batch_exec_p99_ms", "ai4e_batch_exec_seconds"),
                ("batch_queue_wait_p99_ms", "ai4e_batch_queue_wait_seconds")):
            p99 = _hist_p99_ms(hist_name)
            if p99 is not None:
                batch_meta[key] = p99
        # Link accounting: actual h2d/d2h bytes per request (padding
        # included).
        h2d, d2h = (_counter_total("ai4e_batch_h2d_bytes_total"),
                    _counter_total("ai4e_batch_d2h_bytes_total"))
        if n_examples:
            batch_meta["h2d_bytes_per_req"] = round(h2d / n_examples)
            batch_meta["d2h_bytes_per_req"] = round(d2h / n_examples)
        batch_meta["wire_bytes_per_req"] = len(payload)
        if getattr(batcher, "_pad_enabled", False):
            # Pad-waste accounting (docs/device_path.md): cumulative
            # padded/occupied slots per model + total padding bytes —
            # the A/B lever --ladder-derive exists to move.
            pad_gauge = batcher.metrics.gauge("ai4e_batch_pad_ratio", "")
            batch_meta["pad_ratio"] = {
                m: round(pad_gauge.value(model=m), 4)
                for m in batcher.runtime.models}
            batch_meta["pad_bytes_total"] = round(_counter_total(
                "ai4e_batch_pad_bytes_total"))

    # Link-independent device capability (VERDICT r2 #3): time the compiled
    # program on an already-on-device batch (no h2d per iteration, outputs
    # left on device) — what the chip would sustain if the host link weren't
    # the cap. Runs after the window, device idle.
    donated = bool(getattr(batcher.runtime, "_donate", False))
    capability_meta = {"device_capability": {
        name: _measure_device_capability(servable, donated=donated)
        for name, servable in batcher.runtime.models.items()}}

    # MFU accounting (VERDICT r3 #1): XLA-reported FLOPs per request and the
    # fraction of chip peak the measured end-to-end throughput represents.
    # device_capability carries the chip-side MFU (what the compiled program
    # achieves); mfu_delivered is the platform-level figure (wire + control
    # plane included) — the gap between them is the link/dispatch tax.
    peak = _peak_flops_per_chip()
    if peak is not None:
        capability_meta["device_peak_bf16_flops"] = peak
    flops_per_req_total = 0.0
    for name, servable in batcher.runtime.models.items():
        flops = _model_flops_per_batch(servable, servable.max_bucket)
        if flops is None:
            continue
        per_req = flops / servable.max_bucket
        flops_per_req_total += per_req
        cap = capability_meta.get("device_capability", {}).get(name)
        if cap is not None:
            cap["flops_per_req"] = round(per_req)
            cap["device_flops_per_s"] = round(per_req * cap["req_s"])
            if peak:
                cap["mfu"] = round(per_req * cap["req_s"] / peak, 4)
    if flops_per_req_total:
        # Pipeline runs feed two models; each task crosses both, so the
        # per-request figure is the sum over served models.
        capability_meta["model_flops_per_req"] = round(flops_per_req_total)
        capability_meta["delivered_flops_per_s"] = round(
            flops_per_req_total * throughput)
        if peak:
            capability_meta["mfu_delivered"] = round(
                flops_per_req_total * throughput / peak, 4)

    # --observability: per-request device-phase decomposition from the
    # batcher's phase histograms (observability satellite; ROADMAP item
    # 2's decomposition) — where a request's time goes between queue
    # wait, h2d, execute, and d2h, per percentile, plus the
    # transfer/execute overlap ratio the pipeline window exists to
    # create.
    phases_meta = {}
    if getattr(args, "observability", False) and batcher.measure_phases:
        def _phase_pcts(hist, **labels) -> dict | None:
            count = sum(
                int(data["count"])
                for _k, _n, hl, data in hist.collect()
                if all(hl.get(k) == v for k, v in labels.items()))
            if not count:
                return None
            # Bucket upper-edge quantiles — same convention as the
            # batch_exec/queue_wait p99 fields above.
            return {"count": count,
                    **{f"p{int(q * 100)}_ms": round(
                        1000 * hist.quantile(q, **labels), 2)
                       for q in (0.5, 0.9, 0.99)}}

        phase_hist = batcher.metrics.histogram(
            "ai4e_device_phase_seconds", "")
        wait_hist = batcher.metrics.histogram(
            "ai4e_batch_queue_wait_seconds", "")
        block: dict = {}
        for model in batcher.runtime.models:
            per_model: dict = {}
            wait = _phase_pcts(wait_hist, model=model)
            if wait is not None:
                per_model["queue_wait"] = wait
            for phase in ("h2d", "compile", "execute", "d2h"):
                pcts = _phase_pcts(phase_hist, phase=phase, model=model)
                if pcts is not None:
                    per_model[phase] = pcts
            if per_model:
                block[model] = per_model
        if block:
            phases_meta["phases"] = {
                **block,
                # Cumulative overlap ratio: 1.0 = every h2d second hid
                # under another batch's execute (docs/observability.md
                # documents the in-flight approximation).
                "h2d_execute_overlap_ratio": round(batcher.metrics.gauge(
                    "ai4e_batch_overlap_ratio", "").value(), 4),
            }

    # --ladder-derive: the derived-ladder block — per-model generation,
    # factory baseline vs the ladder that ended the run serving, and the
    # derive-outcome counts (docs/device_path.md).
    ladder_meta = {}
    if getattr(batcher, "_ladders", None) is not None:
        mgr = batcher._ladders
        derives = batcher.metrics.counter("ai4e_ladder_derives_total", "")
        ladder_meta["ladder"] = {
            "derive": True,
            "models": {
                m: {"generation": mgr.generation(m),
                    "baseline": list(mgr.baseline(m)),
                    "buckets": list(
                        batcher.runtime.models[m].batch_buckets)}
                for m in batcher.runtime.models},
            "derives": {
                outcome: int(sum(
                    v for _, _, labels, v in derives.collect()
                    if labels.get("outcome") == outcome))
                for outcome in ("swapped", "unchanged", "skipped",
                                "failed")},
        }

    pallas_meta = {}
    if _device_fields()["platform"] == "tpu":
        pallas_meta["pallas_tpu"] = _validated_kernels()

    metric = cfg["metric"]
    if args.mode == "sync":
        metric = metric.replace("async_", "sync_", 1)
    return {
        "metric": metric,
        "value": round(throughput, 2),
        "unit": "req/s",
        "mode": args.mode,
        "transport": args.transport,
        "fabric": args.fabric,
        **({"donate_batch": True} if args.donate_batch else {}),
        **({"double_buffer": True}
           if getattr(args, "double_buffer", False) else {}),
        "vs_baseline": round(throughput / cfg["anchor"], 2),
        "baseline_anchor": cfg["anchor"],
        **{k: window[k] for k in ("p50_latency_ms", "p95_latency_ms",
                                  "p99_latency_ms", "completed", "failed",
                                  "duration_s")},
        "concurrency": args.concurrency,
        **_device_fields(),
        **({"mix": args.mix} if getattr(args, "mix", "") else {}),
        **build_meta,
        **admission_meta,
        **orchestration_meta,
        **tenancy_meta,
        **cache_meta,
        **shard_meta,
        **journal_meta,
        **fault_meta,
        **batch_meta,
        **phases_meta,
        **ladder_meta,
        **capability_meta,
        **pallas_meta,
    }


async def run_pipeline_dag_bench(args) -> dict:
    """``--pipeline``: the declared-DAG preset (docs/pipelines.md) — a
    2-stage echo chain (`s1 -> s2`, both through the real runtime +
    micro-batcher) executed by the pipeline coordinator, driven by the
    shared closed-loop client CONSUMING THE SSE STREAM, so the run
    measures pipeline goodput and **time-to-first-partial** beside
    end-to-end latency. Honest CPU numbers: the echo family carries no
    model weight — the figure is the platform's DAG-coordination path
    itself (entry queue → stage sub-task → dispatcher → worker → stage
    result → join → terminal), exactly like the plain echo config
    measures the task path."""
    from aiohttp import ClientSession, TCPConnector, web

    from ai4e_tpu.pipeline import PipelineSpec, StageSpec
    from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
    from ai4e_tpu.runtime import (InferenceWorker, MicroBatcher,
                                  ModelRuntime, build_servable)
    from ai4e_tpu.utils.loadclient import run_closed_loop

    platform = LocalPlatform(PlatformConfig(
        pipeline=True, retry_delay=0.05,
        dispatcher_concurrency=args.dispatcher_concurrency))
    runtime = ModelRuntime()
    size = 16
    for name in ("s1", "s2"):
        runtime.register(build_servable("echo", name=name, size=size,
                                        buckets=(1, 16)))
    batcher = MicroBatcher(runtime, max_wait_ms=args.max_wait_ms,
                           max_pending=args.concurrency * 4)
    worker = InferenceWorker("pipe-echo", runtime, batcher,
                             task_manager=platform.task_manager,
                             prefix="v1/pchain", store=platform.store)
    for name in ("s1", "s2"):
        worker.serve_model(runtime.models[name], async_path=f"/{name}-async",
                           maximum_concurrent_requests=args.concurrency * 4)
    t0 = time.perf_counter()
    runtime.warmup()
    warmup_s = round(time.perf_counter() - t0, 1)

    be_runner = web.AppRunner(worker.service.app)
    await be_runner.setup()
    be_site = web.TCPSite(be_runner, "127.0.0.1", 0)
    await be_site.start()
    be = f"http://127.0.0.1:{be_runner.addresses[0][1]}"

    # Stage 2 replays the ORIGINAL body (`input="original"`): the echo
    # servables decode npy, not each other's JSON results — the replay
    # contract the reference's ensembles used, declared per stage.
    spec = PipelineSpec("echo2", "/v1/pipe/echo2", [
        StageSpec("s1", f"{be}/v1/pchain/s1-async"),
        StageSpec("s2", f"{be}/v1/pchain/s2-async", after=("s1",),
                  input="original"),
    ])
    platform.register_pipeline(spec)
    for st in spec.stages:
        platform.register_internal_route(st.endpoint)

    gw_runner = web.AppRunner(platform.gateway.app)
    await gw_runner.setup()
    gw_site = web.TCPSite(gw_runner, "127.0.0.1", 0)
    await gw_site.start()
    gw = f"http://127.0.0.1:{gw_runner.addresses[0][1]}"

    await batcher.start()
    await platform.start()

    payload_arr = np.arange(size, dtype=np.float32)
    buf = io.BytesIO()
    np.save(buf, payload_arr)
    payload = buf.getvalue()
    headers = {"Content-Type": "application/octet-stream"}

    # Client-side goodput budget: completions within the caller's
    # deadline count as good (admission stays off — the preset measures
    # the DAG path, not shedding; pair with --deadline-ms for that).
    deadline_s = (args.deadline_ms / 1000.0) if args.deadline_ms else 2.0

    async with ClientSession(connector=TCPConnector(limit=0)) as session:
        # Warm the full DAG path to terminal once (first request pays
        # queue registration + compile).
        async with session.post(f"{gw}/v1/pipe/echo2", data=payload,
                                headers=headers) as resp:
            warm = await resp.json()
        async with session.get(
                f"{gw}/v1/taskmanagement/task/{warm['TaskId']}",
                params={"wait": "60"}) as resp:
            record = await resp.json()
        assert "completed" in record["Status"], record
        staged = platform.store.get_result(warm["TaskId"], stage="s1")
        assert staged is not None, "stage 1 result missing — the DAG never ran"

        window = await run_closed_loop(
            session,
            post_url=f"{gw}/v1/pipe/echo2", payload=payload,
            headers=headers, mode="async",
            status_url_for=lambda tid: f"{gw}/v1/taskmanagement/task/{tid}",
            events_url_for=(
                lambda tid: f"{gw}/v1/taskmanagement/task/{tid}/events"),
            concurrency=args.concurrency, duration=args.duration,
            ramp=args.ramp, deadline_s=deadline_s)

    runs = platform.metrics.counter("ai4e_pipeline_runs_total", "")
    completed_runs = int(runs.value(pipeline="echo2", outcome="completed"))
    await platform.stop()
    await batcher.stop()
    await gw_runner.cleanup()
    await be_runner.cleanup()

    ttfp_p50 = window.get("time_to_first_partial_ms_p50")
    return {
        "metric": "async_pipeline_dag_throughput",
        "value": window["value"],
        "unit": "req/s",
        "mode": "async",
        "pipeline": "echo2 (2-stage echo chain, declared DAG)",
        # Goodput beside raw req/s, per the preset's contract.
        "pipeline_goodput_req_s": window.get("goodput", window["value"]),
        "goodput_budget_ms": round(deadline_s * 1000),
        **{k: window[k] for k in ("p50_latency_ms", "p95_latency_ms",
                                  "p99_latency_ms", "completed", "failed",
                                  "duration_s") if k in window},
        "first_partials": window.get("first_partials", 0),
        **({"time_to_first_partial_ms_p50": ttfp_p50,
            "time_to_first_partial_ms_p95":
                window.get("time_to_first_partial_ms_p95"),
            # The streaming surface's headline claim, checked in-run:
            # a client sees stage 1's output before the final answer.
            "ttfp_lt_e2e_p50": bool(
                ttfp_p50 is not None
                and ttfp_p50 < window["p50_latency_ms"])}
           if ttfp_p50 is not None else {}),
        "pipeline_runs_completed": completed_runs,
        "concurrency": args.concurrency,
        "warmup_s": warmup_s,
        **_device_fields(),
    }


def _measure_device_capability(servable, iters: int = 12,
                               min_seconds: float = 0.5,
                               donated: bool = False) -> dict:
    """Requests/second the chip sustains with the input already resident on
    device and outputs left there — the link-independent ceiling. Iterations
    are launched without per-call blocking (one sync at the end) so host
    dispatch overlaps device execution. Reuses the warmed
    serving program; only a donating runtime (--donate-batch) forces a
    fresh non-donating jit (reusing a donated buffer across iterations
    would crash) — that one extra compile is the A/B's accepted cost."""
    import jax

    servable_bucket = servable.max_bucket
    fn = (jax.jit(servable.apply_fn,
                  in_shardings=(None, servable._batch_sharding))
          if donated else
          (lambda params, batch: servable._compiled(params, batch)))
    x = jax.device_put(
        np.zeros((servable_bucket, *servable.input_shape),
                 servable.input_dtype),
        servable._batch_sharding)
    jax.block_until_ready(fn(servable.params, x))  # warm
    t0 = time.perf_counter()
    done = 0
    while True:
        outs = [fn(servable.params, x) for _ in range(iters)]
        jax.block_until_ready(outs)
        done += iters
        elapsed = time.perf_counter() - t0
        if elapsed >= min_seconds:
            break
    return {"req_s": round(servable_bucket * done / elapsed, 2),
            "bucket": servable_bucket,
            "exec_ms_per_batch": round(1000 * elapsed / done, 2)}


def _clamp_for_cpu(args) -> None:
    """Size a ``--cpu`` test run so it finishes promptly: XLA:CPU sustains
    ~0.5 req/s on the UNet, so the chip-sized defaults (448 in-flight
    clients, 400 ms accumulation, depth-6 pipelining, 64-buckets) only
    stretch the drain (r1: 233 s at 128 clients)."""
    # echo has no device work; only the slow-model sizings apply. An EXPLICIT --concurrency wins:
    # saturation runs (--fabric comparisons) exist to push past the
    # comfortable defaults.
    if not getattr(args, "explicit_concurrency", False):
        args.concurrency = min(args.concurrency,
                               64 if args.model == "echo" else 16)
    args.pipeline_depth = min(args.pipeline_depth, 2)  # CPU compute serialises
    # With few clients the largest bucket rarely fills, so a long accumulation
    # window would just stale-wait every flush.
    args.max_wait_ms = min(args.max_wait_ms, 5.0)
    args.ramp = min(args.ramp, 2.0)  # ~0.5 req/s: a long ramp measures nothing
    if args.model != "echo":
        args.buckets = [b for b in args.buckets if b <= 16] or [1, 8]
    if args.model == "mixed":
        # Five families on one CPU core: one background stream of small
        # stacks is plenty to demonstrate the priority classes.
        args.stack_size = min(args.stack_size, 4)
        args.stack_streams = 1


def _apply_mesh_cpu_devices(args) -> None:
    """--mesh on the CPU substrate: fan the host out into enough XLA host
    devices to carry the layout via
    ``--xla_force_host_platform_device_count`` — the same substrate the
    mesh test suite runs on (docs/mesh_serving.md). XLA_FLAGS is read at
    backend *init*, not ``import jax``, so appending here works as long
    as no devices have been touched yet — which is why every caller sits
    before the first ``jax.devices()`` of its path."""
    if not getattr(args, "mesh", ""):
        return
    from ai4e_tpu.runtime.mesh import parse_mesh_spec
    layout = parse_mesh_spec(args.mesh)
    if layout is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count"
            f"={layout.size}").strip()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--duration", type=float, default=20.0)
    parser.add_argument("--ramp", type=float, default=6.0,
                        help="untimed steady-state ramp before the measured "
                             "window opens")
    # Enough in-flight clients to keep pipeline_depth × max-bucket examples
    # in the batcher (6 × 64 = 384) with headroom for tasks mid-transport.
    # Default is per model (None → see below): the composite config gets
    # fewer clients because every task crosses TWO dispatch+inference stages
    # and two host-side JPEG decodes. Not re-measured on a local chip.
    parser.add_argument("--concurrency", type=int, default=None)
    # Accumulation window: long enough that 64-buckets actually fill
    # (3 ms shipped ~21-example batches; 400 ms fills to ~50). Not
    # re-measured on a local chip.
    parser.add_argument("--max-wait-ms", type=float, default=400.0)
    # In-flight device batches. Not re-measured on a local chip.
    parser.add_argument("--pipeline-depth", type=int, default=6)
    # The worker's async endpoint replies with the TaskId immediately
    # (execution continues in the background), so each dispatch POST is a
    # short round trip — but at high task rates those round trips serialise
    # per dispatcher loop (measured on the echo config: 563 req/s at
    # concurrency 1 vs 880 at 64). Sized generously; cheap when idle.
    parser.add_argument("--dispatcher-concurrency", type=int, default=512)
    parser.add_argument("--buckets", type=int, nargs="+", default=None,
                        help="batch buckets (default per model)")
    parser.add_argument("--model", choices=sorted(CONFIGS),
                        default="landcover",
                        help="measurement config (BASELINE.json #1-#5)")
    parser.add_argument("--mode", choices=("async", "sync"), default="async",
                        help="async = task path (gateway→store→broker→worker);"
                             " sync = gateway reverse proxy to the worker's"
                             " sync endpoint (BASELINE configs #1/#2)")
    parser.add_argument("--transport", choices=("queue", "push"),
                        default="queue",
                        help="async transport under measurement: durable "
                             "queues + dispatchers (Service Bus analogue) or "
                             "topic push (Event Grid analogue) — the "
                             "reference's TRANSPORT_TYPE switch")
    parser.add_argument("--fabric", choices=("python", "native"),
                        default="python",
                        help="task-fabric cores under measurement: Python "
                             "store/broker or the C++ twins (native/"
                             "taskstore_core.cpp, broker_core.cpp) — the "
                             "control-plane saturation comparison")
    parser.add_argument("--checkpoint-dir", default="checkpoints",
                        help="trained weights (ai4e_tpu.train.make_checkpoints)")
    parser.add_argument("--tile", type=int, default=TILE,
                        help="landcover tile size (default 256 — the "
                             "production/baseline tile)")
    parser.add_argument("--stack-size", type=int, default=16,
                        help="--model mixed: images per background "
                             "megadetector stack")
    parser.add_argument("--stack-streams", type=int, default=2,
                        help="--model mixed: concurrent background stack "
                             "tasks")
    parser.add_argument("--donate-batch", action="store_true",
                        help="compile serving programs with input-batch "
                             "donation. NOTE: none of the bench families "
                             "can alias input to output (outputs are small "
                             "histograms/logits, shapes never match), so "
                             "this is an EARLY-FREE lever only — at most "
                             "it trims peak HBM while outputs materialize; "
                             "cheap to A/B in a window, expected ~neutral")
    parser.add_argument("--seq-len", type=int, default=4096,
                        help="sequence length for --model longcontext")
    parser.add_argument("--seq-input", choices=("tokens", "features"),
                        default="tokens",
                        help="longcontext input contract: token ids embedded "
                             "on-device (production wire, 2 B/token) or "
                             "pre-embedded f16 feature sequences (128 "
                             "B/token at D=64)")
    parser.add_argument("--wire",
                        choices=("rgb8", "yuv420", "dct", "jpeg"),
                        default="rgb8",
                        help="wire for the image configs (landcover/"
                             "megadetector/species/pipeline): rgb8 "
                             "(default — the families' and the deploy "
                             "spec's own) = raw uint8 (3 B/px); yuv420 = "
                             "planar 4:2:0 h2d (1.5 B/px, ops/yuv.py); "
                             "dct = quantized-DCT h2d (0.375 B/px, "
                             "ops/dct.py — device decodes with MXU matmuls; "
                             "fidelity-gated in tests/test_dct_wire.py); "
                             "jpeg = CLIENT wire of real camera JPEGs "
                             "(~0.3-1 B/px on the HTTP leg), host-decoded, "
                             "h2d rides yuv420. No wire has been measured "
                             "on a local chip (ROADMAP.md Speed 8)")
    parser.add_argument("--cache-hit-ratio", type=float, default=0.0,
                        help="enable the inference result cache (rescache/) "
                             "and drive a duplicate-request mix: this share "
                             "of POSTs repeat one identical hot request "
                             "(served from cache after the first "
                             "execution), the rest are unique and always "
                             "execute. The JSON gains a 'cache' block with "
                             "the measured hit ratio and served-from-cache "
                             "req/s. 0 (default) = cache off")
    parser.add_argument("--deadline-ms", type=float, default=0.0,
                        help="enable admission control (ai4e_tpu/admission/)"
                             " and attach this X-Deadline-Ms budget to every"
                             " request: the platform sheds work that cannot"
                             " finish in time (terminal `expired` status, "
                             "504/429 with X-Shed-Reason) and the JSON "
                             "gains an 'admission' block with GOODPUT "
                             "(within-deadline completions/s) beside raw "
                             "req/s plus shed/expired counts by hop and "
                             "priority. 0 (default) = admission off")
    parser.add_argument("--fault-rate", type=float, default=0.0,
                        help="inject seeded 5xx faults on the backend-POST "
                             "hop (dispatcher deliveries + sync proxy) at "
                             "this rate (ai4e_tpu/chaos/): the JSON gains "
                             "a 'fault' block with goodput under failure — "
                             "pair with/without --resilience for the A/B. "
                             "0 (default) = no injection")
    parser.add_argument("--fault-seed", type=int, default=0,
                        help="seed for the --fault-rate injector (runs "
                             "replay identically under one seed)")
    parser.add_argument("--resilience", action="store_true",
                        help="enable resilient routing (ai4e_tpu/"
                             "resilience/): per-backend circuit breakers, "
                             "health-aware picks, budget-bounded retries "
                             "with failover, 5xx-as-transient redelivery "
                             "(docs/resilience.md)")
    parser.add_argument("--ladder-derive", action="store_true",
                        help="derive the batch-bucket ladder from the "
                             "live cut-size histogram (runtime/ladder.py, "
                             "docs/device_path.md) — bench-tuned cadence "
                             "(2s period, 1s dwell) so swaps land inside "
                             "the measured window; result JSON gains a "
                             "`ladder` block")
    parser.add_argument("--ladder-path", default="",
                        help="persisted derived-ladder file (default: a "
                             "per-run temp file, reaped at exit); pass "
                             "the same path across two runs to measure "
                             "the restart-serves-hot contract")
    parser.add_argument("--double-buffer", action="store_true",
                        help="double-buffered device transfers "
                             "(AI4E_RUNTIME_BATCH_DOUBLE_BUFFER shape): "
                             "h2d/execute/d2h on dedicated threads so "
                             "transfer overlaps execute — pair with "
                             "--observability to read the overlap ratio")
    parser.add_argument("--observability", action="store_true",
                        help="enable the request-observability layer "
                             "(hop ledger + flight recorder + device-"
                             "phase decomposition, docs/observability"
                             ".md); the result JSON gains a 'phases' "
                             "block (queue-wait/h2d/execute/d2h "
                             "percentiles + h2d/execute overlap ratio)")
    parser.add_argument("--fsync-policy", default="",
                        help="journal the task store under this fsync "
                             "policy (never | always | group:<ms>, "
                             "docs/durability.md) and report a "
                             "`journal` block (bytes appended, fsyncs, "
                             "compactions, append p99 ms) in the result "
                             "JSON; empty (default) stays journal-less")
    parser.add_argument("--task-shards", type=int, default=1,
                        help="shard the task keyspace over N store shards "
                             "with per-shard dispatcher sub-queues "
                             "(docs/sharding.md); the result JSON gains a "
                             "'shards' block with per-shard goodput and "
                             "the peak long-poll watcher count")
    parser.add_argument("--mix", default="",
                        choices=("", *sorted(MIX_PRESETS)),
                        help="named traffic profile bundling the deadline/"
                             "priority/fault knobs (docs/orchestration.md): "
                             "interactive-heavy (2 s budgets, 70%% "
                             "interactive), batch-heavy (8 s budgets, 70%% "
                             "background), faulty-mixed (2 s budgets + 10%% "
                             "injected 5xx + resilience). Explicit knob "
                             "flags override the preset's values. Pair "
                             "with/without --orchestration for the A/B; "
                             "the JSON reports per-priority goodput and "
                             "deadline-miss rate either way")
    parser.add_argument("--orchestration", action="store_true",
                        help="enable deadline/cost-aware orchestration "
                             "(ai4e_tpu/orchestration/): per-request "
                             "placement on predicted completion-within-"
                             "deadline, the brownout degradation ladder, "
                             "predictive scaling. Forces admission + "
                             "resilience on (it composes their signals)")
    parser.add_argument("--priority-mix", default="",
                        help="weighted X-Priority draw per request, e.g. "
                             "'interactive:6,default:3,background:1' — "
                             "enables admission control; under saturation "
                             "the shedder refuses lowest class first. "
                             "Empty (default) = unlabeled traffic")
    parser.add_argument("--tenant-mix", default="",
                        help="declared tenants + per-request key draw, "
                             "e.g. 'paid=3:50,trial=1:5' "
                             "(name=weight:rps[:share]) — enables "
                             "multi-tenancy (docs/tenancy.md): gateway-"
                             "edge key resolution, token-bucket quotas "
                             "(429 + Retry-After over rate), weighted-"
                             "fair broker lanes, per-tenant accounting. "
                             "share defaults to weight; keys are "
                             "synthesized as key-<name>. The JSON gains "
                             "a 'tenancy' block and a per-tenant client "
                             "window. Empty (default) = tenancy off")
    parser.add_argument("--mesh", default="",
                        help="serving-mesh layout spec, e.g. 'dp=2' or "
                             "'dp=2,tp=2' (runtime/mesh/, "
                             "docs/mesh_serving.md): the worker serves "
                             "through a validated MeshEndpoint with "
                             "NamedSharding batch placement; on --cpu the "
                             "host is fanned out into dp*tp*sp XLA host "
                             "devices so the mesh path runs end-to-end. "
                             "The JSON gains a 'mesh' block (spec/tier/"
                             "devices/health). Empty (default) = unmeshed "
                             "runtime, identical to pre-mesh builds")
    parser.add_argument("--pipeline", action="store_true",
                        help="declared-DAG preset (docs/pipelines.md): a "
                             "2-stage echo chain executed by the pipeline "
                             "coordinator with the closed-loop client "
                             "consuming the SSE event stream — reports "
                             "pipeline goodput and time-to-first-partial "
                             "beside end-to-end latency. Async-only; "
                             "honest on CPU (no model weight — it "
                             "measures the DAG-coordination path).")
    parser.add_argument("--cpu", action="store_true",
                        help="explicit CPU test mode, sized to finish "
                             "promptly: correctness and counts only. "
                             "Without it the bench needs a TPU and exits "
                             "non-zero when JAX finds none")
    args = parser.parse_args()
    if args.mode == "sync" and args.model == "pipeline":
        parser.error("the composite pipeline is async-only (task handoffs)")
    apply_mix_preset(args)
    args.explicit_concurrency = args.concurrency is not None
    if args.concurrency is None:
        args.concurrency = {"pipeline": 160}.get(args.model, 448)
    if args.buckets is None:
        # Detector tiles are 4x the pixels of the others — bucket 64 would
        # spend HBM on padding the queue rarely fills.
        args.buckets = {"landcover": [1, 16, 64], "megadetector": [1, 8],
                        "species": [1, 16, 64], "pipeline": [1, 8],
                        "longcontext": [1, 4], "echo": [1, 64],
                        "mixed": [1, 16, 64]}[args.model]  # mixed: per-model
        if args.model == "longcontext" and args.seq_input == "tokens":
            # The 2 B/token wire makes big device batches nearly free on the
            # link (64 x 4096 ids = 1 MB vs the feature wire's 33 MB), so
            # token mode fills real buckets.
            args.buckets = [1, 16, 64]

    # One process, one device: the bench runs here, on the chip, or — only
    # when asked — on the CPU. Nothing below starts a child.
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        _apply_mesh_cpu_devices(args)
    else:
        _require_tpu()
    log(f"devices: {jax.devices()}")

    if args.pipeline:
        # Declared-DAG preset: the echo chain carries no model weight — it
        # measures the DAG-coordination path.
        if args.mode == "sync":
            parser.error("--pipeline is async-only (task events)")
        if not args.explicit_concurrency:
            args.concurrency = 64
        result = asyncio.run(run_pipeline_dag_bench(args))
    else:
        if args.cpu:
            _clamp_for_cpu(args)
        result = asyncio.run(run_bench(args))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
