"""On-device train-step bench: fine-tuning MFU for the longcontext family.

The reference platform cannot train at all (frozen GPU containers); this
framework fine-tunes on the serving slice (``ai4e_tpu/train/step.py``).
Round 5 made the pallas flash-attention kernels differentiable
(``ops/pallas/flash_attention.py`` custom_vjp), so the long-context
TRAINING path no longer falls back to materializing S×S score matrices —
this script measures what that is worth on real hardware and what train
MFU the platform delivers (VERDICT r4 #4: publish measured before/after
MFU, not projections).

Method: SeqFormer at the trained serving geometry (the longcontext
checkpoint recipe: dim 256, depth 4, heads 2 → head_dim 128, vocab 32768,
S=4096, batch 8), one adamw Trainer step jitted on a 1-device mesh; timed
by the loss fetch (``train_step`` returns ``float(loss)`` — a host
readout, so every timed step has finished on the device). FLOPs from XLA cost
analysis of the compiled step; MFU against the chip's bf16 peak. Runs the
flash strategy first, then (``--compare-full``, default) the full-attention
strategy at the same geometry — the before/after pair.

Usage (time-boxed; partial output is valid JSONL):
    timeout 900 python scripts/bench_train_step.py
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

BF16_PEAK_FLOPS = {"tpu": 197e12}  # v5e per-chip; cpu/other → no MFU claim


def bench_strategy(attention: str, seq_len: int, dim: int, depth: int,
                   heads: int, vocab_size: int, batch: int, steps: int,
                   num_classes: int = 16) -> dict:
    import jax

    from ai4e_tpu.models import create_seqformer
    from ai4e_tpu.parallel import MeshSpec, make_mesh
    from ai4e_tpu.train import Trainer, cross_entropy_loss
    from ai4e_tpu.train.make_checkpoints import longcontext_batch

    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    model, params = create_seqformer(
        seq_len=seq_len, dim=dim, depth=depth, heads=heads,
        num_classes=num_classes, vocab_size=vocab_size, attention=attention)
    rng = np.random.default_rng(0)
    toks, labels = longcontext_batch(rng, batch, seq_len, vocab_size,
                                     num_classes)

    with mesh:
        trainer = Trainer(model.apply, params, mesh,
                          loss_fn=cross_entropy_loss)
        t0 = time.perf_counter()
        trainer.train_step(toks, labels)  # compile + first step
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        loss = 0.0
        for _ in range(steps):
            # Each call fetches the scalar loss to host — real timings.
            loss = trainer.train_step(toks, labels)
        elapsed = time.perf_counter() - t0

        flops = None
        try:
            cost = trainer._step.lower(
                trainer.params, trainer.opt_state, toks, labels
            ).compile().cost_analysis()
            if cost and cost.get("flops"):
                flops = float(cost["flops"])
        except Exception:  # cost analysis is best-effort per backend
            pass

    steps_per_s = steps / elapsed
    rec = {
        "attention": attention,
        "geometry": {"seq_len": seq_len, "dim": dim, "depth": depth,
                     "heads": heads, "vocab_size": vocab_size,
                     "batch": batch},
        "steps": steps,
        "steps_per_s": round(steps_per_s, 3),
        "tokens_per_s": round(steps_per_s * batch * seq_len, 1),
        "compile_s": round(compile_s, 1),
        "final_loss": round(float(loss), 4),
        "backend": jax.default_backend(),
        "device": jax.devices()[0].device_kind,
    }
    if flops:
        rec["step_flops"] = flops
        peak = BF16_PEAK_FLOPS.get(jax.default_backend())
        if peak:
            rec["train_mfu"] = round(flops * steps_per_s / peak, 4)
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    # Defaults = the longcontext checkpoint recipe's serving geometry
    # (train/make_checkpoints.py train_longcontext).
    p.add_argument("--seq-len", type=int, default=4096)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--vocab-size", type=int, default=32768)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--compare-full", dest="compare_full",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="also bench attention='full' at the same geometry "
                        "(the pre-r5 training path) for the before/after")
    p.add_argument("--cpu", action="store_true",
                   help="force XLA:CPU (debug/smoke) — JAX_PLATFORMS=cpu "
                        "as a flag; no MFU is reported off the chip")
    args = p.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    records = []
    for strategy in (["flash", "full"] if args.compare_full else ["flash"]):
        rec = bench_strategy(strategy, args.seq_len, args.dim, args.depth,
                             args.heads, args.vocab_size, args.batch,
                             args.steps)
        records.append(rec)
        print(json.dumps(rec), flush=True)

    summary = {"summary": True,
               "flash_steps_per_s": records[0]["steps_per_s"]}
    if records[0].get("train_mfu") is not None:
        summary["flash_train_mfu"] = records[0]["train_mfu"]
    if len(records) == 2:
        summary["full_steps_per_s"] = records[1]["steps_per_s"]
        summary["flash_speedup_vs_full"] = round(
            records[0]["steps_per_s"] / records[1]["steps_per_s"], 2)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
