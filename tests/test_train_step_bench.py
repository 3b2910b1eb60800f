"""The train-step bench (`scripts/bench_train_step.py`) — measures
fine-tuning MFU for the longcontext family on device.

A chip run is one unattended command, so the script's record shape is
pinned here at a tiny geometry on CPU: both attention strategies train to
a finite loss, the record carries its fields, and XLA cost analysis yields
step FLOPs (without which the record cannot carry an MFU).
"""

import importlib.util
import sys
from pathlib import Path

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_train_step",
    Path(__file__).resolve().parent.parent / "scripts" / "bench_train_step.py")
bench_train_step = importlib.util.module_from_spec(_spec)
sys.modules["bench_train_step"] = _spec.loader.exec_module(bench_train_step) \
    or bench_train_step


GEOM = dict(seq_len=128, dim=32, depth=1, heads=2, vocab_size=256, batch=2,
            steps=1)


class TestBenchStrategy:
    def test_full_attention_record(self):
        rec = bench_train_step.bench_strategy("full", **GEOM)
        assert rec["attention"] == "full"
        assert rec["steps_per_s"] > 0
        assert np.isfinite(rec["final_loss"])
        assert rec["geometry"]["seq_len"] == 128
        assert rec["tokens_per_s"] > 0
        # CPU CI must still produce FLOPs so the TPU capture can carry MFU.
        assert rec.get("step_flops", 0) > 0
        # No MFU claim off-TPU: the peak table is TPU-only.
        assert "train_mfu" not in rec

    def test_flash_attention_trains(self):
        # The r5 differentiable pallas path (interpret mode on CPU):
        # gradients flow through the custom_vjp and the loss is finite.
        rec = bench_train_step.bench_strategy("flash", **GEOM)
        assert rec["attention"] == "flash"
        assert np.isfinite(rec["final_loss"])
