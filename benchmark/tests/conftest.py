"""What PR 26's cell adds to the rehearsal tables of ``test_benchmark.py``
(the CPU cut of the ``olmoe`` family and of the ``decode`` mix), from a new
file: a PR that is not a ``benchmark`` PR edits no file the benchmark has.
``test_olmoe_cell.py`` runs the rehearsals these entries name."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_benchmark as tb  # noqa: E402

tb.CPU_CUT["olmoe"] = {
    "model": {"vocab_size": 256, "max_len": 128, "dim": 64, "depth": 2,
              "heads": 4, "experts": 8, "experts_per_token": 2,
              "expert_dim": 32},
    "worker_env": {"AI4E_RUNTIME_KV_SLOTS": "8",
                   "AI4E_RUNTIME_KV_MAX_LEN": "128",
                   "AI4E_RUNTIME_DECODE_PROMPT_BUCKETS": "16,32"},
    "derived": {"vocab_size": 256, "kv_slots": 8, "reference_max_len": 128}}
tb.TRAFFIC_CUT["decode"] = {
    "rate_per_s": 6.0, "ramp_s": 2.0, "drain_cap_s": 20.0,
    "prompt_len": {"median": 12, "sigma": 0.6, "lo": 4, "hi": 30},
    "max_new_tokens": {"median": 14, "sigma": 0.5, "lo": 6, "hi": 28}}
tb.REHEARSALS += [("olmoe.decode", 0), ("olmoe.decode", 1)]
