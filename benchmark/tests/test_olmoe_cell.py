"""Tests of what PR 26 added beside the benchmark: the cell ``olmoe.decode``
rehearsed on the CPU cut, its metric definitions, and the ``olmoe``
reference against the decode path. Not tier-1 (the reference's forward, its
routing and its counts are held to the system in ``tests/test_olmoe.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import importlib
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark.lib import prom  # noqa: E402
from test_benchmark import _load, _run, cpu_root, test_rehearsal  # noqa: E402,F401 — cpu_root is a fixture

CELL = "olmoe.decode"
# One entry a metric since PR 42 (PR 26's names carried the mix; ``step_ms``
# is retired: the tick's phases summed again).
METRICS = (
    "engine_itl_ms", "tick_device_wait_ms", "tick_host_ms", "tick_admit_ms",
    "prefill_ms", "queue_wait_ms", "step_active_slots", "kv_useful_share",
    "experts_touched", "expert_peak_load", "moe_step_roofline")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_is_rehearsed(cpu_root, trace):  # noqa: F811
    test_rehearsal(cpu_root, CELL, trace)


def test_rehearsal_reports_the_routing_metrics(cpu_root):  # noqa: F811
    import json
    proc = _run(cpu_root, os.path.join(cpu_root, "manifest.cpu.json"), CELL, 1)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["rehearsal_metrics"]
    # every counter-borne metric; the trace-borne roofline needs a chip
    assert set(METRICS) - {"moe_step_roofline"} <= set(got)
    assert 2 <= got["experts_touched"]["value"] <= 8
    assert got["expert_peak_load"]["value"] >= 1.0
    assert "compile phases inside the window: 0" in proc.stdout


def test_entries_are_appended_and_name_only_the_new_cell():
    """Membership, not position: later PRs appended cells and entries, and
    PR 42 folded the per-cell copies into one entry a metric."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert set(METRICS) <= set(entries)
    assert CELL in {w["name"] for w in manifest["workloads"]}
    assert "olmoe-1b-7b" in {c["name"] for c in manifest["configs"]}
    for name in METRICS:
        assert CELL in entries[name]["workloads"]
    assert entries["moe_step_roofline"]["workloads"] == [CELL]
    for m in manifest["end_to_end"]:
        if m["name"] != "setup_s":
            assert CELL in m["workloads"]


@pytest.mark.parametrize("name", METRICS)
def test_metric_is_silent_on_the_parents_program(name):
    """On a program without the new series (the parent's worker: no routing
    histograms; no trace) the new readers return nothing and do not raise;
    the ones over old series read them."""
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")), name=name)
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    old = prom.parse('ai4e_decode_step_seconds_sum{phase="decode"} 1.0\n'
                     'ai4e_decode_step_seconds_count{phase="decode"} 20\n')
    ctx = {"prom_before": {}, "prom_after": old, "ledgers": [],
           "config": {"derived": {}}, "gauge_samples": [], "notes": {},
           "trace": None}
    value = reader.read(definition, ctx)
    assert value is None


def test_configuration_holds_every_published_number():
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                "olmoe-1b-7b.json"))
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "max_position_embeddings"}
    assert set(config["reduced"]) == differs | {"weights"}
    spec = config["models"]["models"][0]
    assert (spec["dim"], spec["heads"], spec["experts"],
            spec["experts_per_token"], spec["expert_dim"], spec["vocab_size"],
            spec["depth"], spec["max_len"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_experts"], config["num_experts_per_tok"],
        config["intermediate_size"], config["vocab_size"],
        config["num_hidden_layers"], config["max_position_embeddings"])
    assert spec["rms_eps"] == config["rms_norm_eps"]
    assert spec["rope_theta"] == config["rope_theta"]


def test_reference_against_the_decode_path_and_a_fault():
    """Tokens the decode path serves pass the check; the same stream with
    one token replaced by an unlikely id does not."""
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    from benchmark.lib.payloads import PromptPayloads
    from benchmark.references import olmoe
    spec = {"family": "olmoe", "vocab_size": 64, "max_len": 48, "dim": 64,
            "depth": 2, "heads": 4, "experts": 8, "experts_per_token": 2,
            "expert_dim": 32}
    config = {"models": {"models": [spec]}}
    lm = build_lm_servable(**spec)
    backend = PagedDecodeRuntime(lm, slots=2, prompt_buckets=(16,))
    payloads = PromptPayloads(5, spec["vocab_size"])
    prompt = payloads.prompt(0, 9)
    tokens = [backend.prefill_into(0, prompt)]
    for i in range(11):
        tokens.append(backend.step([tokens[-1], 0], [len(prompt) + i, 0],
                                   [True, False])[0])
    state = olmoe.prepare(config, {"seed": 5})
    job = {"counter": 0, "prompt_len": 9, "result": {"tokens": tokens}}
    verdict = olmoe.check(state, [job])
    assert verdict["ok"], verdict
    logits = olmoe.forward(state["raw"], spec, prompt + tokens[:5])
    bad = list(tokens)
    bad[5] = int(np.argmin(logits[-1]))
    assert not olmoe.check(state, [dict(job, result={"tokens": bad})])["ok"]
