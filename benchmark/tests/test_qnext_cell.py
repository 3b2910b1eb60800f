"""Tests of what PR 32 added beside the benchmark: the cell ``qnext.docqa``
rehearsed on the CPU cut, its metric definitions, its configuration against
the published one, and the ``qwen3-next`` reference against the decode path.
Not tier-1 (the reference's forward, the share of experts and its counts are
held to the system in ``tests/test_qwen3_next.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark.lib import prom  # noqa: E402
from test_benchmark import (  # noqa: E402,F401 — cpu_root is a fixture
    _load, _manifest_since, _run, cpu_root, test_rehearsal)

CELL = "qnext.docqa"
PARENT = "d516040db90cd05f785cbb75498e3ea295f4ba51"
# One entry a metric since PR 42 (PR 32's names carried the mix where another
# cell has the metric too; ``step_ms`` is retired: the tick's phases summed).
METRICS = (
    "engine_itl_ms", "tick_device_wait_ms", "tick_host_ms", "tick_admit_ms",
    "prefill_ms", "queue_wait_ms", "step_active_slots", "kv_useful_share",
    "experts_touched", "expert_peak_load", "held_picks_share.docqa",
    "state_bytes_share", "qnext_step_roofline",
    # the layers above the engine, which run here as in ``gpt2m.chat``
    "fabric_ms", "fabric_queue_ms", "fabric_deliver_ms", "shell_in_ms",
    "shell_out_ms", "engine_ttft_ms", "slot_occupancy")
# the metrics of the block that only this cell has
OWN = ("held_picks_share.docqa", "qnext_step_roofline")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_is_rehearsed(cpu_root, trace):  # noqa: F811
    test_rehearsal(cpu_root, CELL, trace)


def test_rehearsal_reports_the_state_and_routing_metrics(cpu_root):  # noqa: F811
    proc = _run(cpu_root, os.path.join(cpu_root, "manifest.cpu.json"), CELL, 1)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["rehearsal_metrics"]
    # every counter-borne metric; the trace-borne roofline needs a chip
    assert set(METRICS) - {"qnext_step_roofline"} <= set(got)
    assert 0 < got["experts_touched"]["value"] <= 4
    assert 0 < got["held_picks_share.docqa"]["value"] < 100
    assert 0 < got["state_bytes_share"]["value"] < 100
    assert "compile phases inside the window: 0" in proc.stdout


def test_entries_are_appended_and_name_only_the_new_cell():
    """Membership, not position: every metric of PR 32's block lists the
    cell, and the ones no other cell has list it alone (PR 42 folded the
    per-cell copies into one entry a metric)."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert set(METRICS) <= set(entries)
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "qwen3-next-80b-a3b",
                    "traffic": "docqa", "chips": 1, "why": cell["why"]}
    assert "qwen3-next-80b-a3b" in [c["name"] for c in manifest["configs"]]
    for name in METRICS:
        assert CELL in entries[name]["workloads"]
    for name in OWN:
        assert entries[name]["workloads"] == [CELL]
    for m in manifest["end_to_end"]:
        if m["name"] != "setup_s":
            assert CELL in m["workloads"]


def test_nothing_the_benchmark_had_is_edited():
    """Against PR 32's parent commit (``test_benchmark._manifest_since``):
    the cell's name follows the parent's at the end of the two latency
    metrics' ``workloads``, and nothing else of an end-to-end entry
    differs."""
    old, new = _manifest_since(PARENT)
    for was, now in zip(old["end_to_end"], new["end_to_end"], strict=True):
        if "workloads" in was:
            given = was["workloads"] + [CELL]
            assert now == dict(was, workloads=now["workloads"])
            assert now["workloads"][:len(given)] == given
        else:
            assert now == was


@pytest.mark.parametrize("name", METRICS)
def test_metric_is_silent_on_the_parents_program(name):
    """On a program without the new series (the parent's worker: no routing
    histograms, no cache-bytes counter; no trace) the new readers return
    nothing and do not raise; the ones over old series read them."""
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")), name=name)
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    old = prom.parse('ai4e_decode_step_seconds_sum{phase="decode"} 1.0\n'
                     'ai4e_decode_step_seconds_count{phase="decode"} 20\n')
    ctx = {"prom_before": {}, "prom_after": old, "ledgers": [],
           "config": {"derived": {}}, "gauge_samples": [], "notes": {},
           "trace": None}
    assert reader.read(definition, ctx) is None


def test_state_bytes_share_reads_the_two_kinds():
    definition = _load(os.path.join(
        ROOT, "benchmark", "layer_metrics", "state_bytes_share.json"))
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    after = prom.parse(
        'ai4e_decode_cache_bytes_total{model="lm",kind="state"} 900\n'
        'ai4e_decode_cache_bytes_total{model="lm",kind="kv"} 300\n')
    before = prom.parse(
        'ai4e_decode_cache_bytes_total{model="lm",kind="state"} 300\n'
        'ai4e_decode_cache_bytes_total{model="lm",kind="kv"} 100\n')
    assert reader.read(definition, {"prom_before": before,
                                    "prom_after": after}) == pytest.approx(75)


def test_roofline_counts_the_live_slots_states():
    """``step_roofline_live`` hands the mean live slots of the window to the
    family's ``ops_and_bytes``: fewer live slots, fewer least bytes, a lower
    share of the same device time."""
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", "qnext_step_roofline.json")),
        name="qnext_step_roofline")
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                "qwen3-next-80b-a3b.json"))

    def share(live):
        after = prom.parse(
            'ai4e_decode_step_seconds_count{phase="decode"} 100\n'
            f'ai4e_decode_step_active_slots_sum {100 * live}\n'
            'ai4e_decode_step_active_slots_count 100\n')
        ctx = {"prom_before": {}, "prom_after": after, "config": config,
               "gen": {"requests": [{"ok": True, "prompt_len": 500,
                                     "max_new_tokens": 100}]},
               "trace": {"modules": {"jit_step": {"seconds": 1.8,
                                                  "calls": 100}}},
               "peaks": {"flops_per_s": {"bf16": 197e12},
                         "hbm_bytes_per_s": 819e9}, "notes": {}}
        value = reader.read(definition, ctx)
        assert ctx["notes"]["qnext_step_roofline"]["bound"] == "memory"
        return value

    from benchmark.references import qwen3_next
    per_slot = qwen3_next.state_bytes_per_slot(qwen3_next._model_spec(config))
    assert share(32) - share(16) == pytest.approx(
        100 * 2 * 16 * per_slot / 819e9 * 100 / 1.8)
    assert 60 < share(16) < share(32) < 100


def test_configuration_holds_every_published_number():
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                "qwen3-next-80b-a3b.json"))
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size",
                       "max_position_embeddings"}
    assert set(config["reduced"]) == differs | {"weights"}
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = (c for c in manifest["configs"]
              if c["name"] == "qwen3-next-80b-a3b")
    assert set(entry["reduced"]) == set(config["reduced"])
    spec = config["models"]["models"][0]
    assert (spec["dim"], spec["heads"], spec["kv_heads"], spec["head_dim"],
            spec["lin_k_heads"], spec["lin_v_heads"], spec["lin_dim"],
            spec["conv"], spec["experts_per_token"], spec["expert_dim"],
            spec["shared_dim"], spec["full_interval"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["linear_num_key_heads"], config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_conv_kernel_dim"],
        config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["shared_expert_intermediate_size"],
        config["full_attention_interval"])
    assert spec["rotary_dim"] == config["head_dim"] * config[
        "partial_rotary_factor"]
    assert spec["experts"] == published["num_experts"] == config[
        "router_width"]
    assert spec["experts_held"] == config["num_experts"] == 128
    assert spec["vocab_size"] == config["vocab_size"] == config["derived"][
        "vocab_size"] == published["vocab_size"] // 4
    assert spec["depth"] == config["num_hidden_layers"] == 12
    assert spec["max_len"] == config["max_position_embeddings"] == int(
        config["worker_env"]["AI4E_RUNTIME_KV_MAX_LEN"])
    assert spec["rms_eps"] == config["rms_norm_eps"]
    assert spec["rope_theta"] == config["rope_theta"]
    # the longest stream of the mix fits the cache; sampled streams cross the
    # decode kernel's 1,024-position block edge
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic", "docqa.json"))
    assert traffic["prompt_len"]["hi"] + traffic["max_new_tokens"][
        "hi"] < spec["max_len"]
    assert config["derived"]["reference_max_len"] >= 1536


def test_reference_against_the_decode_path_and_a_fault(monkeypatch):
    """Tokens the decode path serves pass the check; the same stream with
    one token replaced by an unlikely id does not."""
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    from benchmark.lib.payloads import PromptPayloads
    from benchmark.references import qwen3_next
    spec = {"family": "qwen3-next", "vocab_size": 64, "max_len": 48,
            "dim": 64, "depth": 4, "full_interval": 4, "heads": 4,
            "kv_heads": 2, "head_dim": 32, "rotary_dim": 8, "lin_k_heads": 2,
            "lin_v_heads": 4, "lin_dim": 16, "conv": 4, "experts": 16,
            "experts_held": 8, "first_expert": 0, "experts_per_token": 3,
            "expert_dim": 32, "shared_dim": 32}
    config = {"models": {"models": [spec]}}
    lm = build_lm_servable(**spec)
    backend = PagedDecodeRuntime(lm, slots=2, prompt_buckets=(16,))
    payloads = PromptPayloads(5, spec["vocab_size"])
    prompt = payloads.prompt(0, 9)
    tokens = [backend.prefill_into(0, prompt)]
    for i in range(11):
        tokens.append(backend.step([tokens[-1], 0], [len(prompt) + i, 0],
                                   [True, False])[0])
    state = qwen3_next.prepare(config, {"seed": 5})
    job = {"counter": 0, "prompt_len": 9, "result": {"tokens": tokens}}
    verdict = qwen3_next.check(state, [job])
    assert verdict["ok"], verdict
    assert verdict["share_beyond"] <= verdict["limit_share"] == 0.03
    # the second limit alone: every token counted as beyond its margin
    monkeypatch.setattr(qwen3_next, "SHARE_MARGIN", -1.0)
    crowded = qwen3_next.check(state, [job])
    assert not crowded["ok"] and not crowded["bad"]
    assert crowded["share_beyond"] == 1.0
    monkeypatch.undo()
    logits = qwen3_next.forward(state["raw"], spec, prompt + tokens[:5])
    bad = list(tokens)
    bad[5] = int(np.argmin(logits[-1]))
    assert not qwen3_next.check(state, [dict(job, result={"tokens": bad})])[
        "ok"]
