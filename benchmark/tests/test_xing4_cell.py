"""Tests of what PR 43 added beside the benchmark: the cell ``xing4.reason``
rehearsed on the CPU cut, its metric definitions, its configuration against
the catalog's row and the hand arithmetic of its memory. Not tier-1 (the
reference's forward and the counts are held to the system in
``tests/test_xing4.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark.lib import prom  # noqa: E402
from benchmark.readers import prefill_roofline  # noqa: E402
from benchmark.references import xing4 as reference  # noqa: E402
from test_benchmark import _load, _run, cpu_root  # noqa: E402,F401 — cpu_root is a fixture
from test_benchmark import test_rehearsal as _rehearsal  # noqa: E402
import test_trace_placement as placement  # noqa: E402

# ``test_trace_placement.py`` holds every cell of the manifest to half its
# longest scheduled gap by a table of its own, which only a ``benchmark`` PR
# may edit: the new cell's line is added here, at import, so that a run of
# ``benchmark/tests`` holds the six cells (run ALONE, that file misses this
# key: ``PERF.md`` section 7 names the edit). 58 arrivals a window at 1.14
# req/s, the longest gap 4.17 s; the traced instant moves 1.87 s at the most.
placement.HALF_LONGEST_GAP.setdefault("xing4.reason", 2.09)

CELL = "xing4.reason"
CONFIG = "xing4.0-29b-a4b"
PARENT = "b2dd26fd0d6d98da7f90eca7004be850a5265ee3"
# The lists ISSUE 43 has the cell join: every entry's ``moves`` is an
# end-to-end metric the cell reports (it reports both p95s).
JOINED = (
    "engine_ttft_ms", "engine_itl_ms", "slot_occupancy", "prefill_ms",
    "tick_device_wait_ms", "tick_host_ms", "tick_admit_ms", "queue_wait_ms",
    "queue_wait_slot_ms", "queue_wait_joins_ms", "queue_wait_tick_ms",
    "step_active_slots", "kv_useful_share", "experts_touched",
    "expert_peak_load", "step_ahead_share", "step_starved_share",
    "join_dispatch_ms", "join_run_ms", "join_ahead_share",
    "device_unqueued_live_share", "device_idle_queued_share")
TRACE_BORNE = ("xing4_step_roofline", "xing4_prefill_roofline",
               "prefill_program_ms.reason")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_is_rehearsed(cpu_root, trace):  # noqa: F811
    _rehearsal(cpu_root, CELL, trace)


def test_rehearsal_reports_the_cells_metrics(cpu_root):  # noqa: F811
    proc = _run(cpu_root, os.path.join(cpu_root, "manifest.cpu.json"), CELL, 1)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["rehearsal_metrics"]
    # every counter-borne metric; the trace-borne ones need a chip
    missing = set(JOINED) - set(got)
    # the device thread's ledger is read between a trace's own scrapes, and a
    # 4 s window on the CPU may see no launch that found its step unread
    assert missing <= {"step_starved_share", "device_idle_queued_share",
                       "device_unqueued_live_share"}, sorted(missing)
    assert got["engine_itl_ms"]["value"] > 0
    # sixteen experts, four a token: a step of a few live slots touches some
    assert 1 <= got["experts_touched"]["value"] <= 16
    assert 0 < got["kv_useful_share"]["value"] <= 100
    assert "compile phases inside the window: 0" in proc.stdout


def test_the_entries_exist_and_agree_with_the_files():
    """The manifest has the configuration, the cell and its metrics, each
    listing this cell and each with its file."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    config, = (c for c in manifest["configs"] if c["name"] == CONFIG)
    body = _load(os.path.join(ROOT, config["file"]))
    assert config["source"] == body["source"]
    assert set(config["reduced"]) == set(body["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "max_position_embeddings", "weights"}
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "reason",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    reported = {m["name"] for m in manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"token_latency_p95_ms", "gen_latency_p95_ms",
                        "setup_s"}
    for name in JOINED + TRACE_BORNE:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] in reported, name
        if name in TRACE_BORNE:
            assert by_name[name]["workloads"] == [CELL]
        definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                        name + ".json"))
        importlib.import_module("benchmark.readers." + definition["reader"])
    # 50 entries stood; this PR brings three, each with its file
    assert len(manifest["per_layer"]) == 53
    files = {name[:-5] for name in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))}
    assert set(by_name) <= files
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"] == {"median": 384, "sigma": 0.7,
                                     "lo": 64, "hi": 2048}
    assert traffic["max_new_tokens"] == {"median": 896, "sigma": 0.5,
                                         "lo": 256, "hi": 1536}
    assert (traffic["ramp_s"], traffic["drain_cap_s"]) == (30.0, 45.0)
    docqa = _load(os.path.join(ROOT, "benchmark", "traffic", "docqa.json"))
    assert (traffic["path"], traffic["mode"]) == (docqa["path"],
                                                 docqa["mode"])
    # the longest stream fits a slot, and the bucket ladder holds every prompt
    env = body["worker_env"]
    assert (traffic["prompt_len"]["hi"] + traffic["max_new_tokens"]["hi"]
            <= int(env["AI4E_RUNTIME_KV_MAX_LEN"]))
    assert traffic["prompt_len"]["hi"] <= max(
        map(int, env["AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(",")))


def test_every_rotation_of_the_cell_traces_an_arrival():
    placement.test_every_rotation_traces_an_arrival(CELL)


def test_nothing_that_existed_changed_but_workloads_lists():
    """Against the parent commit: no file under ``benchmark/`` that existed
    has another byte, and an entry of the manifest that existed differs only
    by this cell's name at the end of its ``workloads``."""
    def git(*args):
        return subprocess.run(["git", "-C", ROOT, *args], capture_output=True,
                              text=True)
    if git("cat-file", "-e", PARENT).returncode:
        pytest.skip("the parent commit is not in this checkout")
    changed = git("diff", "--name-status", PARENT, "--",
                  "benchmark").stdout.split("\n")
    assert [line for line in changed
            if line and not line.startswith("A")] == []
    old = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    new = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key, added in (("configs", 1), ("workloads", 1), ("end_to_end", 0),
                       ("per_layer", 3)):
        assert len(new[key]) == len(old[key]) + added, key
        for was, now in zip(old[key], new[key]):
            if now != was:
                assert now == dict(was, workloads=was["workloads"] + [CELL])


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under its
    key, but for the keys ``reduced`` names; the models spec runs the
    published widths; the memory is the issue's hand arithmetic."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in reach")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    body = _load(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json"))
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert body["num_hidden_layers"] - body["first_k_dense_replace"] >= 4
    spec = body["models"]["models"][0]
    pub = row["config"]
    for ours, theirs in {
            "dim": "hidden_size", "heads": "num_attention_heads",
            "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank",
            "nope": "qk_nope_head_dim", "rope_dim": "qk_rope_head_dim",
            "v_dim": "v_head_dim", "rope_theta": "rope_theta",
            "mlp_dim": "intermediate_size", "experts": "n_routed_experts",
            "experts_per_token": "num_experts_per_tok",
            "expert_dim": "moe_intermediate_size",
            "route_scale": "routed_scaling_factor",
            "rms_eps": "rms_norm_eps", "vocab_size": "vocab_size",
            "streams": "hc_mult", "sinkhorn_iters": "hc_sinkhorn_iters",
            "hc_eps": "hc_eps", "hc_clamp": "mhc_h_res_clamp_max"}.items():
        assert spec[ours] == pub[theirs], ours
    assert -spec["hc_clamp"] == pub["mhc_h_res_clamp_min"]
    for ours, theirs in {
            "rope_factor": "factor", "beta_fast": "beta_fast",
            "rope_original": "original_max_position_embeddings",
            "beta_slow": "beta_slow", "mscale": "mscale",
            "mscale_all_dim": "mscale_all_dim"}.items():
        assert spec[ours] == pub["rope_scaling"][theirs], ours
    assert spec["shared_dim"] == (pub["n_shared_experts"]
                                  * pub["moe_intermediate_size"])
    assert (spec["depth"], spec["dense_layers"]) == (
        body["num_hidden_layers"], body["first_k_dense_replace"])
    assert spec["max_len"] == body["max_position_embeddings"] == int(
        body["worker_env"]["AI4E_RUNTIME_KV_MAX_LEN"])


def test_ops_and_bytes_are_the_hand_arithmetic():
    """ISSUE 43's count, by hand: mixer 28.4 M, an expert 11.01 M, an expert
    layer 744.9 M, the dense layer 128.2 M, embedding + head 939.5 M: 5.54 B
    = 11.07 GB; a cached position 8,960 B padded, 8,064 as published."""
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    spec = config["models"]["models"][0]
    d = 3584
    mixer = (d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256
             + 32 * 128 * d)
    assert mixer == reference.mixer_params(spec) - 768 - 512   # less 2 norms
    assert round(mixer / 1e6, 1) == 28.4
    assert reference.hyper_params(spec) == 4 * d * 24
    expert = 3 * d * 1024
    expert_layer = mixer + 65 * expert + d * 64 + 2 * 4 * d * 24
    dense_layer = mixer + 3 * d * 9216 + 2 * 4 * d * 24
    assert 744.9 <= expert_layer / 1e6 < 745.0     # the issue's 744.9
    assert round(dense_layer / 1e6, 1) == 128.2
    total = dense_layer + 6 * expert_layer + 2 * 131072 * d
    assert 11.07 <= 2 * total / 1e9 < 11.08         # the issue's 11.07 GB
    # what a step reads: all of it but the embedding table, + norms and bias
    read = reference.weight_bytes(spec)
    assert 0 < read - 2 * (total - 131072 * d) < 1e6
    # a step of 32 slots over 32,000 cached positions
    flops, nbytes = reference.ops_and_bytes(config, 32, 32000.0)
    rows = 7 * 2 * 576 * (32000 + 32)
    streams = 14 * 2 * 2 * 4 * d * 32
    assert nbytes == read + 2 * d * 32 + rows + streams
    active = (7 * (mixer + 768 + 512 + 2 * 4 * d * 24) + 3 * d * 9216
              + 6 * (d * 64 + 128 + 5 * expert) + d * 131072)
    assert flops == 2.0 * active * 32 + 7 * 2.0 * 32 * (2 * 512 + 64) * 32000
    # the bound of a full step is the weights' read: ~12.5 ms at 819 GB/s
    assert 12.0e-3 < nbytes / 819e9 < 13.5e-3 and flops / 197e12 < 2e-3
    # a prefill of 1,000 real tokens
    pairs = {"latent": 1000 * 1001 // 2}
    flops, nbytes = reference.prefill_ops_and_bytes(config, 1000.0, pairs)
    assert flops == 2.0 * ((active - d * 131072) * 1000 + d * 131072
                           + 7 * pairs["latent"] * 32 * 320)
    assert nbytes == read + 14 * 2 * 2 * 4 * d * 1000


@pytest.mark.parametrize("name", TRACE_BORNE)
def test_metric_is_silent_on_the_parents_program(name):
    """Without a trace (and on a worker that never served the family) the
    three new entries' readers return nothing and do not raise."""
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")), name=name)
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    old = prom.parse('ai4e_decode_step_seconds_sum{phase="decode"} 1.0\n'
                     'ai4e_decode_step_seconds_count{phase="decode"} 20\n')
    ctx = {"config": _load(os.path.join(ROOT, "benchmark", "configs",
                                        CONFIG + ".json")),
           "traffic": {}, "gen": {"requests": []}, "prom_before": {},
           "prom_after": old, "trace_prom_before": {},
           "trace_prom_after": old, "gauge_samples": [], "ledgers": [],
           "trace": None, "peaks": None, "notes": {}}
    assert reader.read(definition, ctx) is None


def test_the_prefill_roofline_reads_a_trace_and_the_counters():
    trace = {"modules": {"jit_prefill": {"seconds": 0.4, "calls": 8}},
             "devices": 1}
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))

    def scrape(joins, n):
        return prom.parse(
            f'ai4e_decode_step_seconds_count{{phase="prefill"}} {joins}\n'
            f'ai4e_decode_prefill_tokens_total{{kind="real"}} {joins * n}\n'
            f'ai4e_decode_prefill_pairs_total{{kind="latent"}} '
            f'{joins * n * (n + 1) // 2}\n')

    definition = {"name": "xing4_prefill_roofline", "module": "^jit_prefill$",
                  "family": "xing4", "dtype": "bf16"}
    peaks = _load(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    ctx = {"trace": trace, "config": config, "peaks": peaks,
           "trace_prom_before": scrape(3, 400),
           "trace_prom_after": scrape(11, 400), "notes": {}}
    share = prefill_roofline.read(definition, ctx)
    # 8 prefills of 400 tokens: every weight once each (10.1 GB: 12.4 ms at
    # 819 GB/s) outweighs ~0.5 TFLOP (2.6 ms): bound by memory
    assert ctx["notes"][definition["name"]]["bound"] == "memory"
    assert 20 < share < 30
