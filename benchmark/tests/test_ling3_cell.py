"""Tests of what PR 48 added beside the benchmark: the cell ``ling3.toolctx``
rehearsed on the CPU cut, its metric definitions, its configuration against
the catalog's row and the hand arithmetic of its memory. Not tier-1 (the
reference's forward, the shares of the experts and the counts are held to the
system in ``tests/test_ling3.py``):

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
from benchmark.references import ling3 as reference  # noqa: E402
from test_benchmark import _load, _run, cpu_root  # noqa: E402,F401 — cpu_root is a fixture
from test_benchmark import test_rehearsal as _rehearsal  # noqa: E402
import test_trace_placement as placement  # noqa: E402

# ``test_trace_placement.py`` holds every cell of the manifest to half its
# longest scheduled gap by a table of its own, which only a ``benchmark`` PR
# may edit: the new cell's line is added here, at import, as
# ``test_xing4_cell.py`` adds its own, so that a run of ``benchmark/tests``
# holds the seven cells (run ALONE, that file misses both keys: ``PERF.md``
# section 7 names the edit). 153 arrivals a window at 3.0 req/s, the longest
# gap 1.91 s; the traced instant moves 0.94 s at the most.
placement.HALF_LONGEST_GAP.setdefault("xing4.reason", 2.09)
placement.HALF_LONGEST_GAP.setdefault("ling3.toolctx", 0.96)

CELL = "ling3.toolctx"
CONFIG = "ling-3.0-flash"
PARENT = "f5910f944e3da65239cb9a555ad284b1cfcf527b"
# The lists ISSUE 48 has the cell join: every entry's ``moves`` is an
# end-to-end metric the cell reports (it reports both p95s).
JOINED = (
    "engine_ttft_ms", "engine_itl_ms", "slot_occupancy", "prefill_ms",
    "tick_device_wait_ms", "tick_host_ms", "tick_admit_ms", "queue_wait_ms",
    "queue_wait_slot_ms", "queue_wait_joins_ms", "queue_wait_tick_ms",
    "step_active_slots", "kv_useful_share", "experts_touched",
    "expert_peak_load", "state_bytes_share", "state_live_share",
    "step_ahead_share", "step_starved_share", "join_dispatch_ms",
    "join_run_ms", "join_ahead_share", "device_unqueued_live_share",
    "device_idle_queued_share")
OWN = ("ling3_step_roofline", "ling3_prefill_roofline",
       "prefill_program_ms.toolctx", "held_picks_share.toolctx")
TRACE_BORNE = OWN[:3]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_is_rehearsed(cpu_root, trace):  # noqa: F811
    _rehearsal(cpu_root, CELL, trace)


def test_rehearsal_reports_the_cells_metrics(cpu_root):  # noqa: F811
    proc = _run(cpu_root, os.path.join(cpu_root, "manifest.cpu.json"), CELL, 1)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["rehearsal_metrics"]
    # every counter-borne metric; the trace-borne ones need a chip
    missing = set(JOINED + OWN[3:]) - set(got)
    # the device thread's ledger is read between a trace's own scrapes, and a
    # 4 s window on the CPU may see no launch that found its step unread
    assert missing <= {"step_starved_share", "device_idle_queued_share",
                       "device_unqueued_live_share"}, sorted(missing)
    assert got["engine_itl_ms"]["value"] > 0
    # sixteen experts, eight held, three a token inside two of four groups
    assert 0 < got["experts_touched"]["value"] <= 8
    assert 0 < got["held_picks_share.toolctx"]["value"] < 100
    assert 0 < got["state_bytes_share"]["value"] < 100
    assert 0 < got["state_live_share"]["value"] <= 100
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
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "max_position_embeddings", "weights"}
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "toolctx",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    reported = {m["name"] for m in manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"token_latency_p95_ms", "gen_latency_p95_ms",
                        "setup_s"}
    for name in JOINED + OWN:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] in reported, name
        if name in OWN:
            assert by_name[name]["workloads"] == [CELL]
        definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                        name + ".json"))
        importlib.import_module("benchmark.readers." + definition["reader"])
    # 53 entries stood; this PR brings four, each with its file (a later PR
    # brings more: no exact count, which would fail on its first entry as
    # ``test_xing4_cell.py``'s 53 does on these)
    assert len(manifest["per_layer"]) >= 57
    files = {name[:-5] for name in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))}
    assert set(by_name) <= files
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"] == {"median": 1024, "sigma": 0.9,
                                     "lo": 128, "hi": 8192}
    assert traffic["max_new_tokens"] == {"median": 640, "sigma": 0.5,
                                         "lo": 192, "hi": 1024}
    assert (traffic["ramp_s"], traffic["drain_cap_s"]) == (30.0, 45.0)
    assert isinstance(traffic["rate_per_s"], float)
    assert f"{traffic['rate_per_s']:g} req/s" in cell["why"]
    docqa = _load(os.path.join(ROOT, "benchmark", "traffic", "docqa.json"))
    assert (traffic["path"], traffic["mode"]) == (docqa["path"],
                                                 docqa["mode"])
    # the longest stream fits a slot, and the bucket ladder holds every prompt
    env = body["worker_env"]
    assert (traffic["prompt_len"]["hi"] + traffic["max_new_tokens"]["hi"]
            <= int(env["AI4E_RUNTIME_KV_MAX_LEN"]))
    assert traffic["prompt_len"]["hi"] <= max(
        map(int, env["AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(",")))
    # the admission cap is slots + pending, the route's concurrency with it
    cap = int(env["AI4E_RUNTIME_KV_SLOTS"]) + int(
        env["AI4E_RUNTIME_DECODE_MAX_PENDING"])
    assert body["models"]["models"][0]["maximum_concurrent_requests"] == cap
    assert body["routes"][0]["concurrency"] == cap


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
    # at least this PR's entries after the parent's (a later PR appends its
    # own, and its cell's name to lists: only THIS cell's name is held here)
    for key, added in (("configs", 1), ("workloads", 1), ("end_to_end", 0),
                       ("per_layer", 4)):
        assert len(new[key]) >= len(old[key]) + added, key
        for was, now in zip(old[key], new[key]):
            if now != was:
                at = now["workloads"].index(CELL)
                assert now == dict(was, workloads=now["workloads"])
                assert now["workloads"][:at] == was["workloads"]
    joined = {m["name"] for was, m in zip(old["per_layer"], new["per_layer"])
              if m != was}
    assert joined >= set(JOINED)


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under its
    key, but for the keys ``reduced`` names; the models spec runs the
    published widths."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in reach")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    body = _load(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json"))
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    # one whole period, at least four expert layers, a quarter held
    assert body["num_hidden_layers"] - body["first_k_dense_replace"] >= 4
    assert body["num_hidden_layers"] >= body["layer_group_size"]
    assert (body["num_experts"], body["router_width"]) == (128, 512)
    assert body["vocab_size"] * 4 == row["config"]["vocab_size"]
    spec = body["models"]["models"][0]
    pub = row["config"]
    for ours, theirs in {
            "dim": "hidden_size", "heads": "num_attention_heads",
            "head_dim": "head_dim", "conv": "short_conv_kernel_size",
            "gate_bound": "kda_lower_bound", "group": "layer_group_size",
            "kv_rank": "kv_lora_rank", "nope": "qk_nope_head_dim",
            "rope_dim": "qk_rope_head_dim", "v_dim": "v_head_dim",
            "rope_theta": "rope_theta", "mlp_dim": "intermediate_size",
            "experts": "num_experts",
            "experts_per_token": "num_experts_per_tok",
            "expert_dim": "moe_intermediate_size",
            "route_scale": "routed_scaling_factor",
            "rms_eps": "rms_norm_eps"}.items():
        assert spec[ours] == pub[theirs], ours
    assert spec["route_groups"] == [pub["n_group"], pub["topk_group"]]
    assert spec["shared_dim"] == (pub["num_shared_experts"]
                                  * pub["moe_shared_expert_intermediate_size"])
    assert spec["nope"] + spec["rope_dim"] == pub["qk_head_dim"]
    assert spec["rope_dim"] == pub["rotary_dim"]
    assert (spec["depth"], spec["dense_layers"]) == (
        body["num_hidden_layers"], body["first_k_dense_replace"])
    assert (spec["experts_held"], spec["first_expert"]) == (
        body["num_experts"], 0)
    assert spec["vocab_size"] == body["vocab_size"]
    assert spec["max_len"] == body["max_position_embeddings"] == int(
        body["worker_env"]["AI4E_RUNTIME_KV_MAX_LEN"])
    # the held layers' clamps are the published lists' first entries: all off
    depth = spec["depth"]
    assert spec["expert_swiglu_limits"] == pub[
        "expert_swiglu_limit_list"][:depth] == [0] * depth
    assert spec["shared_swiglu_limits"] == pub[
        "share_expert_swiglu_limit_list"][:depth] == [0] * depth
    # every assumption names the reading not taken or the key it reads
    assert {"kda_gate", "group_norm_size", "num_kv_heads_for_linear_attn",
            "use_qk_norm", "head_gate", "swiglu_limits", "mtp",
            "memory"} <= set(body["assumed"])
    assert "a quarter" in body["deployment"]       # the experts' load


def test_ops_and_bytes_are_the_hand_arithmetic():
    """ISSUE 48's count, by hand: KDA mixer 63.05 M, latent mixer 31.97 M, an
    expert 5.898 M, an expert layer's FFN 762.2 M, dense FFN 47.19 M: a dense
    KDA layer 110.2 M, an expert KDA layer 825.2 M, an expert latent layer
    794.1 M, embedding + head 201.2 M: 5,231.7 M = 10.46 GB; a slot's state
    13.03 MB; a cached position 1,280 B padded, 1,152 as published."""
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    spec = config["models"]["models"][0]
    d, wide = 2560, 32 * 128
    kda = 5 * d * wide + d * 32 + wide * d + 4 * 3 * wide
    assert round(kda / 1e6, 2) == 63.05
    assert kda == reference.kda_params(spec) - 32 - wide - 128   # A, b, n_o
    latent = d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32 + wide * d
    assert round(latent / 1e6, 2) == 31.97
    assert latent == reference.latent_params(spec) - 512         # n_kv
    expert = 3 * d * 768
    assert round(expert / 1e6, 3) == 5.898
    moe = 128 * expert + expert + d * 512
    assert round(moe / 1e6, 1) == 762.2
    assert moe == reference.ffn_params(spec, False) - 2 * 512    # the bias
    dense = 3 * d * 6144
    assert round(dense / 1e6, 2) == 47.19
    assert dense == reference.ffn_params(spec, True)
    layers = (kda + dense) + 5 * (kda + moe) + (latent + moe)
    assert round((kda + dense) / 1e6, 1) == 110.2
    assert round((kda + moe) / 1e6, 1) == 825.2
    assert round((latent + moe) / 1e6, 1) == 794.1
    vocabulary = 2 * 39296 * d
    assert round(vocabulary / 1e6, 1) == 201.2
    total = layers + vocabulary
    assert round(total / 1e6, 1) == 5231.7 and round(2 * total / 1e9, 2) == 10.46
    # what a step reads: all of it but the embedding table, + norms, A, b, bias
    read = reference.weight_bytes(spec)
    assert 0 < read - 2 * (total - 39296 * d) < 1e6
    # a slot: six KDA states in float32 and their tails in bfloat16
    state, tails = reference.state_bytes_per_slot(spec)
    assert (state, tails) == (6 * 32 * 128 * 128 * 4, 6 * 3 * 12288 * 2)
    assert round((state + tails) / 1e6, 2) == 13.03
    assert round(96 * (state + tails) / 1e9, 2) == 1.25
    assert reference.latent_row_bytes(spec) == 1152
    assert round(96 * 9216 * 1280 / 1e9, 2) == 1.13
    # a step of 96 slots, 40 of them live over 80,000 cached positions
    live = dict(config, derived=dict(config["derived"], live_slots=40.0))
    flops, nbytes = reference.ops_and_bytes(live, 96, 80000.0)
    assert nbytes == (read + 2 * d * 96 + 2 * state * 40.0 + 2 * tails * 96
                      + 1152 * (80000 + 96))
    met = 8 * 128 / 512
    active = (6 * (kda + 32 + wide + 128) + latent + 512 + dense + 14 * d
              + 6 * (d * 512 + 2 * 512 + (met + 1) * expert) + d * 39296)
    assert flops == (2.0 * active * 96 + 4.0 * (state // 4) * 96
                     + 2.0 * 32 * (2 * 512 + 64) * 80000)
    # the live states are about a tenth of a step's least bytes at 40 live
    # slots (a fifth at 96); with nobody saying how many are live, every
    # slot's
    assert 0.08 < 2 * state * 40 / nbytes < 0.10
    _, every = reference.ops_and_bytes(config, 96, 80000.0)
    assert every - nbytes == 2 * state * 56
    # bound by the read: ~14 ms at 819 GB/s
    assert 13.5e-3 < nbytes / 819e9 < 14.5e-3 and flops / 197e12 < 3e-3
    # a prefill of 1,000 real tokens
    pairs = {"latent": 1000 * 1001 // 2}
    flops, nbytes = reference.prefill_ops_and_bytes(config, 1000.0, pairs)
    assert flops == (2.0 * (active - d * 39296) * 1000 + 2.0 * d * 39296
                     + 2.0 * 4.0 * 6 * wide * 128 * 1000
                     + 2.0 * pairs["latent"] * 32 * 320)
    assert nbytes == read + 2 * 7 * 2 * 2 * d * 1000


@pytest.mark.parametrize("name", OWN)
def test_metric_is_silent_on_the_parents_program(name):
    """Without a trace (and on a worker that never served the family) the
    four new entries' readers return nothing and do not raise."""
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

    definition = {"name": "ling3_prefill_roofline", "module": "^jit_prefill$",
                  "family": "ling3", "dtype": "bf16"}
    peaks = _load(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    ctx = {"trace": trace, "config": config, "peaks": peaks,
           "trace_prom_before": scrape(3, 400),
           "trace_prom_after": scrape(11, 400), "notes": {}}
    share = prefill_roofline.read(definition, ctx)
    # 8 prefills of 400 tokens: every held weight once each (10.1 GB: 12.3
    # ms at 819 GB/s) outweighs ~0.3 TFLOP: bound by memory
    assert ctx["notes"][definition["name"]]["bound"] == "memory"
    assert 20 < share < 30
