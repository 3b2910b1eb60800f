"""Tests of what PR 51 added beside the benchmark: the cell ``glm53.longctx``
rehearsed on the CPU cut, its metric definitions, its configuration against
the catalog's row and the hand arithmetic of its memory. Not tier-1 (the
reference's forward, the shares of the experts and the counts are held to the
system in ``tests/test_glm5.py``):

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
from benchmark.readers import counter_ratio  # noqa: E402
from benchmark.references import glm5 as reference  # noqa: E402
from test_benchmark import _load, _run, cpu_root  # noqa: E402,F401 — cpu_root is a fixture
from test_benchmark import test_rehearsal as _rehearsal  # noqa: E402
import test_trace_placement as placement  # noqa: E402

# ``test_trace_placement.py`` holds every cell of the manifest to half its
# longest scheduled gap by a table of its own, which only a ``benchmark`` PR
# may edit: the new cell's line is added here, at import, as
# ``test_ling3_cell.py`` adds its own, so that a run of ``benchmark/tests``
# holds the eight cells (run ALONE, that file misses three keys: ``PERF.md``
# section 7 names the edit).
placement.HALF_LONGEST_GAP.setdefault("xing4.reason", 2.09)
placement.HALF_LONGEST_GAP.setdefault("ling3.toolctx", 0.96)
placement.HALF_LONGEST_GAP.setdefault("glm53.longctx", 2.21)

CELL = "glm53.longctx"
CONFIG = "glm-5.3-flash"
PARENT = "30954b8e0e39da0c8218071ce471fa180e533c63"
# The lists ISSUE 51 has the cell join: every entry's ``moves`` is an
# end-to-end metric the cell reports (it reports both p95s).
# The cell reports the token p95 alone (its gen p95 is not steady under
# rotation: sweeps/glm53.longctx.md), so it joins the standing entries that
# move the token p95 and no other: an entry that moves the gen p95 cannot
# list it, and the ``.longdoc`` twins are held to ``dots3.longdoc`` alone by
# that cell's test.
JOINED = (
    "engine_ttft_ms", "slot_occupancy", "prefill_ms", "tick_admit_ms",
    "queue_wait_ms", "queue_wait_slot_ms", "queue_wait_joins_ms",
    "queue_wait_tick_ms", "step_starved_share", "join_dispatch_ms",
    "join_run_ms")
OWN = ("glm53_step_roofline", "glm53_prefill_roofline",
       "prefill_program_ms.longctx", "selected_share.longctx",
       "prefill_real_share.longctx", "held_picks_share.longctx",
       "engine_itl_ms.longctx")
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
    assert missing <= {"step_starved_share"}, sorted(missing)
    assert got["engine_itl_ms.longctx"]["value"] > 0
    # sixteen experts, four held, three a token
    assert 0 < got["held_picks_share.longctx"]["value"] <= 100
    assert 0 < got["prefill_real_share.longctx"]["value"] <= 100
    # index_topk 16 of contexts of 24-124: the selection leaves a part
    assert 10 < got["selected_share.longctx"]["value"] < 70
    assert "compile phases inside the window: 0" in proc.stdout


def test_the_entries_exist_and_agree_with_the_files():
    """The manifest has the configuration, the cell and its metrics, each
    listing this cell and each with its file."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    config, = (c for c in manifest["configs"] if c["name"] == CONFIG)
    body = _load(os.path.join(ROOT, config["file"]))
    assert config["source"] == body["source"]
    assert set(config["reduced"]) == set(body["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings", "weights"}
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longctx",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    reported = {m["name"] for m in manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    # the gen p95 is not steady under rotation: the token p95 alone
    assert reported == {"token_latency_p95_ms", "setup_s"}
    for name in JOINED + OWN:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] in reported, name
        if name in OWN:
            assert by_name[name]["workloads"] == [CELL]
        definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                        name + ".json"))
        importlib.import_module("benchmark.readers." + definition["reader"])
    # 57 entries stood; this PR brings seven, each with its file: all 64
    # ``test_trace_placement.py`` admits
    assert len(manifest["per_layer"]) == 64
    files = {name[:-5] for name in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))}
    assert set(by_name) <= files
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"] == {"median": 8192, "sigma": 0.5,
                                     "lo": 3072, "hi": 16384}
    assert traffic["max_new_tokens"] == {"median": 384, "sigma": 0.5,
                                         "lo": 128, "hi": 768}
    assert (traffic["ramp_s"], traffic["drain_cap_s"]) == (30.0, 45.0)
    assert isinstance(traffic["rate_per_s"], float)
    assert f"{traffic['rate_per_s']:g} req/s" in cell["why"]
    docqa = _load(os.path.join(ROOT, "benchmark", "traffic", "docqa.json"))
    assert (traffic["path"], traffic["mode"]) == (docqa["path"],
                                                 docqa["mode"])
    # every prompt is 1.5-8 x what the selection keeps
    spec = body["models"]["models"][0]
    assert traffic["prompt_len"]["lo"] >= 1.5 * spec["index_topk"]
    assert traffic["prompt_len"]["hi"] == 8 * spec["index_topk"]
    # the longest stream fits a slot, and the bucket ladder holds every prompt
    env = body["worker_env"]
    assert (traffic["prompt_len"]["hi"] + traffic["max_new_tokens"]["hi"]
            <= int(env["AI4E_RUNTIME_KV_MAX_LEN"]))
    assert traffic["prompt_len"]["hi"] <= max(
        map(int, env["AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(",")))
    # a bucket and the cache are whole blocks of the pool
    for length in (*env["AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(","),
                   env["AI4E_RUNTIME_KV_MAX_LEN"]):
        assert int(length) % (128 * spec["index_pool"]) == 0
    # the check's one stream: the shortest prompts with any answer fit it
    assert (traffic["prompt_len"]["lo"] + traffic["max_new_tokens"]["hi"]
            <= body["derived"]["reference_max_len"])
    # the admission cap is slots + pending, the route's concurrency with it
    cap = int(env["AI4E_RUNTIME_KV_SLOTS"]) + int(
        env["AI4E_RUNTIME_DECODE_MAX_PENDING"])
    assert spec["maximum_concurrent_requests"] == cap
    assert body["routes"][0]["concurrency"] == cap


def test_every_rotation_of_the_cell_traces_an_arrival():
    placement.test_every_rotation_traces_an_arrival(CELL)


def test_every_window_holds_a_stream_the_check_can_follow():
    """On every rotation the window's requests include the prompts at the
    mix's lower cap, whose stream fits ``reference_max_len`` whatever its
    answer: the sample of one is never empty."""
    from benchmark.generators.open_loop import schedule
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 "longctx.json"))
    body = _load(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json"))
    limit = body["derived"]["reference_max_len"]
    seconds = _load(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    window = [a for a in schedule(traffic, seconds, 0) if a["in_window"]]
    fit = [a for a in window
           if a["prompt_len"] + a["max_new_tokens"] <= limit]
    assert len(fit) >= 2
    assert all(a["prompt_len"] >= 1.5 * 2048 for a in window)


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
    key, but for the keys ``reduced`` names; the nested groups are copied
    whole; the models spec runs the published widths."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in reach")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-5.3-Flash")
    body = _load(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json"))
    assert body["source"] == row["source_url"]
    pub = row["config"]
    for key, value in pub.items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    # one leading dense layer + one whole period, four layers after the dense
    # one, an eighth of the experts and of the vocabulary
    held = body["held_layers"]
    assert held == [0, 3, 4, 5, 6] and body["num_hidden_layers"] == len(held)
    assert body["num_hidden_layers"] - body["first_k_dense_replace"] >= 4
    assert (body["n_routed_experts"], body["router_width"]) == (36, 288)
    assert body["n_routed_experts"] >= 8
    assert body["vocab_size"] * 8 == pub["vocab_size"]
    spec = body["models"]["models"][0]
    linear = pub["linear_attn_config"]
    assert spec["layer_types"] == [
        {"linear_attention": "kda", "deepseek_sparse_attention": "sparse"}[
            pub["layer_types"][i]] for i in held]
    assert spec["mlp_types"] == [pub["mlp_layer_types"][i] for i in held]
    assert [i in linear["kda_layers"] for i in held] == [
        kind == "kda" for kind in spec["layer_types"]]
    for ours, theirs in {
            "dim": "hidden_size", "attn_heads": "num_attention_heads",
            "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank",
            "qk_dim": "qk_head_dim", "v_dim": "v_head_dim",
            "index_heads": "index_n_heads", "index_dim": "index_head_dim",
            "index_topk": "index_topk", "index_pool": "index_kpool",
            "streams": "hc_mult", "sinkhorn_iters": "hc_sinkhorn_iters",
            "hc_eps": "hc_eps", "mlp_dim": "intermediate_size",
            "experts": "n_routed_experts",
            "experts_per_token": "num_experts_per_tok",
            "expert_dim": "moe_intermediate_size",
            "route_scale": "routed_scaling_factor",
            "swiglu_limit": "swiglu_limit",
            "rms_eps": "rms_norm_eps"}.items():
        assert spec[ours] == pub[theirs], ours
    assert (spec["heads"], spec["head_dim"], spec["conv"],
            spec["gate_bound"]) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"], linear["gate_lower_bound"])
    assert pub["qk_rope_head_dim"] == 0 and pub["qk_nope_head_dim"] == spec[
        "qk_dim"]
    assert spec["shared_dim"] == (pub["n_shared_experts"]
                                  * pub["moe_intermediate_size"])
    assert spec["kda_lora"] == linear["head_dim"]       # assumed.kda
    assert (spec["index_rope"], spec["index_theta"]) == (64, 1e6)
    assert (spec["experts_held"], spec["first_expert"]) == (
        body["n_routed_experts"], 0)
    assert spec["vocab_size"] == body["vocab_size"]
    assert spec["max_len"] == body["max_position_embeddings"] == int(
        body["worker_env"]["AI4E_RUNTIME_KV_MAX_LEN"])
    # every assumption names the reading not taken or the key it reads
    assert {"index_kpool", "swiglu_limit", "indexer_rotary", "mla_use_nope",
            "hyper_connections", "kda", "router", "torch_dtype", "mtp",
            "serving"} <= set(body["assumed"])
    for key in ("index_kpool", "swiglu_limit", "indexer_rotary", "kda"):
        assert "NOT taken" in body["assumed"][key], key
    assert "an eighth" in body["deployment"]       # the experts' load
    assert "controls" in body


def test_ops_and_bytes_are_the_hand_arithmetic():
    """ISSUE 51's count, by hand: KDA mixer 137.7 M, sparse mixer 124.4 M, an
    expert 25.17 M, an expert layer's FFN 932.3 M, dense FFN 151.0 M, the
    hyper-connections 0.8 M a layer: a dense KDA layer 289.5 M, an expert KDA
    layer 1,070.8 M, the expert sparse layer 1,057.5 M, embedding + head
    158.6 M: 4,718 M = 9.44 GB; a slot's state 17.37 MB and 18.94 MB of rows:
    64 slots 2.32 GB, 11.76 GB resident."""
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    spec = config["models"]["models"][0]
    d, wide = 4096, 64 * 128
    kda = (4 * d * wide + 2 * (d * 128 + 128 * wide) + d * 64
           + 4 * 3 * wide)
    assert round(kda / 1e6, 1) == 137.7
    # b_z, A, b, n_o
    assert kda == reference.kda_params(spec) - wide - 64 - wide - 128
    index = 1536 * 32 * 128 + d * 128 + d * 32
    assert round(index / 1e6, 2) == 6.95
    sparse = (d * 1536 + 1536 * 64 * 256 + d * 512 + 512 * 64 * 512
              + 64 * 256 * d + index)
    assert round(sparse / 1e6, 1) == 124.4
    # n_q, n_kv, the index key's LayerNorm
    assert sparse == reference.sparse_params(spec) - 1536 - 512 - 256
    expert = 3 * d * 2048
    assert round(expert / 1e6, 2) == 25.17
    moe = 36 * expert + expert + d * 288
    assert round(moe / 1e6, 1) == 932.3
    assert moe == reference.ffn_params(spec, False) - 2 * 288    # the bias
    dense = 3 * d * 12288
    assert round(dense / 1e6, 1) == 151.0
    assert dense == reference.ffn_params(spec, True)
    hyper = 2 * 4 * d * 24
    assert round(hyper / 1e6, 1) == 0.8
    assert hyper == 2 * reference.hyper_params(spec)
    assert round((kda + dense + hyper) / 1e6, 1) == 289.5
    assert round((kda + moe + hyper) / 1e6, 1) == 1070.8
    assert round((sparse + moe + hyper) / 1e6, 1) == 1057.5
    layers = (kda + dense) + 3 * (kda + moe) + (sparse + moe) + 5 * hyper
    vocabulary = 2 * 19360 * d
    assert round(vocabulary / 1e6, 1) == 158.6
    total = layers + vocabulary
    assert round(total / 1e6) == 4718 and round(2 * total / 1e9, 2) == 9.44
    # what a step reads: all of it but the embedding table, + the small ones
    read = reference.weight_bytes(spec)
    assert 0 < read - 2 * (total - 19360 * d) < 1e6
    # a slot: four KDA states in float32, their tails in bfloat16, one open
    # block's sum; a latent row a position and a pooled key a four
    state, small = reference.state_bytes_per_slot(spec)
    assert (state, small) == (4 * 64 * 128 * 128 * 4,
                              4 * 3 * 24576 * 2 + 128 * 4)
    assert round((state + small) / 1e6, 2) == 17.37
    rows = 17408 * 1024 + 17408 // 4 * 256
    assert round(rows / 1e6, 2) == 18.94
    cache = 64 * (state + small + rows)
    assert round(cache / 1e9, 2) == 2.32
    assert round((2 * total + cache) / 1e9, 2) == 11.76 >= 11.5
    # a step of 64 slots, 20 of them live at 9,000 cached positions each
    live = dict(config, derived=dict(config["derived"], live_slots=20.0))
    flops, nbytes = reference.ops_and_bytes(live, 64, 180000.0)
    assert nbytes == (read + 2 * d * 64 + 2 * state * 20.0 + 2 * small * 64
                      + 2 * (512 * (20 * 2048 + 64)
                             + 128 * (180000 / 4 + 64)))
    met = 8 * 36 / 288
    active = (4 * reference.kda_params(spec) + reference.sparse_params(spec)
              + dense + 10 * d + 5 * hyper
              + 4 * (d * 288 + 2 * 288 + (met + 1) * expert) + d * 19360)
    assert flops == (2.0 * active * 64 + 4.0 * (state // 4) * 64
                     + 2.0 * 32 * 128 * 180000 / 4
                     + 2.0 * 64 * 2 * 512 * 20 * 2048)
    # the live states are 7 % of a step's least bytes at 20 live slots; with
    # nobody saying how many are live, every slot's
    assert 0.06 < 2 * state * 20 / nbytes < 0.08
    _, every = reference.ops_and_bytes(config, 64, 180000.0)
    assert every > nbytes
    # bound by the read: ~12 ms at 819 GB/s
    assert 12e-3 < nbytes / 819e9 < 13e-3 and flops / 197e12 < 3e-3
    # a prefill of 8,000 real tokens
    pairs = {"selected": 8000 * 2048 - 2048 * 2047 // 2,
             "index": sum(t // 4 for t in range(8000))}
    flops, nbytes = reference.prefill_ops_and_bytes(config, 8000.0, pairs)
    assert flops == (2.0 * (active - d * 19360) * 8000 + 2.0 * d * 19360
                     + 2.0 * 4.0 * 4 * wide * 128 * 8000
                     + 2.0 * 32 * 128 * pairs["index"]
                     + 2.0 * 64 * 512 * pairs["selected"])
    assert nbytes == read + 2 * 5 * 2 * 2 * 4 * d * 8000
    # bound by compute: ~0.12 s at 197 TFLOP/s
    assert 0.08 < flops / 197e12 < 0.16


@pytest.mark.parametrize("name", OWN)
def test_metric_is_silent_on_the_parents_program(name):
    """Without a trace, and on a worker that never stepped the family (the
    parent cannot build it, so no position of it was ever live), the new
    entries' readers return nothing and do not raise."""
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


def test_the_selected_share_is_the_kept_positions_over_the_live_ones():
    definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                    "selected_share.longctx.json"))
    after = prom.parse(
        'ai4e_decode_kv_positions_total{kind="live",model="lm"} 9001\n'
        'ai4e_decode_kv_positions_total{kind="attended",model="lm"} 9217\n'
        'ai4e_decode_kv_positions_total{kind="selected",model="lm"} 2045\n')
    ctx = {"prom_before": {}, "prom_after": after}
    assert counter_ratio.read(definition, ctx) == pytest.approx(
        100 * 2045 / 9001)


def test_the_prefill_roofline_reads_a_trace_and_the_counters():
    trace = {"modules": {"jit_prefill": {"seconds": 2.4, "calls": 8}},
             "devices": 1}
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    n = 8000

    def scrape(joins):
        selected = n * 2048 - 2048 * 2047 // 2
        index = sum(t // 4 for t in range(n))
        return prom.parse(
            f'ai4e_decode_step_seconds_count{{phase="prefill"}} {joins}\n'
            f'ai4e_decode_prefill_tokens_total{{kind="real"}} {joins * n}\n'
            f'ai4e_decode_prefill_pairs_total{{kind="selected"}} '
            f'{joins * selected}\n'
            f'ai4e_decode_prefill_pairs_total{{kind="index"}} '
            f'{joins * index}\n')

    definition = {"name": "glm53_prefill_roofline", "module": "^jit_prefill$",
                  "family": "glm5", "dtype": "bf16"}
    peaks = _load(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    ctx = {"trace": trace, "config": config, "peaks": peaks,
           "trace_prom_before": scrape(3), "trace_prom_after": scrape(11),
           "notes": {}}
    share = prefill_roofline.read(definition, ctx)
    # 8 prefills of 8,000 tokens: ~24 TFLOP each outweighs the weights' read
    assert ctx["notes"][definition["name"]]["bound"] == "compute"
    assert 30 < share < 50
