"""Tests of what PR 56 added beside the benchmark: the cell ``axk1.history``
rehearsed on the CPU cut, its metric definitions, its configuration against
the catalog's row and the hand arithmetic of its memory. Not tier-1 (the
reference's forward, the shares of the experts and the counts are held to the
system in ``tests/test_axk1.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

It asserts membership and never a count of the manifest's entries
(``PERF.md`` section 7d (15)).
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
from benchmark.references import axk1 as reference  # noqa: E402
from test_benchmark import _load, _run, cpu_root  # noqa: E402,F401 — cpu_root is a fixture
from test_benchmark import test_rehearsal as _rehearsal  # noqa: E402
import test_trace_placement as placement  # noqa: E402

# ``test_trace_placement.py`` holds every cell of the manifest to half its
# longest scheduled gap by a table of its own, which only a ``benchmark`` PR
# may edit: the new cell's line is added here, at import, as
# ``test_glm53_cell.py`` adds its own, so that a run of ``benchmark/tests``
# holds the nine cells (run ALONE, that file misses four keys: ``PERF.md``
# section 7 names the edit).
placement.HALF_LONGEST_GAP.setdefault("xing4.reason", 2.09)
placement.HALF_LONGEST_GAP.setdefault("ling3.toolctx", 0.96)
placement.HALF_LONGEST_GAP.setdefault("glm53.longctx", 2.21)
# 40 arrivals a window at 0.78 req/s, the longest scheduled gap 5.57 s.
placement.HALF_LONGEST_GAP.setdefault("axk1.history", 2.79)

CELL = "axk1.history"
CONFIG = "a.x-k1"
PARENT = "169d7d7564c007e410f6fc789357ad21ca04ea44"
# The cell reports the token p95 alone (its gen p95 is not steady under
# rotation: sweeps/axk1.history.md), so it joins the standing entries that
# move the token p95 — and the six that move ``setup_s`` — and no other.
JOINED = (
    "engine_ttft_ms", "slot_occupancy", "prefill_ms", "tick_admit_ms",
    "queue_wait_ms", "queue_wait_slot_ms", "queue_wait_joins_ms",
    "queue_wait_tick_ms", "step_starved_share", "join_dispatch_ms",
    "join_run_ms")
BOOT = ("boot_serving_s", "boot_reach_chip_s", "boot_build_s", "boot_warm_s",
        "boot_lower_s", "boot_compile_s")
OWN = ("axk1_step_roofline", "axk1_prefill_roofline",
       "prefill_program_ms.history", "held_picks_share.history",
       "engine_itl_ms.history", "experts_touched.history")
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
    missing = set(JOINED + BOOT + OWN[3:]) - set(got)
    # the device thread's ledger is read between a trace's own scrapes, and a
    # 4 s window on the CPU may see no launch that found its step unread
    assert missing <= {"step_starved_share"}, sorted(missing)
    assert got["engine_itl_ms.history"]["value"] > 0
    # sixteen experts, four held, four a token
    assert 0 < got["held_picks_share.history"]["value"] <= 100
    assert 0 <= got["experts_touched.history"]["value"] <= 4
    assert "compile phases inside the window: 0" in proc.stdout


def test_the_entries_exist_and_agree_with_the_files():
    """The manifest has the configuration, the cell and its metrics, each
    listing this cell and each with its file."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    config, = (c for c in manifest["configs"] if c["name"] == CONFIG)
    body = _load(os.path.join(ROOT, config["file"]))
    assert config["source"] == body["source"]
    assert set(config["reduced"]) == set(body["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings", "weights"}
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "history",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    # the experts' load is a sixteenth of a deployment's: the `why` says so
    assert "1/16" in cell["why"] and "attention" in cell["why"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    reported = {m["name"] for m in manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    # the gen p95 is not steady under rotation: the token p95 alone
    assert reported == {"token_latency_p95_ms", "setup_s"}
    for name in JOINED + BOOT + OWN:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] in reported, name
        if name in OWN:
            assert by_name[name]["workloads"] == [CELL]
        definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                        name + ".json"))
        importlib.import_module("benchmark.readers." + definition["reader"])
    # no entry that moves a metric the cell does not report lists it
    for entry in manifest["per_layer"]:
        if CELL in entry.get("workloads", ()):
            assert entry["moves"] in reported, entry["name"]
    files = {name[:-5] for name in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))}
    assert set(by_name) <= files
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"]["median"] == 4096
    assert (traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]) == (
        256, 14336)
    assert 0.7 <= traffic["prompt_len"]["sigma"] <= 0.9
    assert traffic["max_new_tokens"] == {"median": 512, "sigma": 0.5,
                                         "lo": 192, "hi": 1024}
    assert (traffic["ramp_s"], traffic["drain_cap_s"]) == (30.0, 45.0)
    assert isinstance(traffic["rate_per_s"], float)
    assert f"{traffic['rate_per_s']:g} req/s" in cell["why"]
    longctx = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 "longctx.json"))
    assert (traffic["path"], traffic["mode"]) == (longctx["path"],
                                                  longctx["mode"])
    # short and long in one queue: prompts inside YaRN's fitted window and
    # three and a half times past it
    spec = body["models"]["models"][0]
    assert traffic["prompt_len"]["lo"] < spec["rope_original"] < (
        traffic["prompt_len"]["hi"])
    # the longest stream fits a slot, and the bucket ladder holds every prompt
    env = body["worker_env"]
    assert (traffic["prompt_len"]["hi"] + traffic["max_new_tokens"]["hi"]
            <= int(env["AI4E_RUNTIME_KV_MAX_LEN"]))
    buckets = list(map(int,
                       env["AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(",")))
    assert traffic["prompt_len"]["hi"] == max(buckets)
    # a bucket and the cache are whole blocks of 512 positions
    for length in (*buckets, int(env["AI4E_RUNTIME_KV_MAX_LEN"])):
        assert length % 512 == 0
    # the check's one stream may run 2,048 positions past the fitted window
    assert body["derived"]["reference_max_len"] >= 6144
    # the admission cap is slots + pending, the route's concurrency with it
    cap = int(env["AI4E_RUNTIME_KV_SLOTS"]) + int(
        env["AI4E_RUNTIME_DECODE_MAX_PENDING"])
    assert spec["maximum_concurrent_requests"] == cap
    assert body["routes"][0]["concurrency"] == cap
    assert 12 <= int(env["AI4E_RUNTIME_KV_SLOTS"]) <= 24


def test_every_rotation_of_the_cell_traces_an_arrival():
    placement.test_every_rotation_traces_an_arrival(CELL)


def test_every_window_holds_streams_the_check_can_follow():
    """On every rotation the window's requests are the same set: those whose
    stream fits ``reference_max_len`` are several, and some of them run PAST
    the 4,096 positions YaRN was fitted to."""
    from benchmark.generators.open_loop import schedule
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 "history.json"))
    body = _load(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json"))
    limit = body["derived"]["reference_max_len"]
    seconds = _load(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    window = [a for a in schedule(traffic, seconds, 0) if a["in_window"]]
    fit = [a["prompt_len"] + a["max_new_tokens"] for a in window
           if a["prompt_len"] + a["max_new_tokens"] <= limit]
    assert len(fit) >= 8
    assert sum(total > 4096 for total in fit) >= 6
    lengths = sorted(a["prompt_len"] for a in window)
    assert lengths[0] < 1024 and lengths[-1] == 14336


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
                assert now["workloads"][-1] == CELL
                assert now == dict(was, workloads=was["workloads"] + [CELL])
    joined = {m["name"] for was, m in zip(old["per_layer"], new["per_layer"])
              if m != was}
    assert joined == set(JOINED + BOOT)


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under its
    key, but for the keys ``reduced`` names; the nested group is copied
    whole; the models spec runs the published widths."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not in reach")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
    body = _load(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json"))
    assert body["source"] == row["source_url"]
    pub = row["config"]
    for key, value in pub.items():
        if key not in body["reduced"]:
            assert body[key] == value, key
        else:
            assert body["published"][key] == value, key
    # the leading dense layer as published + six expert layers, a sixteenth
    # of the experts (at least 8), an eighth of the vocabulary
    assert body["first_k_dense_replace"] == pub["first_k_dense_replace"] == 1
    assert body["num_hidden_layers"] - body["first_k_dense_replace"] >= 4
    assert body["n_routed_experts"] * 16 == pub["n_routed_experts"]
    assert body["n_routed_experts"] >= 8
    assert body["vocab_size"] * 8 == pub["vocab_size"]
    spec = body["models"]["models"][0]
    rope = pub["rope_scaling"]
    for ours, theirs in {
            "dim": "hidden_size", "heads": "num_attention_heads",
            "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank",
            "nope": "qk_nope_head_dim", "rope_dim": "qk_rope_head_dim",
            "v_dim": "v_head_dim", "rope_theta": "rope_theta",
            "mlp_dim": "intermediate_size", "experts": "n_routed_experts",
            "experts_per_token": "num_experts_per_tok",
            "expert_dim": "moe_intermediate_size",
            "route_scale": "routed_scaling_factor",
            "dense_layers": "first_k_dense_replace",
            "rms_eps": "rms_norm_eps"}.items():
        assert spec[ours] == pub[theirs], ours
    for ours, theirs in {
            "rope_factor": "factor", "beta_fast": "beta_fast",
            "beta_slow": "beta_slow", "mscale": "mscale",
            "mscale_all_dim": "mscale_all_dim",
            "rope_original": "original_max_position_embeddings"}.items():
        assert spec[ours] == rope[theirs], ours
    assert rope["type"] == "yarn" and pub["scoring_func"] == "sigmoid"
    assert pub["norm_topk_prob"] is True and pub["topk_method"] == "none"
    assert spec["route_groups"] is None          # assumed.topk_method
    assert spec["shared_dim"] == (pub["n_shared_experts"]
                                  * pub["moe_intermediate_size"])
    assert (spec["experts_held"], spec["first_expert"], spec["depth"]) == (
        body["n_routed_experts"], 0, body["num_hidden_layers"])
    assert spec["vocab_size"] == body["vocab_size"] == body["derived"][
        "vocab_size"]
    assert spec["max_len"] == body["max_position_embeddings"] == int(
        body["worker_env"]["AI4E_RUNTIME_KV_MAX_LEN"])
    # the assumption names the reading not taken
    assert {"topk_method", "rotary", "torch_dtype", "layout", "memory",
            "route"} <= set(body["assumed"])
    assert "NOT taken" in body["assumed"]["topk_method"]
    assert "route_groups" in body["assumed"]["topk_method"]
    assert "SIXTEEN" in body["deployment"]
    assert "SIXTEENTH" in body["deployment"]       # the experts' load
    assert "controls" in body


def test_ops_and_bytes_are_the_hand_arithmetic():
    """ISSUE 56's count, by hand: mixer 101.1 M, an expert 44.04 M, an expert
    layer here 675.0 M, the dense layer 497.5 M, embedding + head 293.6 M:
    4,841 M = 9.68 GB; a position 8,960 B over seven layers, 16 slots of
    16,384: 2.35 GB; 12.03 GB resident."""
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    spec = config["models"]["models"][0]
    d = 7168
    mixer = (d * 1536 + 1536 * 64 * 192 + d * 576 + 512 * 64 * 256
             + 64 * 128 * d)
    assert round(mixer / 1e6, 1) == 101.1
    assert mixer == reference.mixer_params(spec) - 1536 - 512   # n_q, n_kv
    expert = 3 * d * 2048
    assert round(expert / 1e6, 2) == 44.04
    moe = 12 * expert + expert + d * 192
    assert moe == reference.ffn_params(spec, False)
    assert round((mixer + moe) / 1e6, 1) == 675.0
    dense = 3 * d * 18432
    assert dense == reference.ffn_params(spec, True)
    assert round((mixer + dense) / 1e6, 1) == 497.5
    vocabulary = 2 * 20480 * d
    assert round(vocabulary / 1e6, 1) == 293.6
    total = 7 * mixer + dense + 6 * moe + vocabulary
    assert round(total / 1e6) == 4841 and round(2 * total / 1e9, 2) == 9.68
    # what a step reads: all of it but the embedding table, + the norms
    read = reference.weight_bytes(spec)
    assert 0 < read - 2 * (total - 20480 * d) < 1e6
    assert round(read / 1e9, 2) == 9.39
    # a position: seven padded rows of 640 lanes, bfloat16
    assert 7 * 640 * 2 == 8960
    cache = 16 * 16384 * 8960
    assert round(cache / 1e9, 2) == 2.35
    assert round((2 * total + cache) / 1e9, 2) == 12.03 >= 11.0
    assert reference.row_bytes(spec) == 2 * 576
    # a step of 16 slots, 9 of them live at 6,500 cached positions each
    flops, nbytes = reference.ops_and_bytes(config, 16, 58500.0)
    assert nbytes == read + 2 * d * 16 + 7 * 1152 * (58500 + 16)
    met = 8 * 12 / 192
    active = (7 * (reference.mixer_params(spec) + 2 * d) + dense
              + 6 * (d * 192 + (met + 1) * expert) + d * 20480)
    assert flops == 2.0 * active * 16 + 7 * 2.0 * 64 * 1088 * 58500
    # bound by the read: ~12 ms at 819 GB/s, two thirds of it held experts
    assert 11.5e-3 < nbytes / 819e9 < 12.5e-3 and flops / 197e12 < 1e-3
    assert 0.6 < 2 * 6 * 12 * expert / nbytes < 0.7
    # a prefill of 8,192 real tokens: a third of it the causal pairs
    pairs = {"latent": 8192 * 8193 // 2}
    flops, nbytes = reference.prefill_ops_and_bytes(config, 8192.0, pairs)
    attention = 2.0 * 7 * pairs["latent"] * 64 * 320
    assert flops == (2.0 * (active - d * 20480) * 8192 + 2.0 * d * 20480
                     + attention)
    assert 1.3e12 < attention / 7 < 1.5e12 and 0.25 < attention / flops < 0.4
    assert nbytes == read + 7 * (8 * d + 1152) * 8192
    # bound by compute: ~0.15 s at 197 TFLOP/s
    assert 0.1 < flops / 197e12 < 0.2


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


def test_the_prefill_roofline_reads_a_trace_and_the_counters():
    trace = {"modules": {"jit_prefill": {"seconds": 2.4, "calls": 8}},
             "devices": 1}
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    n = 8000

    def scrape(joins):
        return prom.parse(
            f'ai4e_decode_step_seconds_count{{phase="prefill"}} {joins}\n'
            f'ai4e_decode_prefill_tokens_total{{kind="real"}} {joins * n}\n'
            f'ai4e_decode_prefill_pairs_total{{kind="latent"}} '
            f'{joins * n * (n + 1) // 2}\n')

    definition = {"name": "axk1_prefill_roofline", "module": "^jit_prefill$",
                  "family": "axk1", "dtype": "bf16"}
    peaks = _load(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    ctx = {"trace": trace, "config": config, "peaks": peaks,
           "trace_prom_before": scrape(3), "trace_prom_after": scrape(11),
           "notes": {}}
    share = prefill_roofline.read(definition, ctx)
    # 8 prefills of 8,000 tokens: ~30 TFLOP each outweighs the weights' read
    assert ctx["notes"][definition["name"]]["bound"] == "compute"
    assert 40 < share < 60
