"""Tests of what PR 34 added beside the benchmark: the cell
``granite.burstchat`` rehearsed on the CPU cut, its burst schedule, its
metric definitions, its configuration against the published one, and the
``granite-hybrid`` reference against the decode path. Not tier-1 (the
reference's forward and its counts are held to the system in
``tests/test_granite_hybrid.py``):

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

from benchmark.generators import burst_loop  # noqa: E402
from benchmark.lib import prom  # noqa: E402
from test_benchmark import (  # noqa: E402,F401 — cpu_root is a fixture
    _load, _manifest_since, _run, cpu_root, test_rehearsal)

CELL = "granite.burstchat"
CONFIG = "granite-4.0-h-micro"
PARENT = "d278ad1dddd6f667ecf3953ea300216013ac609b"
# One entry a metric since PR 42 (PR 34's names carried the mix where another
# cell has the metric too; ``step_ms`` is retired: the tick's phases summed).
TICK_SET = (
    "engine_itl_ms", "engine_ttft_ms", "tick_device_wait_ms", "tick_host_ms",
    "tick_admit_ms", "prefill_ms", "queue_wait_ms", "step_active_slots",
    "slot_occupancy", "kv_useful_share", "step_ahead_share",
    "state_bytes_share")
# the metrics of the block that only this cell has
OWN = ("tick_joins.burstchat", "granite_step_roofline")
METRICS = TICK_SET + ("state_live_share",) + OWN


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_is_rehearsed(cpu_root, trace):  # noqa: F811
    test_rehearsal(cpu_root, CELL, trace)


def test_rehearsal_reports_the_state_and_join_metrics(cpu_root):  # noqa: F811
    proc = _run(cpu_root, os.path.join(cpu_root, "manifest.cpu.json"), CELL, 1)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["rehearsal_metrics"]
    # every counter-borne metric; the trace-borne roofline needs a chip
    assert set(METRICS) - {"granite_step_roofline"} <= set(got)
    assert 0 < got["state_live_share"]["value"] < 100
    assert 0 < got["state_bytes_share"]["value"] < 100
    assert got["tick_joins.burstchat"]["value"] >= 1
    # Since PR 35 a step moves the live slots' state blocks only (the
    # convolution tails stay a dense update of every slot's): live / moved
    # lies well above live slots / slots, which it was while the step moved
    # every slot's.
    assert got["state_live_share"]["value"] > 1.5 * (
        100 * got["step_active_slots"]["value"] / 8)
    assert "compile phases inside the window: 0" in proc.stdout


def test_the_entries_exist_and_agree_with_the_files():
    """The manifest has the configuration, the cell and its metrics, each
    listing this cell (alone where no other cell has the metric) and each
    with its file; where they stand in their lists is a later PR's to
    change."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    config, = (c for c in manifest["configs"] if c["name"] == CONFIG)
    body = _load(os.path.join(ROOT, config["file"]))
    assert config["source"] == body["source"]
    assert set(config["reduced"]) == set(body["reduced"]) == {
        "max_position_embeddings", "weights"}
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "burstchat",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in METRICS:
        assert CELL in by_name[name]["workloads"]
        if name in OWN:
            assert by_name[name]["workloads"] == [CELL]
        definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                        name + ".json"))
        importlib.import_module("benchmark.readers." + definition["reader"])
    for m in manifest["end_to_end"]:
        if m["name"] != "setup_s":
            assert CELL in m["workloads"]
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    assert traffic["generator"] == "burst_loop"
    chat = _load(os.path.join(ROOT, "benchmark", "traffic", "chat.json"))
    for key in ("prompt_len", "max_new_tokens", "path", "mode"):
        assert traffic[key] == chat[key]         # lengths exactly gpt2m.chat's
    assert traffic["burst"] == {"median": 4, "sigma": 0.8, "lo": 1, "hi": 16}
    assert traffic["burst_gap_s"] == 0.01


def test_nothing_the_benchmark_had_is_edited():
    """Against PR 34's parent commit (``test_benchmark._manifest_since``):
    every end-to-end entry it had is there unchanged, in its place, but for
    the cell's name appended to the two latency metrics' ``workloads``."""
    old, new = _manifest_since(PARENT)
    for was, now in zip(old["end_to_end"], new["end_to_end"], strict=True):
        if "workloads" in was:
            have = now["workloads"]
            assert have[:len(was["workloads"])] == was["workloads"]
            assert CELL in have[len(was["workloads"]):]
            assert dict(now, workloads=was["workloads"]) == was
        else:
            assert now == was


@pytest.mark.parametrize("name", METRICS)
def test_metric_is_silent_on_the_parents_program(name):
    """On a program without the new series (the parent's worker: no state
    bytes counter, no joins histogram; no trace) the new readers return
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


def test_state_live_share_and_tick_joins_read_their_series():
    def read(name, before, after):
        definition = _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                        name + ".json"))
        reader = importlib.import_module(
            "benchmark.readers." + definition["reader"])
        return reader.read(definition, {"prom_before": prom.parse(before),
                                        "prom_after": prom.parse(after)})
    assert read(
        "state_live_share",
        'ai4e_decode_state_bytes_total{model="lm",kind="moved"} 1000\n'
        'ai4e_decode_state_bytes_total{model="lm",kind="live"} 100\n',
        'ai4e_decode_state_bytes_total{model="lm",kind="moved"} 3000\n'
        'ai4e_decode_state_bytes_total{model="lm",kind="live"} 1000\n'
    ) == pytest.approx(45)
    assert read(
        "tick_joins.burstchat",
        'ai4e_decode_tick_joins_sum{model="lm"} 10\n'
        'ai4e_decode_tick_joins_count{model="lm"} 8\n',
        'ai4e_decode_tick_joins_sum{model="lm"} 31\n'
        'ai4e_decode_tick_joins_count{model="lm"} 18\n') == pytest.approx(2.1)


def test_roofline_counts_the_live_slots_states():
    """``step_roofline_live`` hands the mean live slots of the window to the
    family's ``ops_and_bytes``: fewer live slots, fewer least bytes, a lower
    share of the same device time — never over 100 % at the time a step that
    moves every slot's state can take."""
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", "granite_step_roofline.json")),
        name="granite_step_roofline")
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))

    def share(live):
        after = prom.parse(
            'ai4e_decode_step_seconds_count{phase="decode"} 100\n'
            f'ai4e_decode_step_active_slots_sum {100 * live}\n'
            'ai4e_decode_step_active_slots_count 100\n')
        ctx = {"prom_before": {}, "prom_after": after, "config": config,
               "gen": {"requests": [{"ok": True, "prompt_len": 150,
                                     "max_new_tokens": 100}]},
               "trace": {"modules": {"jit_step": {"seconds": 2.4,
                                                  "calls": 100}}},
               "peaks": {"flops_per_s": {"bf16": 197e12},
                         "hbm_bytes_per_s": 819e9}, "notes": {}}
        value = reader.read(definition, ctx)
        assert ctx["notes"]["granite_step_roofline"]["bound"] == "memory"
        return value

    from benchmark.references import granite_hybrid
    per_slot = granite_hybrid.state_bytes_per_slot(
        granite_hybrid._model_spec(config))
    assert share(64) - share(32) == pytest.approx(
        100 * 2 * 32 * per_slot / 819e9 * 100 / 2.4)
    assert 40 < share(32) < share(64) < 100


def test_configuration_holds_every_published_number():
    """Every key of the catalog row's ``config`` under the same key with the
    same value, but the cache length; the models spec the worker is given
    agrees with them."""
    config = _load(os.path.join(ROOT, "benchmark", "configs",
                                CONFIG + ".json"))
    kinds = ["attention" if i in (5, 15, 25, 35) else "mamba"
             for i in range(40)]
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 8192, "layer_types": kinds, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"max_position_embeddings"}
    assert set(config["reduced"]) == differs | {"weights"}
    spec = config["models"]["models"][0]
    assert spec["family"] == config["family"] == config["reference"][
        "family"] == "granite-hybrid"
    assert spec["attention_layers"] == [
        i for i, kind in enumerate(config["layer_types"])
        if kind == "attention"]
    assert (spec["dim"], spec["depth"], spec["heads"], spec["kv_heads"],
            spec["mlp_dim"], spec["ssm_heads"], spec["ssm_head_dim"],
            spec["ssm_state"], spec["ssm_groups"], spec["conv"],
            spec["vocab_size"], spec["rms_eps"]) == (
        config["hidden_size"], config["num_hidden_layers"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["shared_intermediate_size"], config["mamba_n_heads"],
        config["mamba_d_head"], config["mamba_d_state"],
        config["mamba_n_groups"], config["mamba_d_conv"],
        config["vocab_size"], config["rms_norm_eps"])
    assert spec["head_dim"] * spec["heads"] == config["hidden_size"]
    assert spec["ssm_heads"] * spec["ssm_head_dim"] == (
        config["mamba_expand"] * config["hidden_size"])
    for key in ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling"):
        assert spec[key] == config[key]
    assert spec["vocab_size"] == config["derived"]["vocab_size"]
    assert spec["max_len"] == config["max_position_embeddings"] == int(
        config["worker_env"]["AI4E_RUNTIME_KV_MAX_LEN"])
    assert int(config["worker_env"]["AI4E_RUNTIME_KV_SLOTS"]) == config[
        "derived"]["kv_slots"] == 64
    assert spec["maximum_concurrent_requests"] == config["routes"][0][
        "concurrency"] == 64 + int(
        config["worker_env"]["AI4E_RUNTIME_DECODE_MAX_PENDING"])
    # the longest stream of the mix fits the cache
    traffic = _load(os.path.join(ROOT, "benchmark", "traffic",
                                 "burstchat.json"))
    assert traffic["prompt_len"]["hi"] + traffic["max_new_tokens"][
        "hi"] < spec["max_len"]


def test_every_seed_offers_the_same_bursts_in_another_order():
    committed = _load(os.path.join(ROOT, "benchmark", "traffic",
                                   "burstchat.json"))
    # The committed mix starts EVERY seed at one point of the cycle (PR 42:
    # since PR 35 the starting point itself changed the work); without the
    # key ``--seed`` chooses the point, which is what the rest looks at.
    fixed = burst_loop.schedule(committed, 51.0, seed=1)
    assert fixed == burst_loop.schedule(committed, 51.0, seed=2 ** 31 + 5)
    assert next(x["epoch"] for x in fixed if x["in_window"]) == committed[
        "rotation"] == 60
    traffic = {k: v for k, v in committed.items() if k != "rotation"}
    assert fixed == burst_loop.schedule(traffic, 51.0, seed=60)
    a = burst_loop.schedule(traffic, 51.0, seed=1)
    b = burst_loop.schedule(traffic, 51.0, seed=2 ** 31 + 5)
    assert a == burst_loop.schedule(traffic, 51.0, seed=1)
    assert [x["counter"] for x in a] == list(range(len(a)))
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
    ramp = [x for x in a if not x["in_window"]]
    assert ramp == [x for x in b if not x["in_window"]]
    win_a, win_b = ([x for x in s if x["in_window"]] for s in (a, b))
    assert all(traffic["ramp_s"] < x["due"] < traffic["ramp_s"] + 51.0
               for x in win_a)

    def clumps(win):
        out = {}
        for x in win:
            out.setdefault(x["epoch"], []).append(
                (x["prompt_len"], x["max_new_tokens"]))
        return out

    # the same clumps with the same requests, whatever the seed
    assert clumps(win_a) == clumps(win_b)
    sizes = sorted(map(len, clumps(win_a).values()))
    assert sizes[0] >= 1 and sizes[-1] <= 16
    assert sizes == sorted(burst_loop.burst_sizes(traffic, len(sizes)))
    mean = burst_loop.mean_burst(traffic)
    assert 5.0 < mean < 5.6
    # the mean rate is the mix's, to within half a clump a phase
    assert abs(len(win_a) - traffic["rate_per_s"] * 51.0) <= mean
    # rotated: seed s opens the window with epoch s % E
    first = sorted(clumps(win_a))          # epochs 0 .. E-1
    k = (2 ** 31 + 5) % len(first)
    one = 1 % len(first)
    starts_a = {x["epoch"]: x["due"] for x in reversed(win_a)}
    starts_b = {x["epoch"]: x["due"] for x in reversed(win_b)}
    assert min(starts_a, key=starts_a.get) == one
    assert min(starts_b, key=starts_b.get) == k
    # within a clump, requests are burst_gap_s apart
    by_epoch = {}
    for x in win_a:
        by_epoch.setdefault(x["epoch"], []).append(x["due"])
    for dues in by_epoch.values():
        gaps = np.diff(sorted(dues))
        assert np.allclose(gaps, traffic["burst_gap_s"])
    # the lengths are gpt2m.chat's multiset at this count
    from benchmark.lib import stats
    p = traffic["max_new_tokens"]
    assert sorted(x["max_new_tokens"] for x in win_a) == sorted(
        stats.lognormal_lengths(len(win_a), p["median"], p["sigma"], p["lo"],
                                p["hi"]))


def test_reference_against_the_decode_path_and_a_fault(monkeypatch):
    """Tokens the decode path serves pass the check; the same stream with
    one token replaced by an unlikely id does not."""
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    from benchmark.lib.payloads import PromptPayloads
    from benchmark.references import granite_hybrid
    spec = {"family": "granite-hybrid", "vocab_size": 64, "max_len": 48,
            "dim": 64, "depth": 4, "attention_layers": [2], "heads": 8,
            "kv_heads": 2, "head_dim": 16, "mlp_dim": 96, "ssm_heads": 4,
            "ssm_head_dim": 16, "ssm_state": 16, "chunk": 8}
    config = {"models": {"models": [spec]}}
    lm = build_lm_servable(**spec)
    backend = PagedDecodeRuntime(lm, slots=2, prompt_buckets=(16,))
    payloads = PromptPayloads(5, spec["vocab_size"])
    prompt = payloads.prompt(0, 9)
    tokens = [backend.prefill_into(0, prompt)]
    for i in range(11):
        tokens.append(backend.step([tokens[-1], 0], [len(prompt) + i, 0],
                                   [True, False])[0])
    state = granite_hybrid.prepare(config, {"seed": 5})
    job = {"counter": 0, "prompt_len": 9, "result": {"tokens": tokens}}
    verdict = granite_hybrid.check(state, [job])
    assert verdict["ok"], verdict
    assert verdict["share_beyond"] <= verdict["limit_share"]
    # the second limit alone: every token counted as beyond its margin
    monkeypatch.setattr(granite_hybrid, "SHARE_MARGIN", -1.0)
    crowded = granite_hybrid.check(state, [job])
    assert not crowded["ok"] and not crowded["bad"]
    assert crowded["share_beyond"] == 1.0
    monkeypatch.undo()
    logits = granite_hybrid.forward(state["raw"], spec, prompt + tokens[:5])
    bad = list(tokens)
    bad[5] = int(np.argmin(logits[-1]))
    # at this size the logits deviate by 0.08: the worst id lies ~0.3 under
    monkeypatch.setattr(granite_hybrid, "LOGIT_MARGIN", 0.1)
    assert not granite_hybrid.check(
        state, [dict(job, result={"tokens": bad})])["ok"]
