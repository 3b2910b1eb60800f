"""The benchmark's own tests. Not part of tier-1 (``tests/``): run by hand,

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The rehearsals start real processes on the CPU at a cut size (~25 s each):
the cells of ``BENCHMARK.json`` and of ``benchmark/candidates.json``, their
configurations and mixes cut to CPU size HERE, at test time (``CPU_CUT``), so
that no second copy of the manifest or of a configuration is kept in step by
hand.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.generators import closed_loop, open_loop  # noqa: E402
from benchmark.lib import e2e, prom, stats, xplane  # noqa: E402


# -- percentile and due-time arithmetic ---------------------------------------

def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4     # a tail is a sample
    assert stats.beyond(values, 95) == 5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_request_stays_in_the_tail():
    ok = {"ok": True, "latency_s": 1.0, "result": {"tokens": [1] * 10}}
    failed = {"ok": False, "latency_s": 30.0, "result": None,
              "max_new_tokens": 10}
    gen = {"requests": [ok] * 18 + [failed] * 2, "window_s": 10.0}
    p95 = e2e.percentile({"q": 95, "field": "latency_s", "scale": 1000.0}, gen)
    assert p95 == 30000.0
    per_token = e2e.percentile(
        {"q": 95, "field": "latency_per_token_s", "scale": 1000.0}, gen)
    assert per_token == 3000.0
    assert e2e.rate({}, gen) == 1.8


def test_every_seed_offers_the_same_work_in_another_order():
    traffic = {"rate_per_s": 5.0, "ramp_s": 2.0,
               "prompt_len": {"median": 128, "sigma": 0.8, "lo": 16, "hi": 512},
               "max_new_tokens": {"median": 96, "sigma": 0.7, "lo": 16,
                                  "hi": 256}}
    a = open_loop.schedule(traffic, 20.0, seed=1)
    b = open_loop.schedule(traffic, 20.0, seed=2 ** 31 + 5)
    assert a == open_loop.schedule(traffic, 20.0, seed=1)
    assert [x["prompt_len"] for x in a] != [x["prompt_len"] for x in b]
    # the same ramp, and the window's arrivals rotated: same neighbours
    assert a[:10] == b[:10]
    pairs = [(x["prompt_len"], x["max_new_tokens"]) for x in a[10:]]
    other = [(x["prompt_len"], x["max_new_tokens"]) for x in b[10:]]
    k = (2 ** 31 + 5) % 100 - 1
    assert other == pairs[k:] + pairs[:k]
    for key in ("prompt_len", "max_new_tokens"):
        assert sorted(x[key] for x in a) == sorted(x[key] for x in b)
    win = [x for x in a if x["in_window"]]
    assert len(win) == 100 and len(a) == 110
    assert all(2.0 < x["due"] < 22.0 for x in win)
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
    gaps = np.diff([2.0] + [x["due"] for x in win])
    assert math.isclose(gaps.mean(), 0.2, rel_tol=0.02)   # the fixed rate
    assert all(x["prompt_len"] + x["max_new_tokens"] <= 1024 for x in a)
    # a mix that gives ``rotation`` starts every seed at that point
    fixed = dict(traffic, rotation=7)
    assert open_loop.schedule(fixed, 20.0, seed=1) == open_loop.schedule(
        fixed, 20.0, seed=2 ** 31 + 5) == open_loop.schedule(
        traffic, 20.0, seed=7)


def test_prom_parse_and_delta():
    before = prom.parse('# HELP x\nh_sum{phase="a",model="m"} 1.5\n'
                        'h_count{phase="a",model="m"} 3\nh_count{phase="b"} 9\n')
    after = prom.parse('h_sum{phase="a",model="m"} 4.5\n'
                       'h_count{phase="a",model="m"} 5\nh_count{phase="b"} 9\n')
    assert prom.delta(before, after, "h_sum", {"phase": "a"}) == 3.0
    assert prom.delta(before, after, "h_count", {"phase": "a"}) == 2.0
    assert prom.delta(before, after, "h_count") == 2.0
    assert prom.delta(before, after, "absent") == 0.0


def test_closed_loop_counts_a_task_that_never_completes():
    """A task submitted inside the window that times out, and one that never
    ends at all, are attempted and failed; tasks that merely end after the
    window are neither."""
    import asyncio
    import time

    state = {"t0": None, "lost": 0}

    class Payloads:
        content_type = "x"

        def body(self, counter):
            return str(counter).encode()

    class Ctx:
        traffic = {"path": "/p", "outstanding": 4, "ramp_s": 0.2,
                   "task_timeout_s": 0.5}
        cp_base, seed, seconds, reference_sample = "", 1, 0.6, 2
        now = staticmethod(time.monotonic)

        def payloads(self):
            return Payloads()

        def valid(self, result, arrival=None):
            return result == {"ok": 1}

        def reference_eligible(self, record):
            return True

        async def window_start(self):
            state["t0"] = time.monotonic()
            return state["t0"]

        async def window_end(self):
            pass

        async def ledgers(self, sess, task_ids):
            return []

    async def fake_task(sess, cp_base, path, body, content_type, deadline):
        out = {"ok": True, "task_id": body.decode(), "status": "completed",
               "result": {"ok": 1}, "error": None}
        if state["t0"] is not None and state["lost"] < 2:
            state["lost"] += 1
            if state["lost"] == 1:
                await asyncio.sleep(3600)        # never ends
            await asyncio.sleep(max(0.0, deadline - time.monotonic()))
            return dict(out, ok=False, result=None, error="timed out")
        await asyncio.sleep(0.05)
        return out

    real = closed_loop.client.async_task
    closed_loop.client.async_task = fake_task
    try:
        gen = asyncio.run(closed_loop.run(Ctx()))
    finally:
        closed_loop.client.async_task = real
    errors = sorted(r["error"] for r in gen["requests"] if not r["ok"])
    assert gen["failed"] == 2 and len(errors) == 2
    assert errors[0].startswith("not ended") and errors[1] == "timed out"
    completed = gen["attempted"] - gen["failed"]
    assert 10 <= completed <= 2 * 13 + 2         # two slots go on at 20/s
    assert all(state["t0"] <= r["end"] <= state["t0"] + 0.6 + 0.01
               for r in gen["requests"] if r["ok"])
    assert e2e.rate({}, gen) == completed / gen["window_s"]


# -- trace reduction -------------------------------------------------------------

def test_reduce_hand_made_planes():
    plane = {"name": "/device:TPU:0", "lines": {
        "XLA Ops": [("a", 0, 10), ("b", 5, 10), ("a", 30, 10)],
        "XLA Modules": [("jit_f(123)", 0, 15), ("jit_f(123)", 30, 10)]}}
    other = {"name": "/device:TPU:1", "lines": {
        "XLA Ops": [("a", 0, 20)], "XLA Modules": [("jit_f(123)", 0, 20)]}}
    out = xplane.reduce_planes([plane, other])
    assert out["devices"] == 2
    assert math.isclose(out["per_device_busy_s"]["/device:TPU:0"], 25e-9)
    assert math.isclose(out["busy_s"], (25e-9 + 20e-9) / 2)
    assert math.isclose(out["window_s"], 40e-9)
    assert out["modules"]["jit_f"]["calls"] == 1.5
    assert out["device_ops"][0][0] == "a"
    assert math.isclose(out["idle_gaps"][0]["seconds"], 15e-9)
    assert xplane.reduce_planes([])["busy_s"] == 0.0


def test_reduce_recorded_trace():
    """Three calls of ``tiny_step`` recorded on the chip
    (``record_tiny_trace.py``)."""
    path = os.path.join(HERE, "data", "tiny.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in tests/data")
    out = xplane.reduce_planes(xplane.load_planes(path))
    assert out["devices"] == 1
    assert out["modules"]["jit_tiny_step"]["calls"] == 3
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0] == "%fusion bf16[]"
    gaps = [g["seconds"] for g in out["idle_gaps"]]
    assert gaps[0] > 5e-3 and gaps[1] > 5e-3 and gaps[2] < 1e-6
    clipped = xplane.reduce_planes(xplane.load_planes(path),
                                   (45_000_000, 60_000_000))
    assert clipped["modules"]["jit_tiny_step"]["calls"] == 1
    assert clipped["window_s"] == 0.015


# -- references --------------------------------------------------------------------

def _unet_config(tile=16, widths=(4, 8), classes=3):
    return {"models": {"models": [{"family": "unet", "tile": tile,
                                   "widths": list(widths),
                                   "num_classes": classes}]}}


def test_unet_ops_by_hand():
    from benchmark.references import unet
    # tile 4, widths [2]: two 3x3 convs (3->2, 2->2) and the 1x1 head (2->3).
    flops, nbytes = unet.ops_and_bytes(_unet_config(4, (2,), 3), rows=2)
    convs = 2 * 16 * 9 * (3 * 2 + 2 * 2) + 2 * 16 * 2 * 3
    params = 9 * (3 * 2 + 2 * 2) + 4 * 2 + 2 * 3 + 3
    assert flops == 2 * convs
    assert nbytes == 2 * (4 * 4 * 3 + 4 * 3) + 4 * params


def test_unet_reference_pieces_and_program_agreement():
    import jax
    import jax.numpy as jnp
    from ai4e_tpu.models.unet import UNet
    from benchmark.references import unet
    spec = _unet_config()["models"]["models"][0]
    model = UNet(num_classes=3, widths=(4, 8), dtype=jnp.float32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 16, 16, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    params = jax.tree.map(np.asarray, variables["params"])
    want = np.asarray(model.apply(variables, x))
    got = np.asarray(unet._forward(params, x, spec["widths"]))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # by hand: a histogram counts every pixel once
    tile = (np.asarray(x[0]) * 255).astype(np.uint8)
    hist = unet.histogram(params, tile, spec)
    assert sum(hist.values()) == 256
    assert unet.pixels_moved({"0": 10, "1": 6}, {"0": 7, "1": 8, "2": 1}) == 3


def _lm_state(dim=32, depth=2, heads=2, vocab=64, max_len=48):
    from benchmark.references import seqformer_lm as ref
    config = {"models": {"models": [{
        "family": "seqformer-lm", "vocab_size": vocab, "max_len": max_len,
        "dim": dim, "depth": depth, "heads": heads}]}}
    return ref, ref.prepare(config, {"seed": 5, "vocab_size": vocab})


def test_lm_reference_against_the_decode_path_and_a_fault():
    """Prefill + decode through the program's cache agree with the
    reference's full forward pass; a wrong token is caught."""
    import jax
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    ref, state = _lm_state()
    lm = build_lm_servable(vocab_size=64, max_len=48, dim=32, depth=2, heads=2)
    backend = PagedDecodeRuntime(lm, slots=2, prompt_buckets=(16,))
    prompt = [3, 9, 27, 17, 5]
    tokens = [backend.prefill_into(0, prompt)]
    for i in range(7):
        out = backend.step([tokens[-1], 0], [len(prompt) + i, 0],
                           [True, False])
        tokens.append(out[0])
    m = ref.margins(state, prompt, tokens)
    assert float(m.max()) <= 1e-4, m
    wrong = list(tokens)
    wrong[3] = (wrong[3] + 1) % 64
    assert float(ref.margins(state, prompt, wrong).max()) > 1e-3
    del jax


def test_lm_bytes_by_hand():
    from benchmark.references import seqformer_lm as ref
    spec = {"family": "seqformer-lm", "vocab_size": 10, "max_len": 8,
            "dim": 4, "depth": 2, "heads": 2}
    config = {"models": {"models": [spec]}}
    per_block = 3 * 16 + 16 + 8 * 16 + 16 + 4 + 16
    assert ref.weight_bytes(spec) == 4 * (40 + 32 + 2 * per_block + 8)
    assert ref.kv_bytes_per_token(spec) == 2 * 2 * 4 * 4
    flops, nbytes = ref.ops_and_bytes(config, slots=3, live_tokens=20.0)
    assert flops == 2.0 * (2 * 12 * 16 + 40) * 3 + 4.0 * 4 * 2 * 20.0
    assert nbytes == ref.weight_bytes(spec) + 64 * 23


# -- rehearsals of run.py on the CPU cut ---------------------------------------------

def _merge(target: dict, cut: dict) -> None:
    for key, value in cut.items():
        if isinstance(value, dict) and isinstance(target.get(key), dict):
            _merge(target[key], value)
        else:
            target[key] = value


# What a rehearsal changes in a configuration, by family, and in a mix, by
# name: the platform, the sizes and the rates — nothing else.
CPU_CUT = {
    "unet": {"model": {"tile": 64, "widths": [8, 16], "buckets": [1, 4, 16]},
             "derived": {"tile": 64, "row_bytes": 64 * 64 * 3,
                         "top_bucket": 16},
             "reference": {"sample": 6}},
    "seqformer-lm": {
        "model": {"vocab_size": 256, "max_len": 128, "dim": 64, "depth": 2,
                  "heads": 4},
        "worker_env": {"AI4E_RUNTIME_KV_SLOTS": "8",
                       "AI4E_RUNTIME_KV_MAX_LEN": "128",
                       "AI4E_RUNTIME_DECODE_PROMPT_BUCKETS": "16,32"},
        "derived": {"vocab_size": 256, "kv_slots": 8,
                    "reference_max_len": 128}},
}
TRAFFIC_CUT = {
    "flood": {"outstanding": 16, "ramp_s": 2.0, "task_timeout_s": 30.0,
              "ledger_sample": 16},
    "sync": {"rate_per_s": 20.0, "ramp_s": 1.0},
    "chat": {"rate_per_s": 6.0, "ramp_s": 2.0, "drain_cap_s": 20.0,
             "prompt_len": {"median": 12, "sigma": 0.6, "lo": 4, "hi": 30},
             "max_new_tokens": {"median": 10, "sigma": 0.5, "lo": 4,
                                "hi": 24}},
}
MANIFESTS = ("BENCHMARK.json", os.path.join("benchmark", "candidates.json"))


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


# The two bounds PR 42's re-check loosened after the check refused them as
# too tight (``PERF.md`` section 2): an end-to-end entry of an older parent is
# held to the manifest with the bound it has now.
LOOSENED = {"token_latency_p95_ms": (0.045, 0.1),
            "gen_latency_p95_ms": (0.02, 0.06)}


def _as_it_stands(was: dict) -> dict:
    before, now = LOOSENED.get(was["name"], (None, None))
    return dict(was, bound=now) if was.get("bound") == before else was


def _manifest_since(parent: str) -> tuple[dict, dict]:
    """``BENCHMARK.json`` at ``parent`` and now, after holding what no PR
    since may have touched — a ``benchmark`` PR's harness, metric files and
    ``per_layer`` list apart (PR 42 folded those): every configuration, mix,
    reference and end-to-end definition the parent had has the same bytes,
    and the manifest's ``command``, ``paths``, ``run_seconds``, ``configs``
    and ``workloads`` differ only by entries appended. Skips where the
    parent commit is not in reach."""
    def git(*args):
        return subprocess.run(["git", "-C", ROOT, *args], capture_output=True,
                              text=True)
    if git("cat-file", "-e", parent).returncode:
        pytest.skip("the parent commit is not in this checkout")
    kept = [os.path.join("benchmark", d)
            for d in ("configs", "traffic", "references", "end_to_end")]
    changed = git("diff", "--name-status", parent, "--",
                  *kept).stdout.split("\n")
    edited = [line for line in changed if line and not line.startswith("A")]
    assert edited == [], edited
    old = json.loads(git("show", parent + ":BENCHMARK.json").stdout)
    new = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key], key
    old["end_to_end"] = [_as_it_stands(m) for m in old["end_to_end"]]
    return old, new


def _copy_of_the_tree(root: str) -> None:
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("ai4e_tpu", "clients"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))


@pytest.fixture(scope="module")
def cpu_root(tmp_path_factory):
    """A scratch checkout whose configurations and mixes are the real ones
    cut to CPU size, with one manifest (``manifest.cpu.json``) that holds the
    benchmark's cells and the candidates."""
    root = str(tmp_path_factory.mktemp("cpu_checkout"))
    _copy_of_the_tree(root)
    merged = None
    for name in MANIFESTS:
        manifest = _load(os.path.join(ROOT, name))
        manifest.pop("what", None)
        if merged is None:
            merged = manifest
            continue
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in merged[key]}
            merged[key] += [e for e in manifest[key] if e["name"] not in have]
    for entry in merged["configs"]:
        path = os.path.join(root, entry["file"])
        config = _load(path)
        cut = CPU_CUT[config["family"]]
        config["platform"] = "cpu"
        if config["chips"] > 1:
            config.setdefault("worker_env", {})["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={config['chips']}")
        _merge(config["models"]["models"][0], cut["model"])
        _merge(config, {k: v for k, v in cut.items() if k != "model"})
        _dump(config, path)
    for mix, cut in TRAFFIC_CUT.items():
        path = os.path.join(root, "benchmark", "traffic", mix + ".json")
        traffic = _load(path)
        _merge(traffic, cut)
        _dump(traffic, path)
    _dump(merged, os.path.join(root, "manifest.cpu.json"))
    return root


def _run(root: str, manifest: str, workload: str, trace: int,
         seconds: float = 4.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2 ** 31 + 17), "--seconds",
         str(seconds), "--trace", str(trace), "--manifest", manifest],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    return proc


REHEARSALS = [
    ("gpt2m.chat", 0), ("gpt2m.chat", 1), ("landcover.flood", 0),
    ("landcover.flood", 1), ("landcover2x2.flood", 0), ("landcover.sync", 0),
    ("landcover.sync", 1)]


@pytest.mark.parametrize("workload,trace", REHEARSALS)
def test_rehearsal(cpu_root, workload, trace):
    manifest_path = os.path.join(cpu_root, "manifest.cpu.json")
    proc = _run(cpu_root, manifest_path, workload, trace)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # what was compared comes last, each number beside its limit, and ends
    # standard error too
    assert list(line)[-1] == "compared" and line["compared"]["failed"] == [0, 0]
    assert all(len(pair) == 2 for pair in line["compared"].values())
    assert proc.stderr.strip().splitlines()[-1].startswith("compared: ")
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # never a device result: no number under a device metric's name
    assert line["device"]["platform"] == "cpu"
    assert line["rehearsal"] is True and line["metrics"] == {}
    manifest = _load(manifest_path)
    group = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in manifest[group]
            if workload in m.get("workloads", [workload])}
    reported = set(line["rehearsal_metrics"])
    assert reported <= mine
    if not trace:
        assert reported == mine
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert len(reported) >= 3      # the trace-borne ones need a chip


def test_every_cell_is_rehearsed():
    cells = {w["name"] for name in MANIFESTS
             for w in _load(os.path.join(ROOT, name))["workloads"]}
    assert cells == {workload for workload, _ in REHEARSALS}


def test_no_accelerator_no_result():
    """The real manifest pins the worker to ``tpu``: here, without a chip,
    the run must exit non-zero and print no result line."""
    proc = _run(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "gpt2m.chat", 0)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in
                   proc.stdout.strip().splitlines())


def test_a_later_pr_adds_a_cell_with_files_only(cpu_root, tmp_path):
    """One configuration file, one traffic file, one layer-metric file and
    one ``workloads`` entry (named in the metrics it reports) — no existing
    file's bytes change — and ``run.py`` finds and runs them."""
    root = str(tmp_path / "checkout")
    shutil.copytree(cpu_root, root, symlinks=True,
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  "chiprun_out"))
    bench = os.path.join(root, "benchmark")
    before = {}
    for base, _, files in os.walk(bench):
        for f in files:
            path = os.path.join(base, f)
            before[path] = open(path, "rb").read()
    config = _load(os.path.join(bench, "configs", "landcover.json"))
    config["name"] = "landcover-small"
    config["models"]["models"][0]["buckets"] = [1, 8]
    config["derived"]["top_bucket"] = 8
    _dump(config, os.path.join(bench, "configs", "landcover-small.json"))
    traffic = _load(os.path.join(bench, "traffic", "flood.json"))
    traffic.update(outstanding=6, ramp_s=1.0, ledger_sample=4)
    _dump(traffic, os.path.join(bench, "traffic", "trickle.json"))
    _dump({"reader": "prom_mean", "metric": "ai4e_batch_exec_seconds",
           "scale": 1000.0},
          os.path.join(bench, "layer_metrics", "batch_exec_ms.trickle.json"))
    manifest = _load(os.path.join(root, "manifest.cpu.json"))
    manifest["configs"].append({
        "name": "landcover-small", "source": "test", "reduced": [],
        "file": "benchmark/configs/landcover-small.json", "why": "test"})
    manifest["workloads"].append({
        "name": "landcover-small.trickle", "config": "landcover-small",
        "traffic": "trickle", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "tiles_per_s":
            m["workloads"].append("landcover-small.trickle")
    manifest["per_layer"].append({
        "name": "batch_exec_ms.trickle", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "device boundary",
        "moves": "tiles_per_s", "workloads": ["landcover-small.trickle"]})
    new_manifest = os.path.join(root, "manifest.json")
    _dump(manifest, new_manifest)
    for trace, want in ((0, "tiles_per_s"), (1, "batch_exec_ms.trickle")):
        proc = _run(root, new_manifest, "landcover-small.trickle", trace, 3.0)
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["correct"] and want in line["rehearsal_metrics"]
    for path, data in before.items():
        assert open(path, "rb").read() == data, path
