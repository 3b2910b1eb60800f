#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Drives the *deployed* wiring once, at the full width of the land-cover model
the repo deploys, through the entry points a user would call:

    this process (never imports JAX)
      ├─ python -m ai4e_tpu control-plane   (never imports JAX)
      ├─ python -m ai4e_tpu worker          (the one process that holds the chip)
      └─ python -m ai4e_tpu.ops.pallas.validate   (after the worker has exited)

Specs are derived from ``deploy/specs/{routes,models}.json``: the ``landcover``
entry exactly as deployed (UNet 64-128-256-512, tile 256, 4 classes, buckets
1/16/64, rgb8 wire, sync and async routes) with ``checkpoint`` dropped, so the
weights are the family's seeded init, plus one ``seqformer-lm`` entry at the
runtime's default preset. Requests go through the gateway over HTTP with
``clients/python/ai4e_client.py``.

Phases — any failure exits non-zero and prints no result line:

1. start: control plane and worker come up; the worker's own device report
   (``GET {prefix}/models``) must say the platform that was asked for;
2. landcover: concurrent async tiles cut the two largest buckets, plus one
   sync call — every task completes, every histogram sums to tile², the same
   tile gives the same histogram whichever bucket it rode, no hop ledger
   carries a ``compile`` stamp;
3. decode: concurrent streams across several prefill buckets through the same
   worker — each ends ``completed - N tokens``, the same prompt gives the same
   tokens;
4. stop: SIGTERM, both processes exit 0; the worker log shows both serving
   kernels lowered the expected way and no native library loaded;
5. kernels: compiled Pallas kernels against their oracles, ``all_ok``.

``--cpu-cut`` runs the same plumbing on the CPU at a cut size (tests,
debugging — never a device result). Without it the worker is pinned to
``tpu`` and a machine without a free chip fails at start-up.

Last line of stdout on success:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "clients", "python")]

from ai4e_client import AI4EClient  # noqa: E402
from ai4e_tpu.config import compile_cache_dir  # noqa: E402

WORK = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# What --cpu-cut overrides in the deployed landcover entry: XLA:CPU runs the
# full-width UNet at ~0.5 tiles/s, so the plumbing check shrinks the model.
CPU_CUT = {"tile": 64, "widths": [8, 16], "buckets": [1, 4, 16]}
# Batch accumulation window for the smoke's worker: long enough that a burst
# sent at once is cut as one batch, so which bucket a burst rides is decided
# by its size and not by how fast the fabric delivers it.
MAX_WAIT_MS = 250
STREAM_TOKENS = 16
START_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(msg, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_text(url: str) -> str:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode()


def get_json(url: str):
    return json.loads(get_text(url))


def metric_samples(text: str, name: str) -> list[tuple[dict, float]]:
    """``(labels, value)`` of every sample of ``name`` in a Prometheus text
    exposition."""
    out = []
    for line in text.splitlines():
        m = re.fullmatch(re.escape(name) + r"(?:\{(.*)\})? (\S+)", line)
        if m:
            labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1) or ""))
            out.append((labels, float(m.group(2))))
    return out


def write_specs(cpu_cut: bool, cp_base: str, wk_base: str) -> tuple[str, str]:
    with open(os.path.join(ROOT, "deploy", "specs", "models.json")) as f:
        deployed_models = json.load(f)
    with open(os.path.join(ROOT, "deploy", "specs", "routes.json")) as f:
        deployed_routes = json.load(f)

    landcover = dict(next(m for m in deployed_models["models"]
                          if m["name"] == "landcover"))
    landcover.pop("checkpoint")  # seeded init: the smoke reads no weights
    if cpu_cut:
        landcover.update(CPU_CUT)
    prefix = deployed_models["prefix"]
    models = {"service_name": deployed_models["service_name"],
              "prefix": prefix, "taskstore": cp_base,
              "models": [landcover,
                         # runtime default preset (runtime/kvcache.py
                         # build_lm_servable; AI4E_RUNTIME_KV_* defaults)
                         {"family": "seqformer-lm", "name": "lm"}]}

    def local(api: dict) -> dict:
        path = api["backend"].split("/", 3)[3]
        return dict(api, backend=f"{wk_base}/{path}")

    routes = {"apis": [local(api) for api in deployed_routes["apis"]
                       if api.get("prefix", "").startswith("/v1/landcover/")]
              + [{"prefix": "/v1/lm/generate-async", "mode": "async",
                  "backend": f"{wk_base}/{prefix}/lm-stream-async",
                  "concurrency": 4}]}
    paths = []
    for name, spec in (("routes.json", routes), ("models.json", models)):
        paths.append(os.path.join(WORK, name))
        with open(paths[-1], "w") as f:
            json.dump(spec, f, indent=1)
    return paths[0], paths[1]


class Procs:
    """The processes this script starts; every one is stopped on exit."""

    def __init__(self):
        self.procs: dict[str, subprocess.Popen] = {}
        self.logs: dict[str, str] = {}

    def start(self, name: str, argv: list[str], env: dict) -> None:
        self.logs[name] = os.path.join(WORK, f"{name}.log")
        with open(self.logs[name], "w") as out:
            self.procs[name] = subprocess.Popen(
                [sys.executable, *argv], env=env, cwd=ROOT,
                stdout=out, stderr=subprocess.STDOUT)

    def log_text(self, name: str) -> str:
        with open(self.logs[name], errors="replace") as f:
            return f.read()

    def wait_http(self, name: str, url: str, timeout: float) -> float:
        """Seconds until ``url`` answers; fails as soon as the process is
        gone — a worker that cannot have its platform exits, it does not
        hang."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            rc = self.procs[name].poll()
            if rc is not None:
                raise SystemExit(
                    f"{name} exited with {rc} before serving; its log ends:\n"
                    + self.log_text(name)[-3000:])
            try:
                with urllib.request.urlopen(url, timeout=2):
                    return time.monotonic() - t0
            except OSError:
                time.sleep(0.25)
        raise SystemExit(f"{name} did not answer {url} within {timeout:.0f}s; "
                         "its log ends:\n" + self.log_text(name)[-3000:])

    def terminate(self, name: str, timeout: float = 60.0) -> int:
        proc = self.procs[name]
        proc.send_signal(signal.SIGTERM)
        return proc.wait(timeout=timeout)

    def kill_all(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)


def tile_payload(seed: int, tile: int) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.random.default_rng(seed).integers(
        0, 256, size=(tile, tile, 3), dtype=np.uint8))
    return buf.getvalue()


def pixels_moved(a: dict, b: dict) -> int:
    """Pixels that changed class between two class histograms."""
    return sum(abs(a.get(k, 0) - b.get(k, 0)) for k in set(a) | set(b)) // 2


def landcover_phase(client: AI4EClient, cp_base: str, tile: int,
                    buckets: list[int]) -> dict:
    payloads = [tile_payload(seed, tile) for seed in range(4)]

    def one(seed: int) -> dict:
        task_id = client.submit("/v1/landcover/classify-async",
                                payloads[seed])
        client.wait(task_id, timeout=300.0)
        record = get_json(
            f"{cp_base}/v1/taskmanagement/task/{task_id}?ledger=1")
        events = record["Ledger"]
        if any(ev["e"] == "compile" for ev in events):
            raise SystemExit(f"task {task_id} paid a serving-path compile "
                             f"after warm-up: {events}")
        cut = next(ev["r"] for ev in events if ev["e"] == "batched")
        size, bucket = (int(n) for n in
                        re.fullmatch(r"size (\d+) bucket (\d+)", cut).groups())
        hist = client.result(task_id)["class_histogram"]
        if sum(hist.values()) != tile * tile:
            raise SystemExit(f"task {task_id}: histogram {hist} does not sum "
                             f"to {tile * tile}")
        return {"seed": seed, "bucket": bucket, "size": size, "hist": hist}

    # One task alone rides the smallest bucket; a burst sized between the
    # two largest buckets rides the middle one; a burst just under the
    # worker's 64-request admission cap rides the largest.
    mid, top = buckets[-2], buckets[-1]
    plan = [1, max(2, mid - 4), min(60, top - 4)]
    done: list[dict] = []
    with ThreadPoolExecutor(max_workers=64) as pool:
        for _ in range(3):
            for n in plan:
                done += list(pool.map(one, [i % 4 for i in range(n)]))
            rode = {r["bucket"] for r in done}
            if {mid, top} <= rode:
                break
        else:
            raise SystemExit(f"buckets {mid} and {top} were not both cut "
                             f"after 3 rounds; rode {sorted(rode)}")

    # Same tile → same histogram, whichever bucket it rode. bf16 programs
    # compiled per bucket may round a near-tie pixel differently, so the
    # deviation is measured and bounded (0 on the CPU path).
    first = {seed: next(r["hist"] for r in done if r["seed"] == seed)
             for seed in range(4)}
    worst = max(pixels_moved(r["hist"], first[r["seed"]]) for r in done)
    moved_limit = tile * tile // 100
    if worst > moved_limit:
        raise SystemExit(f"same tile, different bucket: {worst} pixels "
                         f"changed class (limit {moved_limit})")

    sync_hist = client.call_sync("/v1/landcover/classify",
                                 payloads[0])["class_histogram"]
    moved = pixels_moved(sync_hist, first[0])
    if sum(sync_hist.values()) != tile * tile or moved > moved_limit:
        raise SystemExit(f"sync result {sync_hist} disagrees with the async "
                         f"result {first[0]} for the same tile")
    by_bucket: dict[int, int] = {}
    for r in done:
        by_bucket[r["bucket"]] = by_bucket.get(r["bucket"], 0) + 1
    return {"tasks": len(done), "tasks_by_bucket": by_bucket,
            "largest_cut": max(r["size"] for r in done),
            "same_tile_max_pixels_moved": max(worst, moved)}


def decode_phase(client: AI4EClient) -> dict:
    rng = np.random.default_rng(7)
    # Under the default prefill ladder (1, 16, 64, KV length) lengths 3/12
    # pad to 16, 40 to 64, 100 to the KV length; two prompts are sent twice.
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (3, 12, 40, 100)]
    prompts += [prompts[0], prompts[2]]

    def one(prompt: list[int]) -> list[int]:
        task_id = client.submit(
            "/v1/lm/generate-async",
            json.dumps({"prompt": prompt,
                        "max_new_tokens": STREAM_TOKENS}).encode(),
            content_type="application/json")
        record = client.wait(task_id, timeout=300.0)
        if record["Status"] != f"completed - {STREAM_TOKENS} tokens":
            raise SystemExit(f"stream {task_id} ended {record['Status']!r}")
        result = client.result(task_id)
        if result["count"] != STREAM_TOKENS or len(
                result["tokens"]) != STREAM_TOKENS:
            raise SystemExit(f"stream {task_id} returned {result}")
        return result["tokens"]

    with ThreadPoolExecutor(max_workers=len(prompts)) as pool:
        tokens = list(pool.map(one, prompts))
    if tokens[4] != tokens[0] or tokens[5] != tokens[2]:
        raise SystemExit("the same prompt gave different tokens: "
                         f"{tokens[0]} vs {tokens[4]}; {tokens[2]} vs "
                         f"{tokens[5]}")
    return {"streams": len(prompts), "tokens_each": STREAM_TOKENS,
            "prompt_lengths": [len(p) for p in prompts]}


def check_worker_metrics(wk_base: str) -> dict:
    text = get_text(f"{wk_base}/metrics")
    batches = sum(v for labels, v in metric_samples(
        text, "ai4e_batch_size_count") if labels.get("model") == "landcover")
    singles = sum(v for labels, v in metric_samples(
        text, "ai4e_batch_size_bucket")
        if labels.get("model") == "landcover" and labels.get("le") == "1")
    if batches - singles < 1:
        raise SystemExit("ai4e_batch_size shows no batch larger than 1")
    if not sum(v for _, v in metric_samples(
            text, "ai4e_decode_ttft_seconds_count")):
        raise SystemExit("ai4e_decode_ttft_seconds is empty")
    steps = {labels.get("phase"): v for labels, v in metric_samples(
        text, "ai4e_decode_step_seconds_count")}
    if not (steps.get("prefill") and steps.get("decode")):
        raise SystemExit(f"ai4e_decode_step_seconds phases: {steps}")
    return {"batches": int(batches), "batches_larger_than_1":
            int(batches - singles),
            "decode_steps": {k: int(v) for k, v in steps.items()}}


def check_per_device(device: dict, landcover: dict) -> list[str]:
    """More than one device: the largest bucket must have had a shard on
    each of them, by the batch sharding and by each device's own memory
    high-water mark."""
    n = device["device_count"]
    if n == 1:
        return []
    sharding = landcover["batch_sharding"]
    if sharding["devices"] != n:
        raise SystemExit(f"batch sharding spans {sharding['devices']} of "
                         f"{n} devices")
    if "memory" not in device:
        if device["platform"] != "cpu":  # XLA:CPU keeps no such figures
            raise SystemExit("the worker reported no per-device memory")
        return [f"  batch sharding spans all {n} devices"]
    shard_bytes = int(np.prod(sharding["largest_bucket_shard"]))  # uint8
    lines = []
    for mem in device["memory"]:
        if mem["peak_bytes_in_use"] < shard_bytes:
            raise SystemExit(f"device {mem['device']} peaked at "
                             f"{mem['peak_bytes_in_use']} bytes — less than "
                             f"one input shard ({shard_bytes})")
        lines.append(f"  device {mem['device']}: peak "
                     f"{mem['peak_bytes_in_use'] / 2**20:.0f} MiB, now "
                     f"{mem['bytes_in_use'] / 2**20:.0f} MiB")
    return lines


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--cpu-cut", action="store_true",
                        help="run the plumbing on the CPU at a cut size "
                             "(never a device result); refused by default")
    args = parser.parse_args()
    platform = "cpu" if args.cpu_cut else "tpu"

    os.makedirs(WORK, exist_ok=True)
    cache_dir = compile_cache_dir()
    cache_entries = (len([n for n in os.listdir(cache_dir)
                          if n != "ladders.json"])
                     if os.path.isdir(cache_dir) else 0)
    log(f"compile cache: {cache_dir} ({'cold' if not cache_entries else 'warm'}"
        f", {cache_entries} entries; JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")

    cp_port, wk_port = free_port(), free_port()
    cp_base = f"http://127.0.0.1:{cp_port}"
    wk_base = f"http://127.0.0.1:{wk_port}"
    routes_path, models_path = write_specs(args.cpu_cut, cp_base, wk_base)
    env = dict(os.environ,
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = Procs()
    timings: dict[str, float] = {}
    t_all = time.monotonic()
    try:
        # -- 1. start --------------------------------------------------------
        procs.start("control-plane",
                    ["-m", "ai4e_tpu", "control-plane", "--routes",
                     routes_path, "--port", str(cp_port)],
                    dict(env, AI4E_PLATFORM_OBSERVABILITY="1",
                         AI4E_PLATFORM_RETRY_DELAY="0.5"))
        procs.start("worker",
                    ["-m", "ai4e_tpu", "worker", "--models", models_path,
                     "--port", str(wk_port)],
                    dict(env, AI4E_RUNTIME_PLATFORM=platform,
                         AI4E_RUNTIME_DECODE_ENABLE="1",
                         AI4E_RUNTIME_BATCH_MAX_WAIT_MS=str(MAX_WAIT_MS),
                         AI4E_OBSERVABILITY_HOP_LEDGER="1"))
        procs.wait_http("control-plane", f"{cp_base}/healthz", 120.0)
        listing_url = f"{wk_base}/v1/models/models"
        timings["worker_start_to_serving_s"] = procs.wait_http(
            "worker", listing_url, START_TIMEOUT_S)
        listing = get_json(listing_url)
        device = listing["device"]
        log(f"worker reports: platform: {device['platform']}, device_kind: "
            f"{device['device_kind']}, device_count: "
            f"{device['device_count']}, mesh {device['mesh']}, versions "
            f"{device['versions']}")
        if device["platform"] != platform:
            raise SystemExit(f"worker runs on {device['platform']!r}, "
                             f"not {platform!r}")
        # The worker's own account of its start (observability/boot.py).
        scrape = get_text(f"{wk_base}/metrics")
        boot = {labels.get("phase"): v for labels, v in metric_samples(
            scrape, "ai4e_boot_seconds")}
        for label, phase in (("boot_s", "total"),
                             ("batch_warmup_s", "batch_warmup"),
                             ("decode_warmup_s", "warm")):
            if phase not in boot:
                raise SystemExit(f"worker /metrics has no ai4e_boot_seconds"
                                 f"{{phase=\"{phase}\"}}")
            timings[label] = boot[phase]
        # What the backend compiled (a cold cache) or retrieved (a warm one).
        compiled = [v for labels, v in metric_samples(
            scrape, "ai4e_jax_compile_seconds_total")
            if labels.get("when") == "boot"
            and labels.get("stage") in ("backend", "retrieve")]
        if len(compiled) != 2:
            raise SystemExit("worker /metrics has no ai4e_jax_compile_"
                             "seconds_total{stage=\"backend\"|\"retrieve\","
                             "when=\"boot\"}")
        timings["aot_compile_s"] = sum(compiled)
        landcover = next(m for m in listing["models"]
                         if m["name"] == "landcover")

        # -- 2. landcover ----------------------------------------------------
        client = AI4EClient(cp_base, timeout=120.0)
        t0 = time.monotonic()
        lc = landcover_phase(client, cp_base, landcover["input_shape"][0],
                             landcover["batch_buckets"])
        timings["landcover_phase_s"] = time.monotonic() - t0
        log(f"landcover ok: {lc}")

        # -- 3. decode -------------------------------------------------------
        t0 = time.monotonic()
        dec = decode_phase(client)
        timings["decode_phase_s"] = time.monotonic() - t0
        log(f"decode ok: {dec}")

        log(f"worker metrics ok: {check_worker_metrics(wk_base)}")
        after = get_json(listing_url)["device"]
        for line in check_per_device(after, landcover):
            log(line)

        # -- 4. stop ---------------------------------------------------------
        for name in ("worker", "control-plane"):
            rc = procs.terminate(name)
            if rc != 0:
                raise SystemExit(f"{name} exited {rc} on SIGTERM; its log "
                                 f"ends:\n{procs.log_text(name)[-3000:]}")
        wlog = procs.log_text("worker")
        lowering = "interpreter" if args.cpu_cut else "Mosaic"
        for kernel in ("normalize_image", "segmentation_argmax"):
            if f"pallas {kernel}: {lowering}" not in wlog:
                raise SystemExit(f"worker log does not show {kernel} "
                                 f"lowered to {lowering}")
        for name in procs.logs:
            if "ai4e_tpu.native_build" in procs.log_text(name):
                raise SystemExit(f"{name} loaded a native library")
        log(f"stopped: worker and control plane exited 0; kernels lowered "
            f"to {lowering}; no native library loaded")

        # -- 5. kernels (the chip is free again) -----------------------------
        t0 = time.monotonic()
        procs.start("kernels",
                    ["-m", "ai4e_tpu.ops.pallas.validate",
                     *(["--interpret"] if args.cpu_cut else [])],
                    dict(env, JAX_PLATFORMS=platform))
        rc = procs.procs["kernels"].wait(timeout=900)
        timings["kernel_phase_s"] = time.monotonic() - t0
        klog = procs.log_text("kernels")
        if rc != 0:
            raise SystemExit(f"kernel validation exited {rc}:\n{klog[-3000:]}")
        kernels = json.loads(klog.strip().splitlines()[-1])
        if not kernels["all_ok"] or kernels["device"]["platform"] != platform:
            raise SystemExit(f"kernel validation: {kernels}")
        del kernels["device"]
        log(f"kernels ok: {kernels}")
    finally:
        procs.kill_all()

    timings["total_s"] = time.monotonic() - t_all
    log("timings: " + json.dumps({k: round(v, 1) for k, v in timings.items()}))
    if "jax" in sys.modules:
        raise SystemExit("the smoke's parent process imported jax")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
