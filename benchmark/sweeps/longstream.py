#!/usr/bin/env python3
"""Long streams of an LM cell held to its plain reference, outside a run.

A run's own check is a child on the host's CPU that has to answer within
``run.py``'s ``HELPER_TIMEOUT_S``: for ``dots3.longdoc`` that is ONE stream of
at most 3,300 tokens (168-195 s), so the prefills of the longer buckets, the
top rung of the step and a slot reused after a longer sequence are never held
to the reference there. This script holds them to it, in two halves that need
not share a machine:

    chiprun --chips 1 -- python3 benchmark/sweeps/longstream.py serve \
        <workload> <seed> <slot>:<prompt_len>:<new_tokens> ...
        on the chip: the cell's model as the worker builds it (the models
        entry of the configuration, the worker_env's slots and buckets), each
        stream through ``PagedDecodeRuntime`` — ``prefill_into`` its slot, then
        greedy steps through the cache, ONE stream live at a time, in the
        order given (a slot named twice is reused) — and the served ids written
        to ``chiprun_out/longstream/<workload>.<seed>.json`` in the form of a
        run's ``ref_jobs.json`` (prompts from ``PromptPayloads(seed)``, as a
        run makes them; counters 0, 1, ...).

    python3 benchmark/sweeps/longstream.py check <file> [fault ...]
        on a host CPU (minutes a 10k-token stream, ~40 GB of memory at
        12.5k): the cell's reference, ``check`` of one stream at a time by
        the reference file's own limits; then the same under each ``fault``.

Nothing here is part of ``correct``: it is the builder's reading, reported in
``PERF.md``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SERVING_KEYS = ("async_path", "maximum_concurrent_requests", "checkpoint")


def cell_config(workload: str, path: str | None = None) -> dict:
    """The configuration of the manifest's cell, or the file at ``path`` in
    its place (a CPU cut, to rehearse the script)."""
    if path:
        with open(path) as f:
            return json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == workload)
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        return json.load(f)


def serve(workload: str, seed: int, streams: list[tuple[int, int, int]],
          config_path: str | None = None) -> None:
    from ai4e_tpu.runtime.kvcache import (PagedDecodeRuntime,
                                          build_lm_servable)
    from benchmark.lib.payloads import PromptPayloads
    config = cell_config(workload, config_path)
    family = config["reference"]["family"]
    spec = next(m for m in config["models"]["models"]
                if m["family"] == family)
    env = config["worker_env"]
    slots = int(env["AI4E_RUNTIME_KV_SLOTS"])
    spec = {k: v for k, v in spec.items() if k not in SERVING_KEYS}
    spec.setdefault("max_len", int(env["AI4E_RUNTIME_KV_MAX_LEN"]))
    runtime = PagedDecodeRuntime(
        build_lm_servable(**spec), slots=slots,
        prompt_buckets=[int(b) for b in
                        env["AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(",")])
    payloads = PromptPayloads(seed, config["derived"]["vocab_size"])
    jobs = []
    for counter, (slot, length, new) in enumerate(streams):
        prompt = payloads.prompt(counter, length)
        t0 = time.perf_counter()
        tokens = [runtime.prefill_into(slot, prompt)]
        t_first = time.perf_counter() - t0
        active = [s == slot for s in range(slots)]
        bounds = set()
        while len(tokens) < new:
            position = length + len(tokens) - 1
            fed, at = [0] * slots, [0] * slots
            fed[slot], at[slot] = tokens[-1], position
            bounds.add(runtime.bound_for(position))
            tokens.append(int(runtime.step(fed, at, active)[slot]))
        print(f"stream {counter}: slot {slot}, prompt {length} (bucket "
              f"{runtime.bucket_for(length)}), {new} tokens, step rungs "
              f"{sorted(bounds)}; first id after {t_first:.1f}s, all after "
              f"{time.perf_counter() - t0:.1f}s (compiles included)",
              flush=True)
        jobs.append({"counter": counter, "prompt_len": length, "slot": slot,
                     "bucket": runtime.bucket_for(length),
                     "step_bounds": sorted(bounds),
                     "result": {"tokens": tokens, "count": len(tokens)}})
    out = os.path.join(ROOT, "chiprun_out", "longstream")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"{workload}.{seed}.json")
    with open(path, "w") as f:
        json.dump({"workload": workload, "seed": seed, "jobs": jobs}, f)
    print(f"wrote {path}", flush=True)


def check(path: str, faults: list[str],
          config_path: str | None = None) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with open(path) as f:
        served = json.load(f)
    config = cell_config(served["workload"], config_path)
    reference = importlib.import_module(
        "benchmark.references."
        + config["reference"]["family"].replace("-", "_"))
    t0 = time.monotonic()
    state = reference.prepare(config, dict(config["derived"],
                                           seed=served["seed"]))
    print(f"reference prepared in {time.monotonic() - t0:.0f}s", flush=True)
    keep = ("ok", "checked", "tokens_checked", "argmax_agreement",
            "worst_margin", "share_beyond", "beyond", "allowed_beyond")
    # ``check`` keeps its per-token margins to itself: note them as they pass
    # (where along a stream the served ids leave the reference's says whether
    # it is rounding, spread evenly, or a fault from some position on)
    seen, margins = [], reference.margins

    def noted(*args, **kwargs):
        seen.append(margins(*args, **kwargs))
        return seen[-1]

    reference.margins = noted
    for fault in [None, *faults]:
        for jobs in [[job] for job in served["jobs"]]:
            t0 = time.monotonic()
            verdict = reference.check(state, jobs, fault)
            print(json.dumps({
                "fault": fault, "counter": jobs[0]["counter"],
                "prompt_len": jobs[0]["prompt_len"],
                "bucket": jobs[0].get("bucket"),
                "step_bounds": jobs[0].get("step_bounds"),
                "s": round(time.monotonic() - t0),
                **{k: verdict[k] for k in keep if k in verdict},
                "margins": [round(float(m), 3) for m in seen.pop()]}),
                flush=True)


def main(argv: list[str]) -> int:
    config_path = None
    if argv[:1] == ["--config"]:   # a CPU cut's file, to rehearse
        config_path, argv = argv[1], argv[2:]
    if len(argv) >= 4 and argv[0] == "serve":
        serve(argv[1], int(argv[2]),
              [tuple(int(n) for n in s.split(":")) for s in argv[3:]],
              config_path)
        return 0
    if len(argv) >= 2 and argv[0] == "check":
        check(argv[1], argv[2:], config_path)
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
