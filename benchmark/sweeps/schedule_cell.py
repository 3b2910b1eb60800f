#!/usr/bin/env python3
"""``schedule_model.py`` for any open-loop LM cell of the manifest: which
``schedule_seed`` keeps the cell's two p95s steady under every rotation, and
whether the request at the gen p95's rank is a cap-length answer under each.

    python3 benchmark/sweeps/schedule_cell.py <workload> --tick S --join S \\
        --join-token S [--set KEY=JSON ...] [--scan FIRST LAST | seed ...]

The queueing model is ``schedule_model.p95s`` itself (the engine to the
clock: slots, joins prefilled one at a time between steps, one step a tick).
What it needs of the cell comes from the cell's own files: the mix from
``traffic/<mix>.json`` (``--set`` overrides a parameter, as ``run.py``'s
does), the slots and the prompt buckets from the configuration's
``worker_env`` (the runtime adds the cache length as the last bucket). What
only a chip run knows comes as arguments, read off a traced run: a tick
without joins (``tick_device_wait_ms`` + ``tick_host_ms``), a join's fixed
part and its part per bucket token (``prefill_ms`` at the mix's mean bucket).
Each is also tried 3 % lower, 3 % higher and with the join's two parts traded
against each other, as ``schedule_model.VARIANTS`` does for ``olmoe.decode``.
``schedule_model.py`` is the benchmark's file and keeps its slots and buckets
in module constants: they are set here, once, in ``configure``.

With seeds it prints each; with ``--scan`` the eight steadiest of the range.
It chooses nothing on its own: ``sweeps/<workload>.md`` says which seed was
taken and what the chip read. ``qnext.docqa`` (PR 32): ``--tick 0.0215 --join
0.006 --join-token 0.000068``.

``traced_instant`` (PR 42) is where a traced run of a cell opens its traced
seconds under one rotation: the cell's own schedule through
``lib/tracing.trace_instant``, the rule ``run.py`` applies.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import schedule_model as model  # noqa: E402
from benchmark.generators.open_loop import schedule  # noqa: E402
from benchmark.lib.tracing import trace_instant  # noqa: E402


def load(*parts):
    with open(os.path.join(model.ROOT, *parts)) as f:
        return json.load(f)


def traced_instant(workload: str, rotation: int,
                   seconds: float = model.WINDOW_S,
                   manifest: str = "BENCHMARK.json"):
    """``(instant, dues)``: the seconds of the window at which a traced run of
    the cell at ``--seed rotation`` writes ``trace.start``, and the due
    instants of the window's arrivals it chose among (None for a generator
    without a schedule, which keeps the window's middle)."""
    cell = next(w for w in load(manifest)["workloads"]
                if w["name"] == workload)
    traffic = load("benchmark", "traffic", cell["traffic"] + ".json")
    generator = importlib.import_module(
        "benchmark.generators." + traffic["generator"])
    dues = None
    if hasattr(generator, "schedule"):
        dues = [a["due"] - traffic["ramp_s"]
                for a in generator.schedule(traffic, seconds, rotation)
                if a["in_window"]]
    return trace_instant(seconds, dues), dues


def configure(workload: str, overrides: list[str]) -> dict:
    """The cell's mix; ``schedule_model``'s slots and buckets set to the
    cell's."""
    manifest = load("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    config = load(next(c["file"] for c in manifest["configs"]
                       if c["name"] == cell["config"]))
    env = config["worker_env"]
    model.SLOTS = int(env["AI4E_RUNTIME_KV_SLOTS"])
    model.BUCKETS = (*(int(b) for b in env[
        "AI4E_RUNTIME_DECODE_PROMPT_BUCKETS"].split(",")),
        int(env["AI4E_RUNTIME_KV_MAX_LEN"]))
    traffic = load("benchmark", "traffic", cell["traffic"] + ".json")
    for item in overrides:
        key, value = item.split("=", 1)
        traffic[key] = json.loads(value)
    return traffic


def variants(tick: float, join: float, join_token: float) -> tuple:
    return ((tick, join, join_token),
            (0.97 * tick, join - 0.001, 0.85 * join_token),
            (1.03 * tick, join + 0.001, 1.15 * join_token),
            (tick, 1.5 * join, 0.75 * join_token))


def cap_at_rank(traffic: dict, seed: int) -> bool:
    """Is the request at the gen p95's rank one of the longest answers? With
    every cap-length answer above every other in latency, it is when the
    cap-length answers of the window outnumber the requests beyond the p95."""
    inside = [a for a in schedule(traffic, model.WINDOW_S, seed)
              if a["in_window"]]
    cap = traffic["max_new_tokens"]["hi"]
    at_cap = sum(a["max_new_tokens"] == cap for a in inside)
    beyond = len(inside) - int(0.95 * (len(inside) - 1)) - 1
    return at_cap > beyond + 1


def judge(traffic: dict, schedule_seed: int, constants: tuple) -> tuple:
    """(token p95 range, quartile spread, gen p95 range, quartile spread) at
    their worst over the variants, and ``cap_at_rank`` under every rotation."""
    worst = [0.0] * 4
    mix = dict(traffic, schedule_seed=schedule_seed)
    for variant in constants:
        runs = [model.p95s(mix, seed, *variant) for seed in model.ROTATIONS]
        for k, column in enumerate(zip(*runs)):
            worst[2 * k] = max(worst[2 * k], max(column) / min(column) - 1)
            worst[2 * k + 1] = max(worst[2 * k + 1], model.spread(column))
    return (*worst, all(cap_at_rank(mix, seed) for seed in model.ROTATIONS))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("workload")
    parser.add_argument("--tick", type=float, required=True)
    parser.add_argument("--join", type=float, required=True)
    parser.add_argument("--join-token", type=float, required=True)
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON")
    parser.add_argument("--scan", nargs=2, type=int,
                        metavar=("FIRST", "LAST"))
    parser.add_argument("seeds", nargs="*", type=int)
    args = parser.parse_args()
    traffic = configure(args.workload, args.set)
    constants = variants(args.tick, args.join, args.join_token)
    seeds = args.seeds or (range(args.scan[0], args.scan[1] + 1)
                           if args.scan else [traffic["schedule_seed"]])
    rows = [(judge(traffic, s, constants), s) for s in seeds]
    if args.scan:
        rows = sorted(rows, key=lambda r: (not r[0][4], r[0][2] + r[0][0]))[:8]
    for (tok_range, tok_spread, gen_range, gen_spread, cap), s in rows:
        print(f"schedule_seed {s}: token p95 range {100 * tok_range:.2f} % "
              f"spread {100 * tok_spread:.2f} %, gen p95 range "
              f"{100 * gen_range:.2f} % spread {100 * gen_spread:.2f} %, "
              f"a cap-length answer at the gen p95's rank under every "
              f"rotation: {cap}")


if __name__ == "__main__":
    main()
