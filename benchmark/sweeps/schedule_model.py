#!/usr/bin/env python3
"""How the two p95s of an open-loop LM cell move with the rotation (--seed),
for one ``schedule_seed``: a queueing model of the decode engine, no chip.

    python3 benchmark/sweeps/schedule_model.py <mix> [schedule_seed ...]

The generator offers ONE schedule that ``--seed`` rotates. A p95 over ~160
requests is its 9th worst; where that rank falls on the edge of one burst of
the schedule, the burst is inside the window under some rotations and cut by
its edges under others, and the metric swings with the seed although the work
is the same. This model replays the schedule (``generators/open_loop.py``'s
own ``schedule``) through an engine that is what ``DecodeEngine`` is to the
clock: ``SLOTS`` slots, joins prefilled one at a time between steps
(``PREFILL_S`` by prompt bucket), one step of ``TICK_S`` for all live streams,
``FABRIC_S`` around a request — constants read off PR 26's chip runs of
``olmoe.decode`` (untraced: it reproduces their token p95 to 0.3 ms and their
gen p95 to 0.5 %). It prints, over 100 rotations and four variants of the
constants, the worst range and inter-quartile spread of both p95s. It chooses
nothing: ``sweeps/olmoe.decode.md`` says which seed was taken and what the
chip then read.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators.open_loop import schedule  # noqa: E402
from benchmark.lib.stats import percentile  # noqa: E402

SLOTS, FABRIC_S, WINDOW_S = 32, 0.011, 51.0
BUCKETS = (64, 128, 256, 512, 1024, 2048)
# (a tick without joins, a join's fixed part, a join's part per bucket token)
VARIANTS = ((0.0188, 0.004, 0.000044), (0.0182, 0.003, 0.000035),
            (0.0194, 0.005, 0.000052), (0.0188, 0.006, 0.00003))
ROTATIONS = ([1000003 * k + 17 for k in range(60)]
             + [2 ** 31 + 7919 * k for k in range(40)])


def p95s(traffic: dict, seed: int, tick_s: float, join_s: float,
         join_token_s: float) -> tuple[float, float]:
    """(token_latency_p95_ms, gen_latency_p95_ms) of one modelled run."""
    arrivals = sorted(schedule(traffic, WINDOW_S, seed),
                      key=lambda a: a["due"])
    t, i, queue, active, free, done = 0.0, 0, [], [], SLOTS, []
    while len(done) < len(arrivals):
        while i < len(arrivals) and arrivals[i]["due"] + FABRIC_S / 2 <= t:
            queue.append(arrivals[i])
            i += 1
        if not active and not queue:
            t = arrivals[i]["due"] + FABRIC_S / 2
            continue
        while queue and free:
            a = queue.pop(0)
            free -= 1
            t += join_s + join_token_s * next(
                b for b in BUCKETS if b >= a["prompt_len"])
            a["left"] = a["max_new_tokens"]     # the prefill gives the first
            active.append(a)
        t += tick_s
        for a in active:
            a["left"] -= 1
            if not a["left"]:
                a["end"] = t
                done.append(a)
                free += 1
        active = [a for a in active if a["left"]]
    inside = [a for a in done if a["in_window"]]
    latency = [a["end"] + FABRIC_S / 2 - a["due"] for a in inside]
    per_token = [s / a["max_new_tokens"] for s, a in zip(latency, inside)]
    return percentile(per_token, 95) * 1e3, percentile(latency, 95) * 1e3


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> None:
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           sys.argv[1] + ".json")) as f:
        traffic = json.load(f)
    seeds = [int(s) for s in sys.argv[2:]] or [traffic["schedule_seed"]]
    for schedule_seed in seeds:
        worst = [0.0] * 4
        for variant in VARIANTS:
            runs = [p95s(dict(traffic, schedule_seed=schedule_seed), seed,
                         *variant) for seed in ROTATIONS]
            for k, column in enumerate(zip(*runs)):
                worst[2 * k] = max(worst[2 * k],
                                   max(column) / min(column) - 1)
                worst[2 * k + 1] = max(worst[2 * k + 1], spread(column))
        print(f"schedule_seed {schedule_seed}: token p95 range "
              f"{100 * worst[0]:.2f} % spread {100 * worst[1]:.2f} %, gen p95 "
              f"range {100 * worst[2]:.2f} % spread {100 * worst[3]:.2f} %")


if __name__ == "__main__":
    main()
