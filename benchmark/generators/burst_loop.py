"""Open loop in bursts: requests arrive in clumps (a page that fires several
calls, a batch client, a retry storm) on a fixed schedule at a FIXED mean
rate, whether or not earlier ones have ended.

Everything but the schedule is ``open_loop``'s: the sends, the window, the
drain, the sample for the reference, the ledgers and what ``run`` returns —
``run`` here IS ``open_loop.run`` over this file's ``schedule``. Traffic
parameters: ``open_loop``'s, and ``burst`` (the clump's size: log-normal
``median``, ``sigma``, ``lo``, ``hi``) and ``burst_gap_s`` (seconds between
two requests of one clump).

A burst epoch releases ``k`` requests ``burst_gap_s`` apart. The epochs are
one fixed sequence, as ``open_loop``'s arrivals are: their gaps the
mid-quantiles of the exponential distribution at ``rate_per_s`` / the mean
clump, the sizes ``k`` and the lengths the mid-quantiles of their log-normals
(``lib.stats.stratified``), each shuffled once by the mix's own
``schedule_seed``. ``--seed`` makes the request bodies and ROTATES the
window's epochs, each with its clump: every seed offers the same requests in
the same clumps with the same neighbours, starting at another point of the
cycle (PR 23's finding: a free shuffle per seed is the seed changing the
work). A mix whose file gives ``rotation`` starts every seed at THAT point of
the cycle, and ``--seed`` makes the bodies alone: where a step's time follows
the live count, the starting point itself changes the work (``burstchat``,
PR 42: two runs of one rotation read the gen p95 0.004 % apart, three
rotations 6.2 % apart). A phase offers the whole clumps nearest its share of
the rate, so the mean rate is ``rate_per_s`` to within half a clump a phase.
"""

from __future__ import annotations

import random
from itertools import accumulate

from benchmark.generators import open_loop
from benchmark.lib import stats


def burst_sizes(traffic: dict, n: int) -> list[int]:
    p = traffic["burst"]
    return stats.lognormal_lengths(n, p["median"], p["sigma"], p["lo"],
                                   p["hi"])


def mean_burst(traffic: dict) -> float:
    sizes = burst_sizes(traffic, 1000)
    return sum(sizes) / len(sizes)


def schedule(traffic: dict, seconds: float, seed: int) -> list[dict]:
    """Arrivals of the ramp then the window, in the order they are due:
    ``due`` (seconds from the ramp's start), ``in_window``, ``epoch`` (the
    clump, numbered within its phase as shuffled, before the rotation) and
    the lengths."""
    rng = random.Random(traffic.get("schedule_seed", 0))
    epoch_rate = traffic["rate_per_s"] / mean_burst(traffic)
    out, start = [], 0.0
    for phase, span in (("ramp", traffic["ramp_s"]), ("window", seconds)):
        epochs = max(1, round(epoch_rate * span)) if span > 0 else 0
        gaps = stats.exponential_gaps(epochs, epoch_rate)
        sizes = burst_sizes(traffic, epochs)
        rng.shuffle(gaps)
        rng.shuffle(sizes)
        n = sum(sizes)
        cols = {}
        for key in ("prompt_len", "max_new_tokens"):
            p = traffic[key]
            cols[key] = stats.lognormal_lengths(
                n, p["median"], p["sigma"], p["lo"], p["hi"])
            rng.shuffle(cols[key])
        first = [0, *accumulate(sizes)]   # where a clump's requests begin
        # Scale so the phase's epochs span exactly its seconds.
        scale = (span / sum(gaps) * (epochs / (epochs + 1.0)) if epochs
                 else 1.0)
        order = list(range(epochs))
        if phase == "window" and epochs:
            k = traffic.get("rotation", seed) % epochs
            order = order[k:] + order[:k]
        arrivals = []
        for i, at in zip(order, stats.due_times(
                [gaps[i] * scale for i in order], start)):
            for j in range(sizes[i]):
                arrivals.append({
                    "due": at + j * traffic["burst_gap_s"],
                    "in_window": phase == "window", "epoch": i,
                    **{k: v[first[i] + j] for k, v in cols.items()}})
        out += sorted(arrivals, key=lambda a: a["due"])
        start += span
    for i, a in enumerate(out):
        a["counter"] = i
    return out


async def run(ctx) -> dict:
    """``open_loop.run`` over the schedule above. A run is one process and
    one generator, so the name ``open_loop.run`` looks its schedule up by is
    pointed here for the call."""
    arrivals_of = open_loop.schedule
    open_loop.schedule = schedule
    try:
        return await open_loop.run(ctx)
    finally:
        open_loop.schedule = arrivals_of
