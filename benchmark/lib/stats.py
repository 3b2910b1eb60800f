"""Percentile and due-time arithmetic — the yardstick's own, so that no PR to
the program can change how a tail is taken."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by the nearest-rank rule: the
    smallest sample with at least ``q`` % of the samples at or below it.
    No interpolation, so a tail is always a latency some request had."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return data[rank - 1]


def median(values) -> float:
    return percentile(values, 50.0)


def beyond(values, q: float) -> int:
    """How many samples lie strictly beyond the ``q``-th percentile's rank —
    the guide asks for at least ten before a tail is trusted."""
    n = len(values)
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def stratified(n: int, inverse_cdf) -> list[float]:
    """``n`` values at the mid-quantiles of a distribution. Every seed then
    draws the SAME multiset and only shuffles it, so two seeds offer the
    same work in another order."""
    return [inverse_cdf((i + 0.5) / n) for i in range(n)]


def exponential_gaps(n: int, rate: float) -> list[float]:
    """The mid-quantiles of the exponential distribution with mean 1/``rate``
    (the distribution of a Poisson process's gaps; not a draw from one)."""
    return stratified(n, lambda u: -math.log1p(-u) / rate)


def lognormal_lengths(n: int, median_len: float, sigma: float,
                      lo: int, hi: int) -> list[int]:
    """Stratified log-normal lengths, clipped to [lo, hi]."""
    from statistics import NormalDist
    z = NormalDist()
    return [int(min(hi, max(lo, round(median_len * math.exp(
        sigma * z.inv_cdf(u)))))) for u in stratified(n, lambda u: u)]


def due_times(gaps, start: float = 0.0) -> list[float]:
    """Cumulative due instants of arrivals separated by ``gaps``; the first
    arrival is due one gap after ``start``."""
    out, t = [], start
    for g in gaps:
        t += g
        out.append(t)
    return out
