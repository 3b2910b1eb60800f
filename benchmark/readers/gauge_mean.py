"""Mean of a gauge sampled once a second during the window.

Parameters: ``metric``, ``labels``, ``scale``."""

from benchmark.lib import prom


def read(definition: dict, ctx: dict):
    samples = [prom.total(s, definition["metric"], definition.get("labels"))
               for s in ctx["gauge_samples"]
               if any(n == definition["metric"] for n, _ in s)]
    if not samples:
        return None
    return sum(samples) / len(samples) * definition.get("scale", 1.0)
