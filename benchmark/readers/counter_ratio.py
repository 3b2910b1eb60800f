"""Ratio of two counters' growth over the window: (numerator after - before)
/ (denominator after - before), from the worker's ``/metrics``.

Parameters: ``metric`` (counter name), ``numerator`` and ``denominator``
(labels that pick each series), ``scale``."""

from benchmark.lib import prom


def read(definition: dict, ctx: dict):
    before, after = ctx["prom_before"], ctx["prom_after"]
    below = prom.delta(before, after, definition["metric"],
                       definition["denominator"])
    if below <= 0:
        return None
    return (prom.delta(before, after, definition["metric"],
                       definition["numerator"])
            / below * definition.get("scale", 1.0))
