"""Sum of the means of several series of one Prometheus histogram over the
window: for each value of one label, (sum after - sum before) / (count after
- count before), added up. For quantities observed once each per event — the
phases of a tick — so that the sum is the mean of their total.

A series that did not move makes the whole sum unknown: nothing is reported
(a program without the histogram, or one that books only some of the parts).

Parameters: ``metric`` (histogram base name), ``label`` and ``values`` (the
series to add), ``labels`` (fixed labels, optional), ``scale``."""

from benchmark.lib import prom


def read(definition: dict, ctx: dict):
    before, after = ctx["prom_before"], ctx["prom_after"]
    total = 0.0
    for value in definition["values"]:
        labels = dict(definition.get("labels", {}),
                      **{definition["label"]: value})
        count = prom.delta(before, after, definition["metric"] + "_count",
                           labels)
        if count <= 0:
            return None
        total += prom.delta(before, after, definition["metric"] + "_sum",
                            labels) / count
    return total * definition.get("scale", 1.0)
