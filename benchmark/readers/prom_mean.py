"""Mean of a Prometheus histogram over the window: (sum after - sum before)
/ (count after - count before), from the worker's ``/metrics``.

Parameters: ``metric`` (histogram base name), ``labels``, ``scale``,
``percent_of`` (optional key of the configuration's ``derived`` numbers: the
mean is then given as a percentage of it)."""

from benchmark.lib import prom


def read(definition: dict, ctx: dict):
    before, after = ctx["prom_before"], ctx["prom_after"]
    labels = definition.get("labels")
    count = prom.delta(before, after, definition["metric"] + "_count", labels)
    if count <= 0:
        return None
    value = prom.delta(before, after, definition["metric"] + "_sum",
                       labels) / count * definition.get("scale", 1.0)
    if "percent_of" in definition:
        value = 100.0 * value / ctx["config"]["derived"][
            definition["percent_of"]]
    return value
