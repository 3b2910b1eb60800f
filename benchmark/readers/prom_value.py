"""A value the program set or counted before the window, as the worker's
``/metrics`` holds it at the window's end: the sum of every series of one
metric whose labels match, from the last scrape alone (no delta: a boot's
gauges are set once, and its counters stopped where serving began).

A scrape without one of the series says nothing — a program that does not
have the metric — rather than 0.

Parameters: ``metric`` (the series' name), ``labels`` (fixed labels,
optional), ``label`` and ``values`` (optional: one series a value of that
label, added up; each has to be there), ``scale``."""


def read(definition: dict, ctx: dict):
    after = ctx["prom_after"] or {}
    fixed = definition.get("labels", {})
    wanted = [dict(fixed, **{definition["label"]: value})
              for value in definition["values"]] if "label" in definition \
        else [fixed]
    total = 0.0
    for labels in wanted:
        want = set(labels.items())
        found = [v for (name, ls), v in after.items()
                 if name == definition["metric"] and want <= ls]
        if not found:
            return None
        total += sum(found)
    return total * definition.get("scale", 1.0)
