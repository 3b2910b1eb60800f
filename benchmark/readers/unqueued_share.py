"""Share of the traced window the device had nothing queued by the decode
thread, from the program's own ledger
(``ai4e_decode_device_unqueued_seconds_total{cause}``), between the
launcher's two ``/metrics`` scrapes at the trace's own edges.

Parameters: ``metric`` (the counter), ``mode``:
- ``booked``: the seconds booked to ``causes`` / the trace's ``window_s``;
- ``idle_queued``: (``window_s`` - ``busy_s`` - the seconds booked to every
  cause) / ``window_s``: the device idle while the program believed work was
  queued. Under -0.5 % an interval is opened early or closed late.
"""

from benchmark.lib import prom


def read(definition: dict, ctx: dict):
    trace = ctx.get("trace") or {}
    before = ctx.get("trace_prom_before") or {}
    after = ctx.get("trace_prom_after") or {}
    metric, window = definition["metric"], trace.get("window_s", 0.0)
    if window <= 0 or not any(name == metric for name, _ in after):
        return None
    if definition["mode"] == "booked":
        seconds = sum(prom.delta(before, after, metric, {"cause": cause})
                      for cause in definition["causes"])
    elif definition["mode"] == "idle_queued":
        seconds = (window - trace["busy_s"]
                   - prom.delta(before, after, metric))
    else:
        raise ValueError(f"unknown mode {definition['mode']!r}")
    return 100.0 * seconds / window
