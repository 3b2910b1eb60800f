"""A program's share of its roofline, from the device trace.

Device time is the traced seconds of the XLA modules whose name matches
``module`` (per device). The least time the chip could take is the larger of
operations / peak FLOP/s and bytes / peak bytes/s, with operations and bytes
from the family's own shape functions (``benchmark/references/<family>.py``)
and the peaks from ``benchmark/peaks.json`` for the worker's ``device_kind``.
The result line's ``roofline_bound`` says which of the two bounds it.

Parameters: ``module`` (regular expression), ``family``, ``work``:
- ``batch_rows``: rows executed during the trace = h2d bytes shipped (padded
  batches, ``ai4e_batch_h2d_bytes_total`` scraped at both edges of the trace)
  / bytes per row; the rows are spread over the devices.
- ``decode_step``: one step per module call over ``kv_slots`` slots; the
  live K/V per step = cached-token reads of the window's requests / decode
  steps of the window.
"""

import importlib
import re

from benchmark.lib import prom


def read(definition: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    pattern = re.compile(definition["module"])
    matched = [m for name, m in trace["modules"].items()
               if pattern.search(name)]
    seconds = sum(m["seconds"] for m in matched)
    calls = sum(m["calls"] for m in matched)
    if seconds <= 0:
        return None
    family = importlib.import_module(
        "benchmark.references." + definition["family"].replace("-", "_"))
    config, derived = ctx["config"], ctx["config"]["derived"]
    if definition["work"] == "batch_rows":
        shipped = prom.delta(ctx["trace_prom_before"], ctx["trace_prom_after"],
                             "ai4e_batch_h2d_bytes_total")
        rows = shipped / derived["row_bytes"]
        if rows <= 0:
            return None
        flops, nbytes = family.ops_and_bytes(config, rows)
        flops, nbytes = flops / trace["devices"], nbytes / trace["devices"]
    elif definition["work"] == "decode_step":
        steps = prom.delta(ctx["prom_before"], ctx["prom_after"],
                           "ai4e_decode_step_seconds_count",
                           {"phase": "decode"})
        if steps <= 0:
            return None
        reads = sum(r["max_new_tokens"] * r["prompt_len"]
                    + r["max_new_tokens"] * (r["max_new_tokens"] - 1) / 2
                    for r in ctx["gen"]["requests"] if r["ok"])
        flops, nbytes = family.ops_and_bytes(config, derived["kv_slots"],
                                             reads / steps)
        flops, nbytes = flops * calls, nbytes * calls
    else:
        raise ValueError(f"unknown work {definition['work']!r}")
    peaks = ctx["peaks"]
    by_compute = flops / peaks["flops_per_s"][definition.get("dtype", "bf16")]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})[definition["name"]] = {
        "bound": "compute" if by_compute >= by_memory else "memory",
        "device_seconds": seconds, "calls": calls,
        "least_seconds": max(by_compute, by_memory)}
    return 100.0 * max(by_compute, by_memory) / seconds
