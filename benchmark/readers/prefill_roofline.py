"""A prefill program's share of its roofline, from the device trace and the
engine's counts of what the prefills computed.

Device time is the traced seconds of the XLA modules whose name matches
``module``. The work is the family's ``prefill_ops_and_bytes``
(``benchmark/references/<family>.py``) of the real tokens
(``ai4e_decode_prefill_tokens_total{kind="real"}``) and the (query, key)
pairs a layer by kind (``ai4e_decode_prefill_pairs_total``) that the engine
counted between the trace's own scrapes — the mean of a prefill there, times
the module's traced calls, since a join counted at the interval's edge may
have run outside it; the whole window's scrapes where the interval counted
none. Where the program has no such counters, or the trace no such module,
the reader says nothing. Parameters: ``module`` (regular expression),
``family``, ``dtype``.
"""

import importlib
import re

from benchmark.lib import prom

TOKENS = "ai4e_decode_prefill_tokens_total"
PAIRS = "ai4e_decode_prefill_pairs_total"
JOINS = "ai4e_decode_step_seconds_count"


def _counted(before: dict, after: dict):
    joins = prom.delta(before, after, JOINS, {"phase": "prefill"})
    tokens = prom.delta(before, after, TOKENS, {"kind": "real"})
    if joins <= 0 or tokens <= 0:
        return None
    kinds = {dict(labels).get("kind") for (name, labels) in after
             if name == PAIRS}
    pairs = {kind: prom.delta(before, after, PAIRS, {"kind": kind})
             for kind in kinds if kind}
    return joins, tokens, pairs


def read(definition: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    pattern = re.compile(definition["module"])
    matched = [m for name, m in trace["modules"].items()
               if pattern.search(name)]
    seconds = sum(m["seconds"] for m in matched)
    calls = sum(m["calls"] for m in matched)
    if seconds <= 0 or not calls:
        return None
    counted = (_counted(ctx.get("trace_prom_before") or {},
                        ctx.get("trace_prom_after") or {})
               or _counted(ctx.get("prom_before") or {},
                           ctx.get("prom_after") or {}))
    if counted is None:
        return None
    joins, tokens, pairs = counted
    family = importlib.import_module(
        "benchmark.references." + definition["family"].replace("-", "_"))
    each = calls / joins
    flops, nbytes = family.prefill_ops_and_bytes(
        ctx["config"], tokens * each,
        {kind: n * each for kind, n in pairs.items()}, calls)
    peaks = ctx["peaks"]
    by_compute = flops / peaks["flops_per_s"][definition.get("dtype", "bf16")]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})[definition["name"]] = {
        "bound": "compute" if by_compute >= by_memory else "memory",
        "device_seconds": seconds, "calls": calls, "joins_counted": joins,
        "least_seconds": max(by_compute, by_memory)}
    return 100.0 * max(by_compute, by_memory) / seconds
