"""End-to-end metrics, each from its file ``benchmark/end_to_end/<name>.json``
(a reducer kind + parameters), over what the load generator saw at the
client's side of the gateway. ``setup_s`` is the harness's own."""

from __future__ import annotations

from . import stats


def _field(record: dict, field: str) -> float:
    if field == "latency_per_token_s":
        # A failed request returned no tokens: spread its (censored) wait
        # over the tokens it asked for, so it still counts in the tail.
        tokens = (len(record["result"]["tokens"]) if record["ok"]
                  else record.get("max_new_tokens", 1))
        return record["latency_s"] / max(1, tokens)
    return record[field]


def rate(definition: dict, gen: dict) -> float:
    """Requests that ended inside the window with a valid result, per second
    of the window."""
    return sum(1 for r in gen["requests"] if r["ok"]) / gen["window_s"]


def percentile(definition: dict, gen: dict) -> float:
    """A percentile over EVERY request of the window; a failed one stays in
    the sample with the time it had waited when it was given up."""
    values = [_field(r, definition["field"]) for r in gen["requests"]]
    return stats.percentile(values, definition["q"]) * definition.get(
        "scale", 1.0)


KINDS = {"rate": rate, "percentile": percentile}


def describe(definition: dict, gen: dict) -> str:
    """The earlier line the issue asks for: median and sample count."""
    if definition["kind"] != "percentile":
        return f"{len(gen['requests'])} requests ended in the window"
    values = [_field(r, definition["field"]) for r in gen["requests"]]
    scale = definition.get("scale", 1.0)
    return (f"median {stats.median(values) * scale:.3f}, n={len(values)}, "
            f"{stats.beyond(values, definition['q'])} beyond p{definition['q']}")
