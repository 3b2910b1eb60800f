"""Median over the sampled tasks' hop ledgers of the time between two
stamps (``from_event`` → ``to_event``, first occurrence of each), in ms — or,
with ``ms_of``, of one phase stamp's own duration.

Parameters: ``from_event``, ``to_event`` | ``ms_of``."""

from benchmark.lib import stats


def _first(events: list[dict], name: str):
    return next((ev for ev in events if ev.get("e") == name), None)


def read(definition: dict, ctx: dict):
    spans = []
    for events in ctx["ledgers"]:
        if "ms_of" in definition:
            ev = _first(events, definition["ms_of"])
            if ev is not None and "ms" in ev:
                spans.append(float(ev["ms"]))
            continue
        a = _first(events, definition["from_event"])
        b = _first(events, definition["to_event"])
        if a is not None and b is not None:
            spans.append((b["t"] - a["t"]) * 1000.0)
    return stats.median(spans) if spans else None
