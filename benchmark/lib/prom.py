"""Prometheus text exposition → samples, and deltas between two scrapes."""

from __future__ import annotations

import re

_LINE = re.compile(r"([A-Za-z_:][\w:]*)(?:\{(.*)\})? (\S+)")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """``{(name, frozenset(labels.items())): value}`` of one scrape."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.fullmatch(line)
        if m:
            labels = frozenset(_LABEL.findall(m.group(2) or ""))
            out[(m.group(1), labels)] = float(m.group(3))
    return out


def total(samples: dict, name: str, labels: dict | None = None) -> float:
    """Sum of every series of ``name`` whose labels include ``labels``."""
    want = set((labels or {}).items())
    return sum(v for (n, ls), v in samples.items()
               if n == name and want <= ls)


def delta(before: dict, after: dict, name: str,
          labels: dict | None = None) -> float:
    return total(after, name, labels) - total(before, name, labels)
