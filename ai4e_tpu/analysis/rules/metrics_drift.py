"""AIL010 — metrics/docs drift on the ``ai4e_*`` metric-name surface.

The bug class (the mirror of AIL006's config drift): a metric exists in
code but appears nowhere in ``docs/METRICS.md`` — the operator staring
at a dashboard during an incident cannot find out what it means or what
labels it carries — or the docs describe a metric that no longer exists
(a rename that missed the docs; the alert an operator builds on it will
never fire). The first run of this rule found exactly one of the
latter: ``ai4e_trace_current`` was documented as an open-spans gauge
but had only ever been a ``ContextVar`` name in code.

Two checks, run once over the whole project:

1. every metric name registered in code — a string literal as the first
   argument of a ``.counter("ai4e_…")`` / ``.gauge(…)`` /
   ``.histogram(…)`` call, or a key of a model's ``step_report_series``
   dict literal (``DecodeEngine`` registers ``ai4e_decode_<key>`` for
   each) — appears in ``docs/METRICS.md``;
2. every ``ai4e_*`` token in ``docs/METRICS.md`` corresponds to a
   registered name (exact, a documented ``name_*`` family mention, or a
   histogram/counter exposition suffix ``_bucket``/``_sum``/``_count``
   of one).

File-path tokens (``ai4e_tpu/metrics/registry.py``) are excluded by
context; the module name ``ai4e_tpu`` is never a metric.
"""

from __future__ import annotations

import ast
import os
import re

from ..core import Finding, ProjectRule

_TOKEN_RE = re.compile(r"ai4e_[a-z0-9_]*[a-z0-9]")
_REGISTER_METHODS = ("counter", "gauge", "histogram")
_DOC_FILE = os.path.join("docs", "METRICS.md")
# Prometheus exposition suffixes a doc may legitimately spell out.
_EXPO_SUFFIXES = ("_bucket", "_sum", "_count")
_NEVER_METRICS = {"ai4e_tpu"}  # the package name, not a metric
# A decode backend's model declares its step's figures by name in a dict
# literal of this name; ``runtime/decode.py`` registers each under the prefix.
_DECLARED_SERIES = ("step_report_series", "ai4e_decode_")


def _registered_names(module) -> list[tuple[str, int]]:
    """(metric_name, lineno) for every registry-registration call with a
    literal name, and for every declared step-report series.
    Attribute-based matching (anything ``.counter(…)``) deliberately
    over-collects: a non-registry object with a ``counter``
    method taking an ``ai4e_``-prefixed string literal is not a thing
    this codebase has, and under-collecting would let real metrics ship
    undocumented."""
    out = []
    attr, prefix = _DECLARED_SERIES
    for node in ast.walk(module.tree):
        if (isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict)
                and any(isinstance(t, ast.Name) and t.id == attr
                        for t in node.targets)):
            out.extend((prefix + key.value, key.lineno)
                       for key in node.value.keys
                       if isinstance(key, ast.Constant)
                       and isinstance(key.value, str))
        if not isinstance(node, ast.Call) or not node.args:
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in _REGISTER_METHODS):
            continue
        arg = node.args[0]
        if (isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                and arg.value.startswith("ai4e_")):
            out.append((arg.value, node.lineno))
    return out


class MetricsDrift(ProjectRule):
    rule_id = "AIL010"
    name = "metrics-drift"
    description = ("every registered ai4e_* metric must appear in "
                   "docs/METRICS.md, and every documented one must exist "
                   "in code")

    def check_project(self, ctx):
        findings: list[Finding] = []
        known: dict[str, tuple[str, int]] = {}
        for module in ctx.modules:
            for name, line in _registered_names(module):
                known.setdefault(name, (module.path, line))
        doc_tokens = self._doc_tokens(ctx.root)
        doc_path = _DOC_FILE.replace(os.sep, "/")
        if not known and not doc_tokens:
            return findings
        documented = {tok for tok, _loc, _family in doc_tokens}
        families = {tok for tok, _loc, family in doc_tokens if family}

        def _snippet(path: str, line: int) -> str:
            try:
                with open(os.path.join(ctx.root, path),
                          encoding="utf-8") as fh:
                    lines = fh.read().splitlines()
                return (lines[line - 1].strip()
                        if 0 < line <= len(lines) else "")
            except OSError:
                return ""

        # Check 1: code side must be documented.
        for name, (path, line) in sorted(known.items()):
            if name in documented or any(
                    name == fam or name.startswith(fam + "_")
                    for fam in families):
                continue
            findings.append(Finding(
                self.rule_id, path, line, 0,
                f"metric {name} is registered in code but documented "
                f"nowhere in {doc_path} — dashboards and alerts cannot "
                "be built on an unexplained series",
                snippet=_snippet(path, line)))

        # Check 2: doc side must exist in code.
        for tok, (path, line), family in sorted(doc_tokens):
            if tok in known:
                continue
            if family and any(name == tok or name.startswith(tok + "_")
                              for name in known):
                continue  # explicit starred family covering real names
            if any(tok == name + suffix for name in known
                   for suffix in _EXPO_SUFFIXES):
                continue  # exposition-suffix spelling of a real histogram
            findings.append(Finding(
                self.rule_id, path, line, 0,
                f"{doc_path} documents {tok} but no code registers it — "
                "stale doc or a rename that missed the docs",
                snippet=_snippet(path, line)))
        return findings

    def _doc_tokens(self, root: str
                    ) -> list[tuple[str, tuple[str, int], bool]]:
        """(token, (doc path, line), is_family) from docs/METRICS.md.
        ``is_family`` = the token is immediately starred (``ai4e_slo_*``).
        Tokens in file-path context (followed by ``/`` or ``.py``) and
        the package name are skipped."""
        path = os.path.join(root, _DOC_FILE)
        rel = _DOC_FILE.replace(os.sep, "/")
        out = []
        try:
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
        except OSError:
            return out
        for i, line in enumerate(text.splitlines(), 1):
            for m in _TOKEN_RE.finditer(line):
                tok = m.group(0)
                rest = line[m.end():]
                if tok in _NEVER_METRICS:
                    continue
                if rest.startswith("/") or rest.startswith(".py"):
                    continue  # file path, not a metric
                family = rest.startswith("*") or rest.startswith("_*")
                out.append((tok, (rel, i), family))
        return out
