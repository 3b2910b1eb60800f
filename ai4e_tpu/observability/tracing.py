"""Spans, propagation, and exporters — one trace per task.

Reference behavior being matched (SURVEY.md §5 "Tracing / profiling"):

- every endpoint execution is wrapped in a span
  (``ai4e_service.py:158-178`` — ``tracer.span(name=trace_name)``);
- trace context crosses process boundaries via the ``x-b3-*`` headers Istio
  propagates and the mixer adapter maps to App Insights
  (``application-insights-istio-adapter/configuration.yaml:10-13``);
- span durations double as latency metrics (the reference's ``Stopwatch``
  blocks around Redis/publish, ``CacheConnectorUpsert.cs:162-201``).

TPU addition: ``device_trace`` bridges spans into the XLA/JAX profiler
(``jax.profiler.TraceAnnotation``) so a TaskId-keyed request span and its
device execution line up in one timeline.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import sys
import threading
import time
import uuid
from dataclasses import dataclass, field

log = logging.getLogger("ai4e_tpu.trace")

# Same header names Istio/B3 uses (configuration.yaml:10-13) so meshes that
# already speak B3 interoperate with no translation.
TRACE_HEADER = "x-b3-traceid"
SPAN_HEADER = "x-b3-spanid"
PARENT_HEADER = "x-b3-parentspanid"
SAMPLED_HEADER = "x-b3-sampled"


def _new_trace_id() -> str:
    return uuid.uuid4().hex


def _new_span_id() -> str:
    return os.urandom(8).hex()


@dataclass
class Span:
    name: str
    service: str
    trace_id: str
    span_id: str
    parent_id: str | None = None
    task_id: str | None = None
    start: float = 0.0          # epoch seconds
    duration: float = 0.0       # seconds
    status: str = "ok"          # ok | error
    error: str | None = None
    attrs: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {
            "name": self.name, "service": self.service,
            "trace_id": self.trace_id, "span_id": self.span_id,
            "start": self.start, "duration": self.duration,
            "status": self.status,
        }
        if self.parent_id:
            d["parent_id"] = self.parent_id
        if self.task_id:
            d["task_id"] = self.task_id
        if self.error:
            d["error"] = self.error
        if self.attrs:
            d["attrs"] = self.attrs
        return d


# -- exporters ---------------------------------------------------------------


class LogExporter:
    """Spans to the Python log — the container-stdout telemetry path."""

    def export(self, span: Span) -> None:
        log.info("span %s/%s trace=%s task=%s %.1fms %s",
                 span.service, span.name, span.trace_id,
                 span.task_id or "-", span.duration * 1e3, span.status)


class JsonlExporter:
    """Append-only JSONL span log (the App Insights sink analogue); one line
    per span, safe across threads."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._fh = open(path, "a", encoding="utf-8")

    def export(self, span: Span) -> None:
        line = json.dumps(span.to_dict(), separators=(",", ":"))
        with self._lock:
            self._fh.write(line + "\n")
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            self._fh.close()


class FanoutExporter:
    """Ship every span to several sinks (e.g. a local JSONL log AND the
    OTLP collector); one sink failing must not starve the others."""

    def __init__(self, exporters):
        self.exporters = list(exporters)

    def export(self, span: Span) -> None:
        for exporter in self.exporters:
            try:
                exporter.export(span)
            except Exception:  # noqa: BLE001 — telemetry must not break serving
                log.exception("span export failed in %s",
                              type(exporter).__name__)

    def close(self) -> None:
        for exporter in self.exporters:
            close = getattr(exporter, "close", None)
            if close is None:
                continue
            try:
                close()
            except Exception:  # noqa: BLE001 — one sink must not starve the rest
                log.exception("exporter close failed in %s",
                              type(exporter).__name__)


class InMemoryExporter:
    """Test sink."""

    def __init__(self):
        self.spans: list[Span] = []

    def export(self, span: Span) -> None:
        self.spans.append(span)

    def by_trace(self, trace_id: str) -> list[Span]:
        return [s for s in self.spans if s.trace_id == trace_id]

    def by_task(self, task_id: str) -> list[Span]:
        return [s for s in self.spans if s.task_id == task_id]


# -- tracer ------------------------------------------------------------------

# (trace_id, span_id, sampled) of the active span in this execution context.
_CURRENT: contextvars.ContextVar[tuple[str, str, bool] | None] = \
    contextvars.ContextVar("ai4e_trace_current", default=None)


class Tracer:
    """Creates spans, propagates context, exports on close.

    Works identically in sync and async code: the active span lives in a
    ``contextvars.ContextVar``, which asyncio tasks inherit and isolate
    automatically (the reference leans on OpenCensus's equivalent machinery
    via ``AzureMonitorLogger``, ``ai4e_service.py:17,53-54``).
    """

    def __init__(self, service: str, exporter=None,
                 sample_rate: float | None = None, metrics=None):
        self.service = service
        # None → follow the process tracer *live* (resolved per span), so
        # configure_tracer() after component construction applies everywhere.
        # Same rule for metrics: a component tracer built WITH a registry
        # (every assembly-owned component passes its own) lands
        # ai4e_span_seconds there; without one it follows the process
        # tracer, then the process default — resolved per observation, not
        # frozen at construction, or the AIL002 leak comes back the moment
        # construction order changes.
        self.exporter = exporter
        self.sample_rate = sample_rate
        self.metrics = metrics
        # (resolved registry, its histogram) — avoids re-taking the
        # registry's get-or-create lock on every span observation while
        # still following a live configure_tracer(metrics=...) rebinding.
        self._span_hist_cache: tuple | None = None

    def _effective_exporter(self):
        if self.exporter is not None:
            return self.exporter
        if self is not _GLOBAL and _GLOBAL.exporter is not None:
            return _GLOBAL.exporter
        return _DEFAULT_EXPORTER

    def _effective_metrics(self):
        # When self IS the global tracer, self.metrics and _GLOBAL.metrics
        # are the same attribute, so one or-chain covers every case.
        from ..metrics import DEFAULT_REGISTRY
        return self.metrics or _GLOBAL.metrics or DEFAULT_REGISTRY

    def _span_seconds(self):
        reg = self._effective_metrics()
        cached = self._span_hist_cache
        if cached is None or cached[0] is not reg:
            cached = (reg, reg.histogram(
                "ai4e_span_seconds", "Span durations by span name"))
            self._span_hist_cache = cached
        return cached[1]

    def _effective_sample_rate(self) -> float:
        if self.sample_rate is not None:
            return self.sample_rate
        if self is not _GLOBAL and _GLOBAL.sample_rate is not None:
            return _GLOBAL.sample_rate
        return 1.0

    # -- propagation -------------------------------------------------------

    def headers(self) -> dict[str, str]:
        """Outbound headers for the active span (inject before any HTTP hop)."""
        cur = _CURRENT.get()
        if cur is None:
            return {}
        trace_id, span_id, sampled = cur
        return {TRACE_HEADER: trace_id, SPAN_HEADER: span_id,
                SAMPLED_HEADER: "1" if sampled else "0"}

    @staticmethod
    def parent_from(headers) -> tuple[str, str, bool] | None:
        """Parse inbound x-b3 headers (case-insensitive mappings like aiohttp's
        work directly)."""
        trace_id = headers.get(TRACE_HEADER)
        if not trace_id:
            return None
        span_id = headers.get(SPAN_HEADER, "")
        sampled = headers.get(SAMPLED_HEADER, "1") != "0"
        return (trace_id, span_id, sampled)

    # -- spans -------------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, task_id: str | None = None,
             headers=None, **attrs):
        """Open a span; yields the ``Span`` (mutable — add attrs mid-flight).

        Parent resolution order: explicit inbound ``headers`` → the active
        span in this context → new root trace. The sampling decision is made
        once at the root and inherited (App Insights samples the same way,
        ``CacheManager/host.json:5-8``).
        """
        parent = self.parent_from(headers) if headers else None
        if parent is None:
            parent = _CURRENT.get()
        if parent is not None:
            trace_id, parent_id, sampled = parent
            parent_id = parent_id or None
        else:
            trace_id, parent_id = _new_trace_id(), None
            sampled = _sample(trace_id, self._effective_sample_rate())
        if self._effective_sample_rate() <= 0.0:
            # Hard off (trace_enabled=0) beats inherited x-b3-sampled:1 —
            # a B3-speaking mesh stamps every request as sampled, and the
            # kill switch must still kill local export.
            sampled = False

        span = Span(name=name, service=self.service, trace_id=trace_id,
                    span_id=_new_span_id(), parent_id=parent_id,
                    task_id=task_id, start=time.time(), attrs=dict(attrs))
        token = _CURRENT.set((trace_id, span.span_id, sampled))
        t0 = time.perf_counter()
        try:
            yield span
        except BaseException as exc:
            span.status = "error"
            span.error = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            _CURRENT.reset(token)
            span.duration = time.perf_counter() - t0
            self.record(span, sampled)

    def record(self, span: Span, sampled: bool = True) -> None:
        """A closed span: observed, and exported if its trace is sampled.
        ``span()`` ends here; so does a span whose interval its maker
        measured — one that began before any tracer could open it, as a
        boot does with its process (``observability/boot.py``)."""
        self._span_seconds().observe(span.duration, name=span.name,
                                     service=span.service)
        if sampled:
            try:
                self._effective_exporter().export(span)
            except Exception:  # noqa: BLE001 — telemetry must not break serving
                log.exception("span export failed")

    def current_trace_id(self) -> str | None:
        cur = _CURRENT.get()
        return cur[0] if cur else None


def _sample(trace_id: str, rate: float) -> bool:
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    # Deterministic per-trace: every service in the hop chain keeps or drops
    # the same traces.
    return (int(trace_id[:8], 16) / 0xFFFFFFFF) < rate


# -- process-global tracer ---------------------------------------------------

_DEFAULT_EXPORTER = LogExporter()
_GLOBAL = Tracer("ai4e")
_UNSET = object()


def get_tracer() -> Tracer:
    return _GLOBAL


def configure_tracer(service: str | None = None, exporter=_UNSET,
                     sample_rate=_UNSET, metrics=_UNSET) -> Tracer:
    """Reconfigure the process tracer in place. Component tracers built
    without an explicit exporter/sample_rate/metrics (every
    service/gateway/dispatcher default) follow these settings live. Pass
    ``None`` explicitly to reset a field to its default (LogExporter /
    rate 1.0 / the process-default metrics registry)."""
    if service is not None:
        _GLOBAL.service = service
    if exporter is not _UNSET:
        _GLOBAL.exporter = exporter
    if sample_rate is not _UNSET:
        _GLOBAL.sample_rate = sample_rate
    if metrics is not _UNSET:
        _GLOBAL.metrics = metrics
    return _GLOBAL


# -- XLA profiler bridge -----------------------------------------------------


_NO_ANNOTATION = contextlib.nullcontext()
_annotation = None   # jax.profiler.TraceAnnotation, resolved once JAX is loaded


def device_trace(name: str, **stats):
    """A region on the JAX profiler's own clock
    (``jax.profiler.TraceAnnotation``): in a trace recorded with the host
    tracer on, the region appears on the calling thread's line of the host
    plane, beside the device planes, with ``stats`` as its arguments.

    Without a profiler session the annotation is one flag test inside the
    C++ ``TraceMe``; in a process that never loaded JAX (control plane,
    race-smoke CI) it is a shared null context and JAX stays unloaded.
    Names are stable and prefixed ``ai4e.`` — the benchmark's trace
    reduction matches on them (docs/observability.md)."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NO_ANNOTATION   # no JAX, so no session: stay unresolved
        import jax.profiler
        _annotation = jax.profiler.TraceAnnotation
    return _annotation(name, **stats)
