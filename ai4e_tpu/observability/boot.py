"""The worker's boot on one ledger — process start to serving, as one trace.

A worker's start is most of what a rollout's respawn or an autoscaler's cold
start waits for, and it was the one stretch the program said nothing about.
This module books it through the tracer that is there (``tracing.Span``,
``Tracer.record``, the configured exporter, ``ai4e_span_seconds``):

- ``boot`` — from the process's own start (``vitals.read_start_epoch``) to
  the instant the HTTP server accepts — is the root of one trace;
- under it the **phases**, contiguous by construction (``enter`` closes the
  phase that is open and opens the next, so their seconds add up to the
  root's): ``boot.import``, ``boot.backend``, ``boot.build``, ``boot.pools``,
  ``boot.batch_warmup``, ``boot.warm``, ``boot.serve``;
- under a ``boot.warm``, one ``boot.warm.program`` a program call of
  ``PagedDecodeRuntime.warm()``, which also opens ``device_trace(
  "ai4e.boot.warm")`` so a profiler session laid over a boot has the host's
  Python and the device's first execution on one clock. How the call came by
  each program it ran (``obtained``) is on the span too: ``loaded`` from the
  store of executables (``runtime/executables.py``) with the ``load_s`` that
  took, or ``built`` — traced, lowered and compiled — and the span's
  ``outcome`` is ``loaded`` where nothing was built.

Every span carries wall seconds and the process's CPU seconds (``cpu_s``:
wall far above it is a worker that waited — for cores, for the device — and
did not run Python), and what JAX itself reported while it was open: one
listener on ``jax.monitoring`` adds each trace / lower / backend-compile /
cache-retrieval duration to the span open on the event's thread (``trace_s``,
``lower_s``, ``compile_s``, ``retrieve_s``, ``cache_hits``, ``cache_misses``)
and to ``ai4e_jax_compile_seconds_total{stage,when}`` /
``ai4e_jax_compile_cache_total{result,when}``. JAX nests these events (a
jitted function traced inside another's trace, a retrieval inside a backend
compile); the listener books each second once, to the innermost event.

Spans are opened and closed by the ledger itself — none is ever the
context's current span, so no request inherits the boot's trace — kept in
memory, and handed to the process's tracer when the worker starts serving
(``serving()``), with the gauges ``ai4e_boot_seconds{phase}`` /
``ai4e_boot_cpu_seconds{phase}``, ``ai4e_boot_programs_total{outcome}``,
``ai4e_boot_program_load_seconds_total`` and one summary in the log. The listener
stays registered: it fires only when something compiles, so a warm worker
pays nothing, and a compile while serving is counted under ``when="serving"``
with the function's name in a WARNING line.

Nothing here runs unless ``begin()`` was called (``cli.run_worker`` does):
in any other process every module-level call is one test of a global.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque

from . import tracing, vitals
from .tracing import Span, device_trace

log = logging.getLogger("ai4e_tpu.boot")

_IMPORTED = time.time()   # the fallback for a process start /proc cannot give

PHASES = ("import", "backend", "build", "pools", "batch_warmup", "warm",
          "serve")

# JAX's event → (span attr, the counter's ``stage``).
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace_s", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower_s", "lower"),
    "/jax/core/compile/backend_compile_duration": ("compile_s", "backend"),
    "/jax/compilation_cache/cache_retrieval_time_sec": ("retrieve_s",
                                                        "retrieve"),
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": ("cache_hits", "hit"),
    "/jax/compilation_cache/cache_misses": ("cache_misses", "miss"),
}
_PARTS = ("trace_s", "lower_s", "compile_s", "retrieve_s")
_NESTED_EPS_S = 1e-4
_now = time.perf_counter   # the listener's clock (a test gives it its own)


class BootLedger:
    """One boot: its spans, what JAX reported inside them, and the series
    they end as. ``serving(metrics)`` closes it; the JAX listener outlives
    that.

    Every span is a ``Span`` this ledger opens and closes itself and hands
    to ``Tracer.record`` at ``serving()``. None of them is ever the
    context's current span (``tracing._CURRENT``): a boot runs on the event
    loop's own context, and every task and handle made while a phase was
    current — the listening socket's reader among them — would carry the
    boot's trace into the requests it serves."""

    def __init__(self, service: str = "worker",
                 start_epoch: float | None = None):
        if start_epoch is None:
            start_epoch = vitals.read_start_epoch()
        if start_epoch is None or not 0.0 <= _IMPORTED - start_epoch < 3600.0:
            start_epoch = _IMPORTED
        self.service = service
        self.spans: list[Span] = []   # closed spans, as they closed
        self.is_serving = False
        # ``{"seconds": Counter, "cache": Counter}`` once ``serving()`` has
        # named the registry; what was counted before waits in ``_pending``.
        self._counters = None
        self._lock = threading.Lock()
        self._pending: list[tuple[str, float, dict]] = []
        self._cpu0: dict[str, float] = {}   # open span → CPU seconds at open
        # By thread: the program span it has open, and the JAX events booked
        # last, ``(start, seconds)`` — what a later event that encloses them
        # has to leave out.
        self._local = threading.local()
        # The warmed programs by how the boot came by them, and the seconds
        # the loaded ones took to load.
        self.programs = {"loaded": 0, "built": 0}
        self.load_s = 0.0
        # Both began with the process: their CPU seconds count from 0.
        self.root = self._open_span("boot", None, start_epoch, cpu0=0.0)
        self._phase = self._open_span("boot.import", self.root, start_epoch,
                                      cpu0=0.0)

    # -- spans ---------------------------------------------------------------

    def _open_span(self, name: str, parent: Span | None, start: float,
                   cpu0: float | None = None, **attrs) -> Span:
        span = Span(name=name, service=self.service,
                    trace_id=(parent.trace_id if parent
                              else tracing._new_trace_id()),
                    span_id=tracing._new_span_id(),
                    parent_id=parent.span_id if parent else None,
                    start=start, attrs=attrs)
        self._cpu0[span.span_id] = (time.process_time() if cpu0 is None
                                    else cpu0)
        return span

    def _close_span(self, span: Span, end: float) -> None:
        """Wall and CPU seconds, and JAX's parts to a tenth of a millisecond
        (they were added up unrounded)."""
        span.duration = end - span.start
        span.attrs["cpu_s"] = round(
            time.process_time() - self._cpu0.pop(span.span_id), 4)
        for part in _PARTS:
            if part in span.attrs:
                span.attrs[part] = round(span.attrs[part], 4)
        self.spans.append(span)

    def enter(self, phase: str, **attrs) -> Span:
        """Close the phase that is open and open ``boot.<phase>`` at the
        same instant: every second of a boot belongs to one phase."""
        if phase not in PHASES:
            raise ValueError(f"unknown boot phase {phase!r}")
        if not attrs and self._phase.name == "boot." + phase:
            return self._phase   # already there: no empty span beside it
        now = time.time()
        self._close_span(self._phase, now)
        self._phase = self._open_span("boot." + phase, self.root, now,
                                      **attrs)
        return self._phase

    def note(self, **attrs) -> None:
        """Attributes for the phase that is open."""
        self._phase.attrs.update(attrs)

    @contextlib.contextmanager
    def program(self, program: str, **attrs):
        """One program call of a warm-up, under the phase that is open
        (``boot.warm``): a ``boot.warm.program`` span whose ``run_s`` is its
        wall less what JAX booked to it, and the same region on the
        profiler's clock."""
        span = self._open_span("boot.warm.program", self._phase, time.time(),
                               program=program, **attrs)
        self._local.program = span
        try:
            with device_trace("ai4e.boot.warm", program=program, **attrs):
                yield span
        finally:
            self._local.program = None
            self._close_span(span, time.time())
            span.attrs["run_s"] = round(
                span.duration - sum(span.attrs.get(p, 0.0) for p in _PARTS)
                - span.attrs.get("load_s", 0.0), 4)
            if span.attrs.get("loaded") or span.attrs.get("built"):
                span.attrs["outcome"] = ("built" if span.attrs.get("built")
                                         else "loaded")

    def obtained(self, outcome: str, load_s: float = 0.0) -> None:
        """A warm-up call came by one of its programs: ``loaded`` from the
        store of executables in ``load_s`` seconds, or ``built``. Counted
        for the boot, and on the program span this thread has open."""
        with self._lock:
            self.programs[outcome] += 1
            self.load_s += load_s
            span = getattr(self._local, "program", None)
            if span is not None:
                span.attrs[outcome] = span.attrs.get(outcome, 0) + 1
                if load_s:
                    span.attrs["load_s"] = round(
                        span.attrs.get("load_s", 0.0) + load_s, 4)

    # -- JAX's events ----------------------------------------------------------

    def _span_of_thread(self) -> Span | None:
        """The program span this thread has open; else — the phase's own
        work, a pool thread of the batch warm-up — the phase. None once the
        boot is closed."""
        if self.is_serving:
            return None
        return getattr(self._local, "program", None) or self._phase

    def _count(self, kind: str, amount: float, **labels) -> None:
        labels["when"] = "serving" if self.is_serving else "boot"
        with self._lock:
            if self._counters is None:
                self._pending.append((kind, amount, labels))
                return
        self._counters[kind].inc(amount, **labels)

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        attr, stage = _STAGES[event]
        # JAX reports an event when it ends, enclosed events first: the
        # seconds of those that began inside this one are already booked.
        start = _now() - seconds
        booked = getattr(self._local, "booked", None)
        if booked is None:
            booked = self._local.booked = deque(maxlen=4096)
        inner = 0.0
        while booked and booked[-1][0] >= start - _NESTED_EPS_S:
            inner += booked.pop()[1]
        booked.append((start, seconds))
        own = max(0.0, seconds - inner)
        span = self._span_of_thread()
        if span is not None:
            with self._lock:
                span.attrs[attr] = span.attrs.get(attr, 0.0) + own
        self._count("seconds", own, stage=stage)
        if self.is_serving and stage == "backend":
            log.warning("compiled while serving: %s took the backend %.3fs "
                        "(ai4e_jax_compile_seconds_total{when=\"serving\"})",
                        kw.get("fun_name", "?"), seconds)

    def on_event(self, event: str, **kw) -> None:
        attr, result = _CACHE[event]
        span = self._span_of_thread()
        if span is not None:
            with self._lock:
                span.attrs[attr] = span.attrs.get(attr, 0) + 1
        self._count("cache", 1.0, result=result)

    # -- the end of a boot -----------------------------------------------------

    def phase_seconds(self) -> dict[str, tuple[float, float]]:
        """``{phase: (wall, cpu)}`` of the closed spans, every phase and
        ``total``."""
        out = {phase: [0.0, 0.0] for phase in PHASES}
        for span in self.spans:
            phase = span.name.removeprefix("boot.")
            if phase in out:
                out[phase][0] += span.duration
                out[phase][1] += span.attrs.get("cpu_s", 0.0)
        out["total"] = [self.root.duration, self.root.attrs.get("cpu_s", 0.0)]
        return {phase: tuple(v) for phase, v in out.items()}

    def serving(self, metrics) -> None:
        """The server accepts: close the boot, publish its series on
        ``metrics`` (the registry the worker's ``/metrics`` renders), hand
        the spans to the process's tracer and log the summary."""
        if self.is_serving:
            return
        now = time.time()
        self._close_span(self._phase, now)
        self._close_span(self.root, now)
        self.is_serving = True
        wall = metrics.gauge(
            "ai4e_boot_seconds", "Wall seconds of this worker's start, by "
            "phase (process start to serving; set once)")
        cpu = metrics.gauge(
            "ai4e_boot_cpu_seconds", "CPU seconds of this process over the "
            "same phases (set once)")
        counters = {
            "seconds": metrics.counter(
                "ai4e_jax_compile_seconds_total", "Seconds JAX reported "
                "tracing, lowering, compiling and retrieving programs, each "
                "second once"),
            "cache": metrics.counter(
                "ai4e_jax_compile_cache_total", "Persistent compile cache "
                "hits and misses as JAX reported them")}
        programs = metrics.counter(
            "ai4e_boot_programs_total", "Programs this worker's start ran "
            "before serving, by how it came by them: loaded from the store "
            "of executables, or built (traced, lowered, compiled)")
        for outcome, count in self.programs.items():
            programs.inc(count, outcome=outcome)
        metrics.counter(
            "ai4e_boot_program_load_seconds_total", "Seconds this worker's "
            "start spent loading stored executables").inc(self.load_s)
        phases = self.phase_seconds()
        for phase, (wall_s, cpu_s) in phases.items():
            wall.set(wall_s, phase=phase)
            cpu.set(cpu_s, phase=phase)
        with self._lock:
            self._counters, pending = counters, self._pending
            self._pending = []
        # Every series of the boot is there from now on, a part that never
        # happened as 0: a reader tells "none" from "no such program".
        pending += [("seconds", 0.0, {"stage": stage, "when": "boot"})
                    for _, stage in _STAGES.values()]
        pending += [("cache", 0.0, {"result": result, "when": "boot"})
                    for _, result in _CACHE.values()]
        for kind, amount, labels in pending:
            counters[kind].inc(amount, **labels)
        # One trace, sampled as a whole, in start order: the root before
        # the phase that began with it.
        tracer = tracing.get_tracer()
        rate = tracer._effective_sample_rate()
        sampled = rate > 0.0 and tracing._sample(self.root.trace_id, rate)
        for span in sorted(self.spans, key=lambda s: (
                s.start, s.parent_id is not None)):
            tracer.record(span, sampled)
        log.info("%s", self.summary(phases))

    def summary(self, phases=None) -> str:
        """One line a phase — wall, CPU, what JAX booked inside it — and
        one a warmed program with its parts and the cache's verdict."""
        phases = phases or self.phase_seconds()
        spans = self.spans
        top = {s.span_id: s.name.removeprefix("boot.") for s in spans
               if s.parent_id == self.root.span_id}
        jax_s = {phase: [0.0] * len(_PARTS) for phase in PHASES}
        for span in spans:
            phase = top.get(span.span_id) or top.get(span.parent_id)
            if phase in jax_s:
                for i, part in enumerate(_PARTS):
                    jax_s[phase][i] += span.attrs.get(part, 0.0)
        parts = "trace %.2f lower %.2f compile %.2f retrieve %.2f"
        lines = ["boot: %.1fs from process start to serving (cpu %.1fs), "
                 "%d programs loaded in %.2fs, %d built, trace %s" % (
                     *phases["total"], self.programs["loaded"], self.load_s,
                     self.programs["built"], self.root.trace_id)]
        for phase in PHASES:
            lines.append(("  %-13s %7.2fs  cpu %7.2fs  " + parts) % (
                phase, *phases[phase], *jax_s[phase]) + "".join(
                    _what(s) for s in spans if top.get(s.span_id) == phase))
        for span in spans:
            if span.name != "boot.warm.program":
                continue
            a = span.attrs
            what = " ".join(f"{k}={a[k]}" for k in ("bucket", "bound", "feed")
                            if k in a)
            lines.append(
                ("  warm %-8s %-24s %6.2fs  cpu %6.2fs  " + parts
                 + " load %.2f run %.2f  hit %d miss %d  %s") % (
                    a.get("program", "?"), what, span.duration,
                    a.get("cpu_s", 0.0), *(a.get(p, 0.0) for p in _PARTS),
                    a.get("load_s", 0.0), a.get("run_s", 0.0),
                    a.get("cache_hits", 0), a.get("cache_misses", 0),
                    a.get("outcome", "ran")))
        return "\n".join(lines)


def _what(span: Span) -> str:
    """What a phase's span was about, for its line of the summary: the
    model and its bytes, a batch model's warm-up seconds."""
    a = span.attrs
    size = a.get("param_bytes", a.get("bytes"))
    out = ""
    if "model" in a and size is not None:
        out = "  %s %.2f GB" % (a["model"], size / 1e9)
    for model, seconds in a.get("model_s", {}).items():
        out += "  %s %.1fs" % (model, seconds)
    return out


# -- the process's ledger ------------------------------------------------------

_ACTIVE: BootLedger | None = None
_listening = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    ledger = _ACTIVE
    if ledger is None or event not in _STAGES:
        return
    try:
        ledger.on_duration(event, seconds, **kw)
    except Exception:  # noqa: BLE001 — a listener must not break a compile
        log.exception("boot ledger: dropped %s", event)


def _on_event(event: str, **kw) -> None:
    ledger = _ACTIVE
    if ledger is None or event not in _CACHE:
        return
    try:
        ledger.on_event(event, **kw)
    except Exception:  # noqa: BLE001 — a listener must not break a compile
        log.exception("boot ledger: dropped %s", event)


def begin(service: str = "worker",
          start_epoch: float | None = None) -> BootLedger:
    """Start this process's ledger (the ``boot`` root and its import phase
    are open from the process's own start) and register the JAX listener,
    once a process. Importing ``jax.monitoring`` here is part of what the
    import phase measures."""
    global _ACTIVE, _listening
    _ACTIVE = BootLedger(service, start_epoch)
    if not _listening:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return _ACTIVE


def active() -> BootLedger | None:
    """The ledger of a boot still under way; None before ``begin`` and
    from ``serving`` on."""
    ledger = _ACTIVE
    return ledger if ledger is not None and not ledger.is_serving else None


def enter(phase: str, **attrs) -> None:
    ledger = active()
    if ledger is not None:
        ledger.enter(phase, **attrs)


def note(**attrs) -> None:
    ledger = active()
    if ledger is not None:
        ledger.note(**attrs)


_NO_SPAN = contextlib.nullcontext()


def program(program: str, **attrs):
    ledger = active()
    return _NO_SPAN if ledger is None else ledger.program(program, **attrs)


def obtained(outcome: str, load_s: float = 0.0) -> None:
    ledger = active()
    if ledger is not None:
        ledger.obtained(outcome, load_s)


def serving(metrics) -> None:
    ledger = active()
    if ledger is not None:
        ledger.serving(metrics)
