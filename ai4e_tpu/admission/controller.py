"""Adaptive concurrency control — the limit that replaces fixed constants.

The reference's only overload story is a static per-endpoint thread cap with
503 backpressure (``ai4e_service.py:116-133``); our port reproduced that
shape with fixed knobs (``submit_concurrency=64``, a hand-picked
``dispatcher_concurrency``, an unbounded gateway sync proxy). A static cap
is wrong in both directions: too low and the device idles under headroom,
too high and queueing delay eats every deadline the moment latency shifts
(a checkpoint reload, a slow backend, a noisy neighbor).

``GradientLimiter`` is a latency-gradient AIMD limiter (the
Netflix-concurrency-limits / TCP-Vegas family): it tracks the observed
minimum RTT as the no-load baseline, compares the recent sample RTT
against it, and resizes the limit —

- sample ≈ baseline (headroom): additive increase, ``+≈√limit`` per
  update, so probing is gentle at small limits and meaningful at large;
- sample ≫ baseline (queueing): multiplicative decrease proportional to
  the gradient ``baseline·tolerance / sample``;
- Little's-law clamp: the limit never grows past twice the concurrency
  actually observed in flight — an idle scope cannot ratchet its cap to
  the maximum and then dump a latency cliff on the first burst.

``AdmissionController`` owns one limiter per SCOPE (the gateway's sync
proxy; each dispatcher queue), applies limit changes to registered targets
(``Gateway`` sync cap, ``Dispatcher.set_concurrency``), estimates the
platform's drain rate from the task store's terminal transitions (the
``Retry-After`` every shed response carries — computed, not hardcoded),
and exports the ``ai4e_admission_*`` metric family including goodput.
"""

from __future__ import annotations

import logging
import math
import threading
import time

from ..metrics import DEFAULT_REGISTRY, MetricsRegistry
from .deadline import drain_retry_after, priority_name, remaining_s
from .shedder import PriorityShedder

log = logging.getLogger("ai4e_tpu.admission")


class DecayingRate:
    """Exponentially decayed event rate (events/second).

    ``on_event`` folds ``n`` events in with time-decay ``tau``; at a steady
    arrival rate r the estimate converges to r. Cheap (O(1), no buckets)
    and thread-safe — terminal transitions arrive from whatever thread ran
    the store upsert."""

    def __init__(self, tau_s: float = 10.0):
        self.tau = tau_s
        self._rate = 0.0
        self._t: float | None = None
        self._lock = threading.Lock()

    def on_event(self, n: float = 1.0, now: float | None = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._t is not None:
                self._rate *= math.exp(-(now - self._t) / self.tau)
            self._t = now
            self._rate += n / self.tau

    def rate(self, now: float | None = None) -> float:
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._t is None:
                return 0.0
            return self._rate * math.exp(-(now - self._t) / self.tau)


class GradientLimiter:
    """Latency-gradient AIMD concurrency limit (see module docstring).

    Updates are sample-window driven (every ``window`` observations), so
    tests can drive convergence deterministically and a dead-quiet scope
    simply keeps its last limit — no background task, no timers."""

    def __init__(self, initial: int = 8, min_limit: int = 1,
                 max_limit: int = 256, window: int = 16,
                 tolerance: float = 2.0, smoothing: float = 0.3):
        if not (0 < min_limit <= initial <= max_limit):
            raise ValueError(
                f"need min <= initial <= max, got {min_limit}/{initial}/"
                f"{max_limit}")
        self.min_limit = min_limit
        self.max_limit = max_limit
        self.window = max(1, window)
        self.tolerance = tolerance
        self.smoothing = smoothing
        self._limit = float(initial)
        self._samples: list[float] = []
        self._peak_inflight = 0
        # No-load RTT baseline: smallest sample seen, aged ~2%/update so a
        # permanent regime change (new model, new link) can re-learn rather
        # than comparing against a baseline no request will ever hit again.
        self._min_rtt: float | None = None

    @property
    def limit(self) -> int:
        return max(self.min_limit, int(self._limit))

    def observe(self, rtt_s: float, inflight: int) -> bool:
        """Record one completed request's RTT at ``inflight`` concurrency.
        Returns True when the limit value changed (callers re-apply targets
        only then)."""
        if rtt_s < 0:
            return False
        self._samples.append(rtt_s)
        self._peak_inflight = max(self._peak_inflight, inflight)
        if len(self._samples) < self.window:
            return False
        return self._update()

    def backoff(self, factor: float = 0.8) -> bool:
        """Out-of-band multiplicative decrease — explicit backpressure
        (429/503 from a backend) is a stronger signal than latency and
        must not wait out a sample window."""
        before = self.limit
        self._limit = max(float(self.min_limit), self._limit * factor)
        return self.limit != before

    def _update(self) -> bool:
        samples = sorted(self._samples)
        self._samples.clear()
        peak, self._peak_inflight = self._peak_inflight, 0
        sample_rtt = samples[len(samples) // 2]  # median: spike-robust
        if self._min_rtt is None:
            self._min_rtt = sample_rtt
        else:
            self._min_rtt = min(self._min_rtt * 1.02, sample_rtt)
        before = self.limit
        allowance = math.sqrt(self._limit)
        target = self._min_rtt * self.tolerance
        if sample_rtt <= target or sample_rtt <= 0:
            # Headroom: additive increase.
            new = self._limit + allowance
        else:
            # Queueing: shrink toward gradient × limit (multiplicative),
            # keeping the queue allowance so the limit can re-probe.
            gradient = max(0.25, target / sample_rtt)
            new = self._limit * gradient + allowance
        # Little's-law clamp: concurrency beyond what the offered load
        # actually uses is pure latency headroom for the next burst to
        # burn — cap growth at 2× the observed in-flight peak.
        if peak > 0:
            new = min(new, 2.0 * peak + allowance)
        self._limit = min(float(self.max_limit),
                          max(float(self.min_limit),
                              (1 - self.smoothing) * self._limit
                              + self.smoothing * new))
        return self.limit != before


class AdmissionScope:
    """One limited surface (the gateway sync proxy, one dispatcher queue):
    a limiter + its in-flight count + the targets its limit drives."""

    def __init__(self, name: str, controller: "AdmissionController",
                 limiter: GradientLimiter):
        self.name = name
        self._controller = controller
        self.limiter = limiter
        self.inflight = 0
        self._targets: list = []

    @property
    def limit(self) -> int:
        return self.limiter.limit

    def add_target(self, apply_fn) -> None:
        """``apply_fn(limit)`` is invoked on every limit change (and once
        at registration, so a target never runs at a stale constant)."""
        self._targets.append(apply_fn)
        self._apply(apply_fn)

    def try_acquire(self, priority: int) -> float | None:
        """Admit one request at ``priority``: None, and the caller MUST
        ``release()``; or the computed Retry-After seconds when the
        shedder refuses the class at the current occupancy."""
        retry_after = self._controller.shedder.check(
            priority, self.inflight, self.limit,
            drain_rate=self._controller.drain_rate())
        if retry_after is not None:
            return retry_after
        self.inflight += 1
        return None

    def release(self) -> None:
        self.inflight = max(0, self.inflight - 1)

    def observe(self, rtt_s: float, inflight: int | None = None) -> None:
        changed = self.limiter.observe(
            rtt_s, self.inflight if inflight is None else inflight)
        if changed:
            self._apply_all()
        self._controller._limit_gauge.set(self.limit, scope=self.name)

    def backoff(self) -> None:
        if self.limiter.backoff():
            self._apply_all()
            self._controller._limit_gauge.set(self.limit, scope=self.name)

    def _apply_all(self) -> None:
        for fn in self._targets:
            self._apply(fn)

    def _apply(self, fn) -> None:
        try:
            fn(self.limit)
        except Exception:  # noqa: BLE001 — a target must not kill admission
            log.exception("admission target for scope %s failed", self.name)


class AdmissionController:
    """The platform's admission brain (one per assembly, opt-in via
    ``PlatformConfig(admission=True)``)."""

    # Scope names the assembly wires (public so tests/docs agree).
    SYNC_SCOPE = "gateway_sync"

    def __init__(self, metrics: MetricsRegistry | None = None,
                 min_limit: int = 1, max_limit: int = 256,
                 initial_limit: int = 8, max_backlog: int = 1024,
                 shedder: PriorityShedder | None = None,
                 drain_tau_s: float = 10.0):
        self.metrics = metrics or DEFAULT_REGISTRY
        if not (0 < min_limit <= initial_limit <= max_limit):
            # Scopes are created lazily (first request); an inconsistent
            # triple must fail HERE, at assembly, not as a 500 inside the
            # first sync handler that touches the limiter.
            raise ValueError(
                f"admission limits need 0 < min <= initial <= max, got "
                f"min={min_limit} initial={initial_limit} max={max_limit}")
        self.min_limit = min_limit
        self.max_limit = max_limit
        self.initial_limit = initial_limit
        self.max_backlog = max_backlog
        self.shedder = shedder or PriorityShedder()
        self._scopes: dict[str, AdmissionScope] = {}
        self._drain = DecayingRate(tau_s=drain_tau_s)
        self._arrivals = DecayingRate(tau_s=drain_tau_s)
        self._tau_s = drain_tau_s
        # Per-route arrival/drain estimators (keyed by endpoint path,
        # populated lazily by the store listener): the predictive
        # autoscaler scales ONE route's dispatchers, so it must read
        # THAT route's imbalance — the platform-global rates above would
        # attribute a flooded route's growth to every idle route's
        # scaler (bounded: one pair per registered endpoint).
        self._route_arrivals: dict[str, DecayingRate] = {}
        self._route_drains: dict[str, DecayingRate] = {}
        # Degradation ladder (orchestration/ladder.py); None → no brownout
        # modes, the pre-orchestration shedder behavior untouched. Set via
        # set_ladder (the platform assembly wires it) and consulted
        # FIRST on every admission decision — a declared brownout
        # outranks per-request occupancy math.
        self._ladder = None
        self._shed_total = self.metrics.counter(
            "ai4e_admission_shed_total",
            "Requests refused under pressure, by hop/priority")
        self._expired_total = self.metrics.counter(
            "ai4e_admission_expired_total",
            "Requests dropped on deadline expiry, by hop/priority")
        self._limit_gauge = self.metrics.gauge(
            "ai4e_admission_limit", "Current adaptive concurrency limit")
        self._goodput_total = self.metrics.counter(
            "ai4e_admission_goodput_total",
            "Terminal completions by deadline outcome")
        self._drain_gauge = self.metrics.gauge(
            "ai4e_admission_drain_rate",
            "Estimated terminal transitions per second")
        self._arrival_gauge = self.metrics.gauge(
            "ai4e_admission_arrival_rate",
            "Estimated task creations per second (predictive-scaling "
            "numerator beside the drain rate)")

    # -- scopes ------------------------------------------------------------

    def scope(self, name: str) -> AdmissionScope:
        sc = self._scopes.get(name)
        if sc is None:
            sc = self._scopes[name] = AdmissionScope(
                name, self,
                GradientLimiter(initial=self.initial_limit,
                                min_limit=self.min_limit,
                                max_limit=self.max_limit))
            self._limit_gauge.set(sc.limit, scope=name)
        return sc

    def add_target(self, scope_name: str, apply_fn) -> None:
        self.scope(scope_name).add_target(apply_fn)

    # -- shed/expiry accounting (every hop funnels through these) ----------

    def note_shed(self, hop: str, priority: int) -> None:
        self._shed_total.inc(hop=hop, priority=priority_name(priority))

    def note_expired(self, hop: str, priority: int) -> None:
        self._expired_total.inc(hop=hop, priority=priority_name(priority))

    # -- drain rate / Retry-After ------------------------------------------

    def on_drain_event(self, n: float = 1.0) -> None:
        self._drain.on_event(n)

    def drain_rate(self) -> float:
        rate = self._drain.rate()
        self._drain_gauge.set(rate)
        return rate

    def retry_after_s(self, excess: float = 1.0) -> float:
        """Seconds until roughly ``excess`` units of backlog should have
        drained — the Retry-After on shed/standby responses (the shared
        ``drain_retry_after`` policy)."""
        return drain_retry_after(excess, self.drain_rate())

    def arrival_rate(self, route: str | None = None) -> float:
        """Decayed task-creation rate — paired with ``drain_rate`` this is
        the queue-growth projection the predictive autoscaler acts on
        (``scaling.predictive_signal``). ``route`` (an endpoint path)
        narrows to that route's own estimator; None is the platform-wide
        rate (and updates the gauge)."""
        if route is not None:
            est = self._route_arrivals.get(route)
            return est.rate() if est is not None else 0.0
        rate = self._arrivals.rate()
        self._arrival_gauge.set(rate)
        return rate

    def route_drain_rate(self, route: str) -> float:
        """One route's decayed terminal-transition rate (the per-route
        counterpart of ``drain_rate``, which stays platform-wide — it
        feeds Retry-After, a whole-platform statement)."""
        est = self._route_drains.get(route)
        return est.rate() if est is not None else 0.0

    def _route_rate(self, table: dict, route: str) -> DecayingRate:
        est = table.get(route)
        if est is None:
            est = table[route] = DecayingRate(tau_s=self._tau_s)
        return est

    # -- degradation ladder (orchestration) --------------------------------

    def set_ladder(self, ladder) -> None:
        """Attach (or clear with None) the degradation ladder: admission
        decisions consult it first, and the store listener feeds it
        actual deadline outcomes (docs/orchestration.md)."""
        self._ladder = ladder

    def brownout_refusal(self, priority: int) -> tuple[float, str] | None:
        """``(retry_after_s, mode)`` when the ladder refuses this class
        right now, else None. The sync proxy calls this beside
        ``try_acquire``; the async edge gets the same consult inside
        ``shed_async``."""
        if self._ladder is None:
            return None
        mode = self._ladder.refuse(priority)
        if mode is None:
            return None
        return self.retry_after_s(), mode

    # -- async-edge admission ----------------------------------------------

    def shed_async(self, priority: int, backlog: int,
                   deadline_at: float = 0.0
                   ) -> tuple[float, str] | None:
        """Edge decision for the async task-creation path: None to admit,
        else ``(retry_after_s, why)``.

        Three tests, cheapest first:
        - brownout — a declared ladder mode refusing this class outranks
          any per-request math (the ladder already saw sustained
          predicted-miss pressure);
        - class pressure — the backlog (created-set depth for the route)
          against this class's share of ``max_backlog``, lowest priority
          refused first (the shedder's fractions);
        - deadline feasibility — with a deadline and an established drain
          rate, a predicted queue wait beyond the remaining budget means
          the task would expire in the queue; refusing NOW costs the
          client one cheap 429 instead of a full transport round trip
          ending in an expired record."""
        brown = self.brownout_refusal(priority)
        if brown is not None:
            return brown[0], "brownout"
        retry_after = self.shedder.check(priority, backlog, self.max_backlog,
                                         drain_rate=self.drain_rate())
        if retry_after is not None:
            return retry_after, "pressure"
        if deadline_at and backlog >= 8:
            rate = self.drain_rate()
            if rate > 1e-9 and backlog / rate > remaining_s(deadline_at):
                return self.retry_after_s(), "deadline"
        return None

    # -- goodput wiring -----------------------------------------------------

    def attach_store(self, store) -> None:
        """Subscribe to the task store's change feed (the same feed the
        gateway's long-poll waiters and the result cache ride): every
        terminal transition is a drain event for the Retry-After
        estimator, and completed tasks score goodput by whether they beat
        their deadline (``no_deadline`` kept separate so the ratio stays
        meaningful for deadline-carrying traffic)."""
        from ..taskstore import TaskStatus, endpoint_path

        def on_task_change(task) -> None:
            status = task.canonical_status
            if status not in TaskStatus.TERMINAL:
                if task.status == TaskStatus.CREATED:
                    # The RAW "created" status is stamped exactly once, at
                    # creation (requeues/backpressure rewrites carry
                    # provenance prose) — the arrival-rate event for the
                    # predictive scaler, platform-wide and per route. The
                    # gauge updates HERE: production readers use the
                    # per-route form of arrival_rate, which must not be
                    # the only thing keeping the platform-wide gauge live.
                    self._arrivals.on_event()
                    self._arrival_gauge.set(self._arrivals.rate())
                    self._route_rate(self._route_arrivals,
                                     endpoint_path(task.endpoint)).on_event()
                return
            self.on_drain_event()
            self._route_rate(self._route_drains,
                             endpoint_path(task.endpoint)).on_event()
            deadline_at = getattr(task, "deadline_at", 0.0)
            if status != TaskStatus.COMPLETED:
                if (self._ladder is not None and deadline_at
                        and status == TaskStatus.EXPIRED):
                    # Shed on its deadline somewhere downstream — actual
                    # miss evidence for the brownout ladder.
                    self._ladder.note(miss=True)
                return
            if not deadline_at:
                outcome = "no_deadline"
            elif time.time() <= deadline_at:
                outcome = "in_deadline"
            else:
                outcome = "late"
            self._goodput_total.inc(outcome=outcome)
            if self._ladder is not None and deadline_at:
                self._ladder.note(miss=(outcome == "late"))

        store.add_listener(on_task_change)
