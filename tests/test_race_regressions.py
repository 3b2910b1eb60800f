"""Interleaving regression suite over the platform's hot critical sections
(docs/concurrency.md) — the ai4e-race dynamic prong's "first run".

Three layers, all deterministic (fixed seed, virtual clock):

- **regressions for the stale-guard defects AIL007 found and this PR
  fixed** (dispatcher dead-letter clobber, cache-complete clobber,
  permanent-fail clobber): the FIXED code passes every schedule in the
  budget; for the two method-sized defects a verbatim pre-fix revert
  (taken from the PR 4 tree) is demonstrated caught by the explorer;
- **replays of the PR 3/PR 4 hand-found races on clean reverts**
  (completed→expired clobber, push ``_forward`` double execution, the
  half-open probe-slot leak): each pre-fix body, verbatim from git
  history, is caught within the schedule budget while current code runs
  race-free under the same budget;
- **clean drives over the remaining hot sections** (taskstore
  reaper/redrive vs completion, rescache single-flight + generation
  fencing, breaker transitions, ``GradientLimiter``) — the sections whose
  first explorer run found nothing, pinned so refactors keep it that way;

plus the documentation test for the REMOTE-store residual window
(``TracedTaskManager(hop=True)``): probe-then-write over an HTTP hop has
an irreducible one-suspension window — the accepted platform contract
whose cure is the store's atomic conditional verbs — and this suite
proves both halves (the window is reachable; ``update_status_if`` closes
it).

The chaos invariant enforced throughout: once a task reaches a terminal
canonical status, that canonical status never changes again — the
client-visible double-outcome ``chaos/invariants.py`` rejects, here
checked per explored schedule instead of per seeded run.
"""

import asyncio
import random

import pytest

aiohttp = pytest.importorskip(
    "aiohttp")  # broker imports it; the race-smoke job installs it (no JAX)

from ai4e_tpu.admission.controller import GradientLimiter
from ai4e_tpu.analysis.race import (TracedTaskManager, explore_interleavings,
                                    yield_point)
from ai4e_tpu.broker.dispatcher import AWAITING_STATUS, Dispatcher
from ai4e_tpu.broker.push import PushEvent, WebhookDispatcher
from ai4e_tpu.broker.queue import EndpointQueue, InMemoryBroker, Message
from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.rescache.cache import ResultCache
from ai4e_tpu.resilience.breaker import CircuitBreaker
from ai4e_tpu.resilience.health import BackendHealth, ResiliencePolicy
from ai4e_tpu.service.task_manager import LocalTaskManager
from ai4e_tpu.taskstore import APITask, InMemoryTaskStore, TaskStatus
from ai4e_tpu.taskstore.reaper import TaskReaper

pytestmark = pytest.mark.race

SEED = 20260803
SCHEDULES = 60


class TerminalInvariant:
    """Once terminal, a task's canonical status never changes again."""

    def __init__(self, store):
        self.violations = []
        # Seed from current state: a task that is ALREADY terminal when
        # the invariant attaches (the lost-response replays) must count
        # any later canonical change as a clobber.
        self._terminal_as = {
            t.task_id: t.canonical_status for t in store.snapshot()
            if t.canonical_status in TaskStatus.TERMINAL}
        store.add_listener(self._on_change)

    def _on_change(self, task):
        prev = self._terminal_as.get(task.task_id)
        cur = task.canonical_status
        if prev is not None and cur != prev:
            self.violations.append(
                (task.task_id, f"{prev} -> {cur} ({task.status!r})"))
        if cur in TaskStatus.TERMINAL:
            self._terminal_as[task.task_id] = cur

    def check(self):
        assert not self.violations, (
            f"terminal status clobbered: {self.violations}")


def _seeded_task(store, broker, task_id="t1", queue="/v1/q",
                 status=TaskStatus.CREATED, deadline_at=0.0):
    task = store.upsert(APITask(task_id=task_id, endpoint=queue + "/op",
                                body=b"payload", publish=False))
    if status != TaskStatus.CREATED:
        store.update_status(task_id, status, status)
    if broker is not None:
        task.deadline_at = deadline_at
        broker.publish(task)
    return task


def _dispatcher(cls, broker, tm, queue="/v1/q", **kw):
    return cls(broker, queue, "http://backend", tm, retry_delay=0.001,
               metrics=MetricsRegistry(), rng=random.Random(0),
               resilience=BackendHealth(metrics=MetricsRegistry()), **kw)


# -- fake HTTP plumbing (the backend hop, with a real suspension) -------------


class _FakeResponse:
    def __init__(self, status):
        self.status = status
        self.headers = {}  # the dispatcher consults X-Draining

    async def read(self):
        return b""


class _FakePost:
    def __init__(self, backend, url):
        self.backend = backend
        self.url = url

    async def __aenter__(self):
        await yield_point()  # the network round trip
        return _FakeResponse(self.backend.execute(self.url))

    async def __aexit__(self, *exc):
        return False


class FakeBackend:
    """Stands in for ``SessionHolder``: ``execute`` runs per POST (counts
    executions, optionally completes the task like a real service shell),
    and the POST awaits one yield point — the suspension a real delivery
    always has."""

    def __init__(self, status=200, on_execute=None):
        self.status = status
        self.on_execute = on_execute
        self.executions = 0

    def execute(self, url):
        self.executions += 1
        if self.on_execute is not None:
            self.on_execute()
        return self.status

    # SessionHolder surface
    async def get(self):
        return self

    # session surface
    def post(self, url, **kwargs):
        return _FakePost(self, url)

    async def close(self):
        pass


class AsyncHopResultStore:
    """Duck-typed result store with the HTTP hop a remote deployment has
    (``HttpResultStore``): one suspension before the write lands."""

    def __init__(self, store):
        self.store = store

    async def set_result(self, task_id, payload,
                         content_type="application/json"):
        await yield_point()
        self.store.set_result(task_id, payload, content_type=content_type)


# -- this PR's fixes: dispatcher stale-guard clobbers -------------------------


class RevertedDeadLetterDispatcher(Dispatcher):
    """``_backpressure`` verbatim from the PR 4 tree — no terminal re-check
    before the dead-letter write (the AIL007 finding)."""

    async def _backpressure(self, msg, backend):
        if self.resilience is not None and await self._suppress_duplicate(msg):
            return
        self._dispatched.inc(outcome="backpressure", queue=self.queue_name,
                             backend=backend)
        await self._try_update(msg.task_id, AWAITING_STATUS,
                               TaskStatus.CREATED)
        await asyncio.sleep(self._redelivery_delay(msg))
        if not self.broker.abandon(msg):
            self._dispatched.inc(outcome="dead_letter",
                                 queue=self.queue_name, backend=backend)
            await self._try_update(msg.task_id, TaskStatus.DEAD_LETTER,
                                   TaskStatus.FAILED)


def _deadletter_scenario(cls):
    def make():
        store = InMemoryTaskStore()
        broker = InMemoryBroker(max_delivery_count=1)
        broker.register_queue("/v1/q")
        tm = TracedTaskManager(LocalTaskManager(store))
        d = _dispatcher(cls, broker, tm)
        _seeded_task(store, broker)
        invariant = TerminalInvariant(store)

        async def deliver():
            msg = await broker.receive("/v1/q", timeout=1.0)
            await d._backpressure(msg, "backend")

        async def completer():
            # The lost-response backend finishing mid-backoff: its own
            # response hop is the one suspension before the completion.
            await yield_point()
            await tm.update_task_status("t1", "completed",
                                        TaskStatus.COMPLETED)

        return [deliver(), completer()], invariant.check

    return make


class TestDeadLetterClobber:
    def test_fixed_dispatcher_race_free(self):
        report = explore_interleavings(_deadletter_scenario(Dispatcher),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_reverted_dispatcher_caught(self):
        report = explore_interleavings(
            _deadletter_scenario(RevertedDeadLetterDispatcher),
            schedules=SCHEDULES, seed=SEED)
        assert not report.ok
        assert "clobbered" in str(report.failures[0].error)


class RevertedCacheCompleteDispatcher(Dispatcher):
    """``_complete_from_cache`` tail verbatim from the PR 4 tree — the
    terminality probe runs BEFORE the result-store hop and is never
    re-checked after it."""

    async def _complete_from_cache(self, msg):
        key = getattr(msg, "cache_key", "")
        if self.result_cache is None or not key:
            return False
        found = self.result_cache.get(key, count=False)
        if found is None:
            return False
        if (self.task_manager is not None
                and await self.task_manager.is_terminal(msg.task_id)):
            self.broker.complete(msg)
            self._dispatched.inc(outcome="duplicate", queue=self.queue_name,
                                 backend="")
            return True
        if self.result_store is None:
            return False
        payload, ctype = found
        import inspect
        res = self.result_store.set_result(msg.task_id, payload,
                                           content_type=ctype)
        if inspect.isawaitable(res):
            await res
        self.broker.complete(msg)
        self._dispatched.inc(outcome="cache_hit", queue=self.queue_name,
                             backend="")
        await self._try_update(msg.task_id, "completed - served from cache",
                               TaskStatus.COMPLETED)
        return True


def _cache_complete_scenario(cls):
    def make():
        store = InMemoryTaskStore()
        broker = InMemoryBroker(max_delivery_count=4)
        broker.register_queue("/v1/q")
        tm = TracedTaskManager(LocalTaskManager(store))
        cache = ResultCache(metrics=MetricsRegistry())
        key = "/v1/q|deadbeef"
        cache.put(key, b"cached-result")
        d = _dispatcher(cls, broker, tm, result_cache=cache,
                        result_store=AsyncHopResultStore(store))
        _seeded_task(store, broker, status=TaskStatus.RUNNING)
        invariant = TerminalInvariant(store)

        async def deliver():
            msg = await broker.receive("/v1/q", timeout=1.0)
            msg.cache_key = key
            await d._complete_from_cache(msg)

        async def reaper_fail():
            # The reaper giving up on the stuck-running task — an atomic
            # conditional transition, exactly as taskstore.reaper does it.
            await yield_point()
            store.update_status_if(
                "t1", TaskStatus.RUNNING,
                "failed - no progress after 3 rescues",
                backend_status=TaskStatus.FAILED)

        return [deliver(), reaper_fail()], invariant.check

    return make


class TestCacheCompleteClobber:
    def test_fixed_dispatcher_race_free(self):
        report = explore_interleavings(_cache_complete_scenario(Dispatcher),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_reverted_dispatcher_caught(self):
        report = explore_interleavings(
            _cache_complete_scenario(RevertedCacheCompleteDispatcher),
            schedules=SCHEDULES, seed=SEED)
        assert not report.ok
        assert "failed -> completed" in str(report.failures[0].error)


class TestPermanentFailClobber:
    """The third AIL007 fix: ``_dispatch_one``'s permanent-failure write
    now re-checks terminality after the POST round trip. No revert replica
    (the method is the whole delivery loop); instead the regression is
    behavioral — remove the re-check and the clobber schedule fails this
    test, and the ``duplicate`` outcome proves the re-check actually fires
    in at least one explored schedule."""

    def test_fixed_dispatch_race_free_and_suppresses(self):
        duplicates = []

        def make():
            store = InMemoryTaskStore()
            broker = InMemoryBroker(max_delivery_count=4)
            broker.register_queue("/v1/q")
            tm = TracedTaskManager(LocalTaskManager(store))
            d = _dispatcher(Dispatcher, broker, tm)
            backend = FakeBackend(status=400)  # permanent-failure class
            d._sessions = backend
            _seeded_task(store, broker)
            invariant = TerminalInvariant(store)

            async def deliver():
                msg = await broker.receive("/v1/q", timeout=1.0)
                await d._dispatch_one(msg)

            async def completer():
                # A concurrent duplicate's execution completing while this
                # attempt's POST is in flight — guarded like the PR 4
                # service shell (probe + write, atomic in-process).
                await yield_point()
                if not await tm.is_terminal("t1"):
                    await tm.update_task_status("t1", "completed",
                                                TaskStatus.COMPLETED)

            def check():
                invariant.check()
                duplicates.append(d._dispatched.value(
                    outcome="duplicate", queue="/v1/q",
                    backend="backend"))

            return [deliver(), completer()], check

        report = explore_interleavings(make, schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()
        # The re-check must have fired (outcome=duplicate) in at least one
        # explored schedule — otherwise the window was never exercised and
        # this test proves nothing.
        assert any(duplicates), "no schedule exercised the re-check window"


# -- PR 3/PR 4 replays on clean reverts ---------------------------------------


class RevertedExpiredDispatcher(Dispatcher):
    """``_drop_expired`` verbatim from the PR 3 tree — no terminality
    probe: a lease-expiry redelivery of a COMPLETED task whose deadline
    passed was stamped ``expired`` (the completed→expired clobber PR 4
    fixed by hand)."""

    async def _drop_expired(self, msg):
        import time as _time
        deadline_at = getattr(msg, "deadline_at", 0.0)
        if not deadline_at or _time.time() < deadline_at:
            return False
        from ai4e_tpu.admission.deadline import expired_status
        self.broker.complete(msg)
        self._dispatched.inc(outcome="expired", queue=self.queue_name,
                             backend="")
        if self.admission is not None:
            self.admission.note_expired("dispatcher",
                                        getattr(msg, "priority", 1))
        await self._try_update(msg.task_id, expired_status("dispatcher"),
                               TaskStatus.EXPIRED)
        return True


def _expired_scenario(cls):
    def make():
        store = InMemoryTaskStore()
        broker = InMemoryBroker(max_delivery_count=4)
        broker.register_queue("/v1/q")
        tm = TracedTaskManager(LocalTaskManager(store))
        d = _dispatcher(cls, broker, tm)
        # The PR 3 incident shape: the task COMPLETED (lost-response
        # execution), then its lease-expiry redelivery pops with the
        # deadline already past.
        _seeded_task(store, broker, status=TaskStatus.COMPLETED,
                     deadline_at=1.0)
        invariant = TerminalInvariant(store)

        async def deliver():
            msg = await broker.receive("/v1/q", timeout=1.0)
            await d._drop_expired(msg)

        return [deliver()], invariant.check

    return make


class TestReplayCompletedExpiredClobber:
    def test_fixed_dispatcher_suppresses_duplicate(self):
        report = explore_interleavings(_expired_scenario(Dispatcher),
                                       schedules=20, seed=SEED)
        assert report.ok, report.describe()

    def test_pr3_revert_caught(self):
        report = explore_interleavings(
            _expired_scenario(RevertedExpiredDispatcher),
            schedules=20, seed=SEED)
        assert not report.ok
        assert "completed -> expired" in str(report.failures[0].error)


class RevertedWebhookDispatcher(WebhookDispatcher):
    """``_forward`` without the retried-delivery terminality suppression —
    the PR 3 tree's webhook (PR 4 added the ``attempts > 1`` guard): a
    retried delivery trailing a lost-response execution re-executed the
    task on the backend."""

    async def _forward(self, event):
        target = self._target_for(event.subject)
        if target is None:
            self._forwarded.inc(outcome="unroutable")
            await self._try_update(
                event.id, f"failed - no backend route for {event.subject}",
                TaskStatus.FAILED)
            return 200
        from urllib.parse import urlparse
        backend = urlparse(target).netloc
        session = await self._sessions.get()
        with self.tracer.span("webhook_dispatch", task_id=event.id) as span:
            headers = {"taskId": event.id,
                       "Content-Type": event.content_type,
                       **self.tracer.headers()}
            async with session.post(target, data=event.data,
                                    headers=headers) as resp:
                status = resp.status
                await resp.read()
            span.attrs["http_status"] = status
        if 200 <= status < 300:
            self._forwarded.inc(outcome="delivered", backend=backend)
            return 200
        self._forwarded.inc(outcome="failed", backend=backend)
        await self._try_update(event.id,
                               f"failed - backend returned {status}",
                               TaskStatus.FAILED)
        return 200


def _forward_scenario(cls):
    def make():
        store = InMemoryTaskStore()
        tm = TracedTaskManager(LocalTaskManager(store))
        wd = cls(tm, metrics=MetricsRegistry())
        wd.add_route("/v1/q", "http://backend")
        _seeded_task(store, None)
        backend = FakeBackend(
            status=200,
            on_execute=lambda: store.update_status(
                "t1", "completed", TaskStatus.COMPLETED))
        wd._sessions = backend

        def event(attempt):
            ev = PushEvent(id="t1", subject="/v1/q/op", data=b"payload")
            ev.attempts = attempt
            return ev

        async def topic_retry():
            # Attempt 1 executes; its response is "lost" upstream, so the
            # topic redelivers as attempt 2 after backoff.
            await wd._forward(event(1))
            await asyncio.sleep(10.0)  # topic backoff (virtual)
            await wd._forward(event(2))

        def check():
            assert backend.executions == 1, (
                f"task executed {backend.executions}x — the retried "
                "delivery re-ran a completed task on the backend")

        return [topic_retry()], check

    return make


class TestReplayPushForwardDoubleExecution:
    def test_fixed_webhook_suppresses_retry_of_completed_task(self):
        report = explore_interleavings(_forward_scenario(WebhookDispatcher),
                                       schedules=20, seed=SEED)
        assert report.ok, report.describe()

    def test_pr3_revert_caught(self):
        report = explore_interleavings(
            _forward_scenario(RevertedWebhookDispatcher),
            schedules=20, seed=SEED)
        assert not report.ok
        assert "executed 2x" in str(report.failures[0].error)


class LeakyBreaker(CircuitBreaker):
    """``available`` without the time-based probe-slot escape — the PR 3
    review find: a probe whose delivery was cancelled before any outcome
    was recorded pinned its slot, ejecting the backend forever."""

    def available(self, now=None):
        if self.state == "closed":
            return True
        now = self._clock() if now is None else now
        if self.state == "open":
            return (now - self._opened_at >= self.recovery_seconds
                    and self._probes_inflight < self.half_open_probes)
        return self._probes_inflight < self.half_open_probes


def _probe_leak_scenario(cls):
    def make():
        clock = [0.0]
        br = cls(failure_threshold=2, recovery_seconds=30.0,
                 clock=lambda: clock[0])

        async def trip_and_vanish():
            br.record_failure()
            await yield_point()
            br.record_failure()          # trips open
            clock[0] += 31.0             # cooldown elapses
            assert br.available()
            br.begin_probe()             # probe dispatched ...
            await yield_point()          # ... and its delivery is
            #                              cancelled: no outcome ever lands.

        async def later_probe():
            await yield_point()
            clock[0] += 62.0             # two more cooldowns of silence

        def check():
            # However the clock advances interleaved: after one more full
            # cooldown of silence past EVERYTHING above, the slot must be
            # free again.
            clock[0] += 31.0
            assert br.available(), (
                "probe slot leaked: backend ejected forever after a "
                "vanished probe")

        return [trip_and_vanish(), later_probe()], check

    return make


class TestReplayHalfOpenProbeSlotLeak:
    def test_fixed_breaker_frees_the_slot_by_time(self):
        report = explore_interleavings(_probe_leak_scenario(CircuitBreaker),
                                       schedules=20, seed=SEED)
        assert report.ok, report.describe()

    def test_pr3_revert_caught(self):
        report = explore_interleavings(_probe_leak_scenario(LeakyBreaker),
                                       schedules=20, seed=SEED)
        assert not report.ok
        assert "leaked" in str(report.failures[0].error)


# -- clean drives over the remaining hot sections -----------------------------


class TestTaskstoreReaperRedrive:
    def test_reaper_rescue_vs_completion_race_free(self):
        def make():
            store = InMemoryTaskStore()
            published = []
            store.set_publisher(published.append)
            tm = TracedTaskManager(LocalTaskManager(store))
            reaper = TaskReaper(store, running_timeout=0.0, interval=3600,
                                metrics=MetricsRegistry())
            _seeded_task(store, None, status=TaskStatus.RUNNING)
            invariant = TerminalInvariant(store)

            async def sweep():
                await yield_point()
                await reaper.sweep()

            async def completer():
                await yield_point()
                await tm.update_task_status("t1", "completed",
                                            TaskStatus.COMPLETED)

            def check():
                invariant.check()
                final = store.get("t1").canonical_status
                if final == TaskStatus.COMPLETED:
                    return  # completion won or survived the requeue
                # The rescue won: the task must be back in CREATED with
                # its replayed body published, never wedged.
                assert final == TaskStatus.CREATED
                assert published

            return [sweep(), completer()], invariant.check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert report.ok, report.describe()

    def test_reaper_give_up_vs_completion_race_free(self):
        def make():
            store = InMemoryTaskStore()
            tm = TracedTaskManager(LocalTaskManager(store))
            reaper = TaskReaper(store, running_timeout=0.0, interval=3600,
                                max_requeues=0, metrics=MetricsRegistry())
            _seeded_task(store, None, status=TaskStatus.RUNNING)
            invariant = TerminalInvariant(store)

            async def sweep():
                await yield_point()
                await reaper.sweep()

            async def completer():
                # Guarded completion (the PR 4 service-shell idiom): the
                # reaper may have failed the task first; an unguarded
                # completed-stamp over it is the bug class, not this
                # fixture's subject.
                await yield_point()
                if not await tm.is_terminal("t1"):
                    await tm.update_task_status("t1", "completed",
                                                TaskStatus.COMPLETED)

            return [sweep(), completer()], invariant.check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert report.ok, report.describe()


class TestRescacheInflight:
    def test_single_flight_has_exactly_one_leader(self):
        def make():
            cache = ResultCache(metrics=MetricsRegistry())
            key = "/v1/q|cafe"
            wins = []

            async def gateway(tid):
                await yield_point()
                if cache.register_inflight(key, tid):
                    wins.append(tid)
                else:
                    assert cache.leader_for(key) is not None

            def check():
                assert len(wins) == 1, f"leaders: {wins}"

            return [gateway("a"), gateway("b")], check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert report.ok, report.describe()

    def test_generation_fencing_refuses_stale_fill(self):
        def make():
            cache = ResultCache(metrics=MetricsRegistry())
            key = "/v1/q|cafe"
            family = "/v1/q"
            captured = {}

            async def leader():
                captured["gen"] = cache.generation(key)
                await yield_point()  # computing on the old weights
                captured["ok"] = cache.put(key, b"result",
                                           if_generation=captured["gen"])

            async def reloader():
                await yield_point()
                cache.invalidate_family(family)

            def check():
                # Whatever the interleaving: a fill that landed must be
                # provably fresh — if the entry is present, no invalidation
                # has advanced the generation since the leader captured it.
                if cache.peek(key):
                    assert cache.generation(key) == captured["gen"], (
                        "stale fill served after invalidation")

            return [leader(), reloader()], check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert report.ok, report.describe()

    def test_fill_inflight_vs_invalidate_race_free(self):
        def make():
            cache = ResultCache(metrics=MetricsRegistry())
            key = "/v1/q|cafe"
            cache.register_inflight(key, "t1")

            async def filler():
                await yield_point()  # the execution
                cache.fill_inflight(key, "t1", b"result")

            async def reloader():
                await yield_point()
                cache.invalidate_family("/v1/q")

            def check():
                # Invalidation after the fill drops the entry; before the
                # fill it clears the registration so the fill refuses.
                # Either way no stale entry AND no orphaned registration
                # blocking the next identical request forever... unless a
                # successful fill already released it.
                assert cache.leader_for(key) is None

            return [filler(), reloader()], check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert report.ok, report.describe()


class TestBreakerTransitions:
    def test_concurrent_delivery_loops_trip_and_recover(self):
        def make():
            clock = [0.0]
            health = BackendHealth(
                ResiliencePolicy(failure_threshold=2, recovery_seconds=5.0),
                metrics=MetricsRegistry(), clock=lambda: clock[0],
                rng=random.Random(0))
            backends = [("http://b", 1)]

            async def failing_loop():
                for _ in range(2):
                    uri = health.pick(backends, None)
                    await yield_point()  # the POST
                    health.record_failure(uri)

            async def probing_loop():
                await yield_point()
                clock[0] += 6.0  # cooldown elapses
                uri = health.pick(backends, None)
                await yield_point()
                health.observe_status(uri, 200)

            def check():
                br = health.breaker_for("http://b")
                assert br.state in ("closed", "open", "half_open")
                assert 0 <= br._probes_inflight <= br.half_open_probes
                # However the loops interleaved, the backend must be
                # reachable again once a success lands or the cooldown
                # passes — never ejected forever.
                clock[0] += 6.0
                assert br.available()

            return [failing_loop(), probing_loop()], check

        report = explore_interleavings(make, schedules=60, seed=SEED)
        assert report.ok, report.describe()


class TestGradientLimiter:
    def test_concurrent_observe_and_backoff_keep_limit_bounded(self):
        def make():
            limiter = GradientLimiter(initial=8, min_limit=1, max_limit=64,
                                      window=4)

            async def observer():
                for rtt in (0.01, 0.02, 0.5, 0.01, 0.01):
                    limiter.observe(rtt, inflight=4)
                    await yield_point()

            async def backer():
                for _ in range(3):
                    await yield_point()
                    limiter.backoff()

            def check():
                assert 1 <= limiter.limit <= 64

            return [observer(), observer(), backer()], check

        report = explore_interleavings(make, schedules=60, seed=SEED)
        assert report.ok, report.describe()


# -- the documented remote-store residual window ------------------------------


class TestRemoteStoreResidualWindow:
    """docs/concurrency.md §"the residual window": over an HTTP store hop,
    probe-then-write is irreducibly non-atomic — one suspension separates
    the probe's answer from the write landing. The platform ACCEPTS that
    window for its probe-guarded cold paths and closes it where it must
    win with the store's atomic conditional verbs. Both halves proven
    here, so the paragraph can't rot."""

    def test_probe_then_write_window_is_reachable_over_a_hop(self):
        def make():
            store = InMemoryTaskStore()
            tm = TracedTaskManager(LocalTaskManager(store), hop=True)
            _seeded_task(store, None, status=TaskStatus.RUNNING)
            invariant = TerminalInvariant(store)

            async def prober_writer():
                if not await tm.is_terminal("t1"):
                    await tm.update_task_status("t1", "expired - deadline",
                                                TaskStatus.EXPIRED)

            async def completer():
                await tm.update_task_status("t1", "completed",
                                            TaskStatus.COMPLETED)

            return [prober_writer(), completer()], invariant.check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert not report.ok, (
            "the documented residual window was not reachable — either the "
            "hop model changed or the docs are now wrong")

    def test_conditional_verb_closes_the_window(self):
        def make():
            store = InMemoryTaskStore()
            tm = TracedTaskManager(LocalTaskManager(store), hop=True)
            _seeded_task(store, None, status=TaskStatus.RUNNING)
            invariant = TerminalInvariant(store)

            async def conditional_writer():
                await yield_point()  # the request hop
                # The store-side atomic verb: transition only if still
                # running (what the HTTP surface's /update-if exposes).
                store.update_status_if("t1", TaskStatus.RUNNING,
                                       "expired - deadline",
                                       backend_status=TaskStatus.EXPIRED)

            async def completer():
                await yield_point()  # its own request hop
                store.update_status_if("t1", TaskStatus.RUNNING,
                                       "completed",
                                       backend_status=TaskStatus.COMPLETED)

            return [conditional_writer(), completer()], invariant.check

        report = explore_interleavings(make, schedules=40, seed=SEED)
        assert report.ok, report.describe()


# ---------------------------------------------------------------------------
# PR 6: sharded-store critical sections (docs/sharding.md)
# ---------------------------------------------------------------------------


class TestRebalanceHandoffRace:
    """The rebalance handoff's stale-owner window: a writer resolves the
    ring, suspends (the hop), and the slot moves before its write lands.
    The store-side ownership fence (``NotOwnerError``, checked under the
    old owner's lock — the same lock the ring flip holds) refuses the
    stale write and the ring re-route lands it on the new owner; with the
    fence disabled, the exact same schedules resurrect the task on the
    old owner — a divergent orphan copy no client read would ever see
    updated again."""

    @staticmethod
    def _scenario(fenced: bool):
        from ai4e_tpu.taskstore import NotOwnerError
        from ai4e_tpu.taskstore.sharding import ShardedTaskStore

        def make():
            store = ShardedTaskStore(2, slots=8)
            if not fenced:
                for g in store.groups:  # the pre-fence world, verbatim
                    g.active.set_write_fence(None)
            store.upsert(APITask(task_id="t-race", endpoint="/v1/q/op",
                                 body=b"b", publish=False))
            slot = store.ring.slot_for("t-race")
            src = store.ring.shard_of_slot(slot)
            dest = 1 - src

            async def stale_writer():
                # Remote-client shape: resolve the owner, hop, write — the
                # requeue/AWAITING upsert every transport cold path makes.
                owner = store.groups[store.ring.shard_for("t-race")].active
                await yield_point()  # the hop the flip can slot into
                retry = APITask(task_id="t-race", endpoint="/v1/q/op",
                                body=b"", status=AWAITING_STATUS,
                                backend_status=TaskStatus.CREATED,
                                publish=False)
                try:
                    owner.upsert(retry)
                except NotOwnerError:
                    # Fenced: re-route via a fresh ring lookup (what the
                    # facade's _route loop does).
                    store.upsert(retry)

            async def mover():
                await yield_point()
                store.move_slot(slot, dest)

            def check():
                src_store = store.groups[src].active
                dest_store = store.groups[dest].active
                assert "t-race" not in src_store._tasks, (
                    "stale-owner write resurrected the task on the old "
                    "owner after the handoff")
                assert dest_store.get("t-race").status == AWAITING_STATUS

            return [stale_writer(), mover()], check

        return make

    def test_fenced_handoff_race_free(self):
        report = explore_interleavings(self._scenario(fenced=True),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_unfenced_replica_caught(self):
        report = explore_interleavings(self._scenario(fenced=False),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the stale-owner window was not reachable without the fence — "
            "either move_slot stopped forgetting the range or the "
            "scenario no longer models the handoff")


class TestFeedAttachRace:
    """The change feed's attach window: a watcher reads a non-terminal
    status, suspends, and the terminal event fires before it attaches.
    ``wait_terminal`` checks the bounded replay map and registers the
    waiter under ONE lock, so the event is either replayed at attach or
    delivered to the future — a replica without the replay check misses
    the wakeup on exactly those schedules and waits out its (virtual)
    timeout."""

    @staticmethod
    def _scenario(feed_cls):
        from ai4e_tpu.taskstore.sharding import ShardedTaskStore

        def make():
            store = ShardedTaskStore(2, slots=8)
            feed = feed_cls(0)
            store.feeds = [feed, feed]  # both shards relay into one feed
            store.upsert(APITask(task_id="t-watch", endpoint="/v1/q/op",
                                 body=b"b", publish=False))
            results = []

            async def watcher():
                # The gateway's long-poll shape: read, then attach.
                record = store.get("t-watch")
                if record.canonical_status in TaskStatus.TERMINAL:
                    results.append(record)  # answered without waiting
                    return
                await yield_point()  # the window the event can fire in
                results.append(await feed.wait_terminal("t-watch", 30.0))

            async def completer():
                await yield_point()
                store.update_status("t-watch", "completed",
                                    TaskStatus.COMPLETED)

            def check():
                assert results and results[0] is not None, (
                    "watcher missed the terminal wakeup")
                assert results[0].canonical_status == "completed"

            return [watcher(), completer()], check

        return make

    def test_feed_attach_race_free(self):
        from ai4e_tpu.taskstore.feed import ShardChangeFeed
        report = explore_interleavings(self._scenario(ShardChangeFeed),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_replay_free_replica_caught(self):
        from ai4e_tpu.taskstore.feed import ShardChangeFeed

        class NoReplayFeed(ShardChangeFeed):
            """wait_terminal WITHOUT the replay-map consult — the naive
            register-then-wait a per-request listener would write."""

            async def wait_terminal(self, task_id, timeout):
                import asyncio as _asyncio
                loop = _asyncio.get_running_loop()
                fut = loop.create_future()
                entry = (loop, fut)
                with self._lock:  # registers, never checks _recent
                    self._waiters[task_id] = self._waiters.get(
                        task_id, frozenset()) | {entry}
                try:
                    return await _asyncio.wait_for(fut, timeout)
                except _asyncio.TimeoutError:
                    return None
                finally:
                    self._drop_waiter(task_id, entry)

        report = explore_interleavings(self._scenario(NoReplayFeed),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the attach-vs-event window was not reachable without the "
            "replay map — the scenario no longer models the race")


# -- PR 7: orchestration check-then-act surfaces (docs/orchestration.md) ------


class TestOrchestrationPlacementVsBreakerTrip:
    """The placement pipeline is estimator-read → decision → POST →
    outcome record: the decision's breaker evidence is one suspension
    stale by the time the outcome lands, and a concurrent delivery loop
    can trip (or recover) the same breaker mid-flight. The invariants a
    schedule must never break: a placement always lands inside the
    backend set, the half-open probe-slot accounting never leaks (the
    PR 3 leak class — a leaked slot ejects a backend forever), and the
    estimator's begin/end in-flight pairing survives every interleaving
    (the dispatcher releases in a finally)."""

    BACKENDS = [("http://tpu", 1.0), ("http://cpu", 1.0)]

    def _make(self):
        from ai4e_tpu.orchestration import Orchestrator, OrchestrationPolicy

        clock = [0.0]
        health = BackendHealth(
            ResiliencePolicy(failure_threshold=2, recovery_seconds=5.0),
            metrics=MetricsRegistry(), clock=lambda: clock[0],
            rng=random.Random(0))
        orch = Orchestrator(
            health,
            policy=OrchestrationPolicy(costs={"cpu": 1.0, "tpu": 3.0}),
            metrics=MetricsRegistry(), clock=lambda: clock[0])
        for _ in range(4):
            orch.observe("http://tpu", 0.01)
            orch.observe("http://cpu", 0.02)
        return clock, health, orch

    def test_placement_vs_trip_race_free(self):
        def make():
            clock, health, orch = self._make()
            placed = []

            async def placing_loop():
                # The dispatcher's attempt shape: place → (suspend: the
                # POST) → outcome, with the estimator's begin/end exactly
                # where _dispatch_one puts them (finally-released).
                for outcome_ok in (True, False):
                    base = orch.place(self.BACKENDS, deadline_at=0.0)
                    placed.append(base)
                    orch.begin(base)
                    try:
                        await yield_point()  # the POST round trip
                        if outcome_ok:
                            health.observe_status(base, 200)
                            orch.observe(base, 0.01)
                        else:
                            health.record_failure(base)
                    finally:
                        orch.end(base)

            async def tripping_loop():
                # A concurrent delivery loop melting the cheap tier: the
                # breaker trips while the placer is mid-POST.
                for _ in range(2):
                    await yield_point()
                    health.record_failure("http://cpu")
                clock[0] += 6.0  # cooldown elapses → half-open probes
                uri = orch.place(self.BACKENDS, deadline_at=0.0)
                await yield_point()
                health.observe_status(uri, 200)

            def check():
                for uri in ("http://tpu", "http://cpu"):
                    br = health.breaker_for(uri)
                    assert 0 <= br._probes_inflight <= br.half_open_probes
                    assert orch.estimator.inflight(uri) == 0, (
                        "estimator in-flight leaked")
                assert set(placed) <= {u for u, _ in self.BACKENDS}
                # However the trip interleaved, the set must stay
                # routable once the cooldown passes (no permanent
                # ejection — the PR 3 slot-leak symptom).
                clock[0] += 6.0
                assert any(health.breaker_for(u).available()
                           for u, _ in self.BACKENDS)

            return [placing_loop(), tripping_loop()], check

        report = explore_interleavings(make, schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()


class TestLadderHysteresisVsMetricsFlush:
    """Ladder step-up racing step-down racing a metrics flush: note()
    arrives from placement (event loop) and from the store-listener
    thread, while /metrics renders mid-transition. Per the
    docs/concurrency.md contract the transition critical section is a
    lock-protected sync block (no suspension points), so every explored
    schedule must observe: level within [0, 4], conservation (up steps −
    down steps == final level), and a flushed gauge that always equals a
    level the ladder actually held."""

    def test_step_up_vs_step_down_vs_flush(self):
        def make():
            from ai4e_tpu.orchestration import DegradationLadder

            clock = [0.0]
            reg = MetricsRegistry()
            ladder = DegradationLadder(up=0.5, down=0.1, hold_s=2.0,
                                       min_rate=0.01, tau_s=5.0,
                                       metrics=reg,
                                       clock=lambda: clock[0])
            seen_levels = []

            async def misser():
                for _ in range(8):
                    clock[0] += 1.0
                    ladder.note(miss=True)
                    seen_levels.append(ladder.level)
                    await yield_point()

            async def recoverer():
                for _ in range(20):
                    clock[0] += 0.5
                    ladder.note(miss=False)
                    seen_levels.append(ladder.level)
                    await yield_point()

            async def flusher():
                for _ in range(4):
                    await yield_point()
                    reg.render_prometheus()  # the metrics scrape
                    gauge = reg.gauge("ai4e_orchestration_ladder_level", "")
                    seen_levels.append(int(gauge.value()))

            def check():
                assert all(0 <= lvl <= 4 for lvl in seen_levels), seen_levels
                counter = reg.counter(
                    "ai4e_orchestration_ladder_transitions_total", "")
                ups = downs = 0
                for _, _, labels, v in counter.collect():
                    if labels.get("direction") == "up":
                        ups += v
                    else:
                        downs += v
                assert ups - downs == ladder.level, (
                    f"transition conservation broken: {ups} up, {downs} "
                    f"down, level {ladder.level}")
                gauge = reg.gauge("ai4e_orchestration_ladder_level", "")
                assert int(gauge.value()) == ladder.level

            return [misser(), recoverer(), flusher()], check

        report = explore_interleavings(make, schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()


class TestLadderSwapVsBatchCut:
    """The derived-ladder swap window (runtime/ladder.py, docs/
    device_path.md): a batch cut reads the servable's ladder tuple
    (``bucket_for``), suspends (the executor hop), and pads to the chosen
    bucket — while the deriver thread compiles a NEW ladder and swaps it
    in. The invariant: no request is ever padded to a bucket that has no
    compiled program. The fixed order — ``prepare_buckets`` warms every
    new bucket, THEN ``apply_ladder`` assigns the tuple (and refuses
    un-executed buckets), with the warm set append-only so old-ladder
    cuts stay compiled — is race-free over the schedule budget; the
    reverted order (assign first, compile after: the naive hot-swap)
    lets a cut pick a bucket whose first call would compile on the
    serving path, and is caught."""

    @staticmethod
    def _scenario(prepare_before_swap: bool):
        def make():
            # Warm set + serving ladder, mirroring ModelRuntime
            # (_executed_shapes is append-only; batch_buckets is swapped
            # in one assignment).
            state = {"ladder": (1, 8), "warm": {1, 8}}
            cold_pads: list[int] = []

            async def cutter():
                # Two cuts racing the swap: each reads the tuple, hops
                # to the executor, then pads — the exact _execute shape.
                for n in (3, 5):
                    ladder = state["ladder"]
                    await yield_point()  # run_in_executor hand-off
                    bucket = next((b for b in ladder if b >= n),
                                  ladder[-1])
                    if bucket not in state["warm"]:
                        cold_pads.append(bucket)
                    await yield_point()

            async def swapper():
                new = (4, 8)
                if prepare_before_swap:
                    for b in new:  # prepare_buckets: warm FIRST…
                        state["warm"].add(b)
                        await yield_point()  # compiles suspend freely
                    state["ladder"] = new  # …then the atomic assignment
                else:
                    state["ladder"] = new  # reverted: assign, then warm
                    await yield_point()
                    for b in new:
                        state["warm"].add(b)
                        await yield_point()

            def check():
                assert not cold_pads, (
                    f"batch padded to bucket(s) {cold_pads} with no "
                    "compiled program — a serving-path compile stall")

            return [cutter(), swapper()], check

        return make

    def test_prepare_then_swap_race_free(self):
        report = explore_interleavings(self._scenario(True),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_swap_before_prepare_caught(self):
        report = explore_interleavings(self._scenario(False),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the assign-before-compile window was not reachable — either "
            "the scenario no longer models the swap or the budget is "
            "too small")


# -- decode engine: KV-cache slot conservation (PR 14) ------------------------
#
# The continuous-batching engine (runtime/decode.py, docs/streaming.md)
# runs four verbs that all touch slot state: join-batch (admission
# prefill), decode-step, expiry-sweep, and hot-reload-invalidate
# (re-prefill). THE invariant: a slot is never double-assigned, never
# leaked, freed exactly once — SlotPool raises SlotError the moment any
# schedule violates it, and check_conservation() audits the end state.
# The engine imports neither JAX nor numpy, so this suite runs in the
# race-smoke job's toolchain-free environment against the REAL engine.

import time as _time

from ai4e_tpu.admission.deadline import DeadlineExceeded
from ai4e_tpu.runtime.decode import DecodeEngine


class _FakeDecodeBackend:
    """Async decode backend: every device call is a real suspension
    (yield_point), so the explorer owns every interleaving window the
    executor-thread hop opens in production."""

    def __init__(self, slots=2, max_len=64, eos_id=None):
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.name = "lm"
        self.params_version = 1
        self.resets = 0

    async def reset_cache(self):
        await yield_point()
        self.resets += 1

    async def prefill_into(self, slot, tokens):
        await yield_point()
        return int(tokens[-1]) + 1

    async def step(self, tokens, positions, active):
        await yield_point()
        return [int(t) + 1 for t in tokens]


class _SplitSweepEngine(DecodeEngine):
    """Verbatim pre-fix expiry sweep: the doomed set is selected, then
    each expiry suspends (publishing the expiry event) BEFORE releasing
    the slot — the guard and the release in different segments, the
    AIL007 shape. A cancel landing in the window retires the sequence
    first; the resumed sweep then releases a slot it no longer holds."""

    async def _tick(self):
        await self._check_reload()
        await self._sweep_split()
        await self._admit()
        await self._step()

    async def _sweep_split(self):
        now = _time.time()
        doomed = [(seq, seq.slot) for seq in self._active.values()
                  if not seq.done and seq.deadline_at
                  and seq.deadline_at <= now]
        for seq, slot in doomed:
            await yield_point()          # pre-fix: emitted the event first
            self._active.pop(slot, None)
            self.pool.release(slot)      # stale guard: freed exactly once?
            seq.slot = None
            seq.done = True
            if not seq.future.done():
                seq.future.set_exception(
                    DeadlineExceeded("decode", seq.deadline_at))


def _decode_drain(engine, results):
    """End-of-run drain: every leftover sequence is retired exactly once
    through the funnel, so an interrupted scenario still lets futures
    resolve and conservation be audited."""
    for seq in (list(engine._active.values()) + list(engine._queue)):
        engine._retire(seq, "cancelled", error=RuntimeError("drained"))
    # A step still in flight holds its riders' slots parked: void it, as
    # the engine's own stop and failure paths do.
    engine._void_launched()
    results["drained"] = True


def _slot_conservation_scenario(engine_cls, ticks=120, gap=6):
    """Join vs decode-step vs expiry-sweep vs cancel vs hot-reload:
    the full verb mix over a 2-slot pool."""

    def make():
        backend = _FakeDecodeBackend(slots=2, max_len=8)
        engine = engine_cls(backend, max_pending=8,
                            metrics=MetricsRegistry())
        results = {}

        async def driver():
            for _ in range(ticks):
                if results.get("stop"):
                    break
                # An idle tick has no suspension point — yield explicitly
                # so submitters are never starved past the tick budget
                # (the drain below would then resolve their futures with
                # the engine never having served them).
                await yield_point()
                await engine._tick()
            _decode_drain(engine, results)

        async def submit(tag, prompt, max_new, **kw):
            try:
                results[tag] = await engine.submit(prompt, max_new, **kw)
            except BaseException as exc:  # noqa: BLE001 — the outcome IS the result under exploration
                results[tag] = exc

        async def joiner():
            # Joins mid-decode of the first sequence under most
            # schedules — the continuous-batching admission window.
            await yield_point()
            await submit("b", [10], 2)

        async def expiring_then_cancel():
            # Arm a mid-decode expiry on the first active sequence, then
            # cancel it — the two release paths that must compose to
            # exactly one free.
            for _ in range(40):
                if engine._active:
                    break
                await yield_point()
            else:
                return
            seq = next(iter(engine._active.values()))
            seq.deadline_at = 1.0        # long past: next sweep dooms it
            # ``gap`` scheduling points later: where, under the explorer's
            # shallow schedules, the tick that sweeps the expiry has its
            # window open (a tick launches the next step before it reads
            # the last one, so that tick starts later than it used to).
            for _ in range(gap):
                await yield_point()
            engine.cancel(seq.future)

        async def reloader():
            await yield_point()
            backend.params_version += 1  # hot reload: cache invalidated

        async def finisher():
            # Let the driver stop once every waiter resolved.
            for _ in range(200):
                if "a" in results and "b" in results:
                    break
                await yield_point()
            results["stop"] = True

        coros = [driver(), submit("a", [1], 6), joiner(),
                 expiring_then_cancel(), reloader(), finisher()]

        def check():
            engine.pool.check_conservation()
            assert engine.pool.free_count == engine.pool.slots, (
                f"slot leak: {engine.pool.busy_count} busy after drain")
            assert not engine._active and not engine._queue
            assert "a" in results and "b" in results, results

        return coros, check

    return make


class TestDecodeSlotConservation:
    def test_fixed_engine_conserves_slots(self):
        report = explore_interleavings(
            _slot_conservation_scenario(DecodeEngine),
            schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_split_sweep_revert_caught(self):
        report = explore_interleavings(
            _slot_conservation_scenario(_SplitSweepEngine),
            schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the sweep-vs-cancel double-free window was not reachable — "
            "either the scenario no longer arms a mid-decode expiry or "
            "the budget is too small")
        assert any("Slot" in type(r.error).__name__
                   or "released" in str(r.error)
                   for r in report.failures), report.describe()


# -- decode engine: retired between a step's launch and its fetch (PR 33) -----
#
# The engine launches step N+1 before it reads step N, so between a launch
# and its fetch there is a window in which ``_retire`` can run (a cancel, an
# expiry sweep, a drain) on a sequence the launched step has live. The slot
# must then stay busy — parked — until that step is read: freed exactly
# once, the step's token for it discarded, and no join written under a step
# launched for the slot's previous tenant.

from ai4e_tpu.runtime.decode import LaunchedStep


class _AheadDecodeBackend(_FakeDecodeBackend):
    """``launch`` / ``fetch`` as ``runtime/kvcache.py`` has them, every
    call a real suspension. It knows which launched steps are unread, and
    refuses a prefill into a slot one of them has live: on the device that
    step and the join's insert would be ordered by the host's word alone."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.unread = []
        self._ids = [0] * self.slots

    def _check(self, slot):
        assert not any(step.active[slot] for step in self.unread), (
            f"join written into slot {slot} under a step launched for its "
            f"previous tenant")

    async def prefill_into(self, slot, tokens):
        self._check(slot)
        await yield_point()
        self._check(slot)
        return int(tokens[-1]) + 1

    async def launch(self, fresh, positions, active):
        await yield_point()
        self._ids = [(self._ids[slot] if token is None else token) + 1
                     for slot, token in enumerate(fresh)]
        step = LaunchedStep(bound=self.max_len, active=list(active),
                            out=list(self._ids))
        self.unread.append(step)
        return step

    async def fetch(self, step):
        await yield_point()
        self.unread.remove(step)
        step.ids, step.out = step.out, None
        return step


class _EagerReleaseEngine(DecodeEngine):
    """Verbatim pre-PR-33 ``_retire``: the slot goes back to the pool the
    moment its sequence is retired, also while a launched step has it
    live — the next ``_admit`` hands it to a join under that step."""

    def _retire(self, seq, outcome, error=None):
        if seq.done:
            return
        seq.done = True
        if seq.slot is not None:
            self._active.pop(seq.slot, None)
            self.pool.release(seq.slot)
            seq.slot = None
            self._occupancy.set(self.pool.busy_count / self.pool.slots,
                                model=self._model)
        else:
            try:
                self._queue.remove(seq)
            except ValueError:
                pass  # already popped by admission
            self._pending_gauge.set(self.pending_count, model=self._model)
        self._sequences_total.inc(model=self._model, outcome=outcome)
        if not seq.future.done():
            if error is not None:
                seq.future.set_exception(error)
            else:
                seq.future.set_result(list(seq.tokens))


def _retired_in_flight_scenario(engine_cls, ticks=80):
    """One slot, "b" queued behind "a"; "a" is cancelled while a launched
    step has it live."""

    def make():
        backend = _AheadDecodeBackend(slots=1, max_len=16)
        engine = engine_cls(backend, max_pending=8,
                            metrics=MetricsRegistry())
        results, delivered = {}, []

        async def driver():
            for _ in range(ticks):
                if "a" in results and "b" in results:
                    break
                await yield_point()
                await engine._tick()
            _decode_drain(engine, results)

        async def submit(tag, prompt, max_new):
            try:
                results[tag] = await engine.submit(
                    prompt, max_new,
                    on_token=lambda i, t: delivered.append((tag, i, t)))
            except BaseException as exc:  # noqa: BLE001 — the outcome IS the result under exploration
                results[tag] = exc

        async def canceller():
            for _ in range(60):
                if backend.unread and engine._active:
                    break
                await yield_point()
            else:
                return
            seq = next(iter(engine._active.values()))
            results["cancelled"] = ("a" if seq.prompt == (1,) else "b",
                                    len(seq.tokens))
            engine.cancel(seq.future)

        coros = [driver(), submit("a", [1], 8), submit("b", [10], 2),
                 canceller()]
        whole = {"a": list(range(2, 10)), "b": [11, 12]}

        def check():
            engine.pool.check_conservation()
            assert engine.pool.free_count == engine.pool.slots, (
                f"slot leak: {engine.pool.busy_count} busy after drain")
            assert not engine._active and not engine._queue
            assert not engine._parked and not engine._launched
            tag, had = results.get("cancelled", (None, 0))
            for name, want in whole.items():
                if name == tag:
                    # Nothing reached it after its retire.
                    want = want[:had]
                    assert sum(t == name for t, _, _ in delivered) == had
                # The other — a join into the freed slot, under most
                # schedules — was served whole and unharmed.
                assert results.get(name) == want, results

        return coros, check

    return make


class TestRetiredBetweenLaunchAndFetch:
    def test_fixed_engine_parks_the_slot_until_the_step_is_read(self):
        report = explore_interleavings(
            _retired_in_flight_scenario(DecodeEngine),
            schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_eager_release_revert_caught(self):
        report = explore_interleavings(
            _retired_in_flight_scenario(_EagerReleaseEngine),
            schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the retire-between-launch-and-fetch window was not reachable "
            "— either the scenario no longer cancels under a launched step "
            "or the budget is too small")
        assert any("previous tenant" in str(r.error)
                   or "previous tenant" in repr(r.error)
                   for r in report.failures), report.describe()


# ---------------------------------------------------------------------------
# PR 16: weighted-fair dequeue vs concurrent tenant weight update
# ---------------------------------------------------------------------------

class _SnapshotRebuildQueue(EndpointQueue):
    """The rejected reweight design, kept as the broken replica: apply a
    tenant weight change by snapshotting the per-tenant lanes, publishing
    the new policy (an await — the config push a multi-process deployment
    would make), then reinstalling rebuilt lanes. Any ``put`` that lands
    inside the publish window is clobbered by the stale snapshot: its seq
    stays in ``_ready_seqs`` but its message object is gone from every
    lane, so it is never delivered again — a silently lost task. The
    shipped design has no such window: ``TenantRegistry.set_weight`` is
    one dict write and ``_pop_fair`` reads the LIVE weight at every ring
    visit, so a reweight needs no queue surgery at all."""

    async def apply_weights(self, registry, tenant_id, weight) -> None:
        from collections import deque as _deque
        snapshot = {k: list(v) for k, v in self._lanes.items()}
        registry.set_weight(tenant_id, weight)
        await yield_point()  # the policy publish hop
        self._lanes = {k: _deque(v) for k, v in snapshot.items() if v}
        self._ring = _deque(self._lanes.keys())
        self._deficit = {}


class TestTenantFairDequeueVsWeightUpdate:
    """PR 16's DRR lanes under a concurrent operator reweight: producers
    for two tenants, a consumer draining by deficit round-robin, and an
    updater changing tenant ``a``'s weight mid-stream. The shipped
    live-read design delivers every message exactly once under every
    schedule and the deficit counters conserve (never negative, bounded
    by ``_DRR_COST`` + the largest quantum). The snapshot-rebuild replica
    loses concurrently-enqueued messages inside its publish window."""

    @staticmethod
    def _scenario(rebuild: bool):
        from ai4e_tpu.tenancy import Tenancy

        def make():
            tenancy = Tenancy.from_spec("a=ka:1,b=kb:1")
            cls = _SnapshotRebuildQueue if rebuild else EndpointQueue
            q = cls("/v1/q", fair=tenancy.lanes)
            seqs_a, seqs_b = (1, 2, 3), (10, 11)
            delivered: list[int] = []

            def _put(seq, tenant):
                q.put(Message(task_id=f"{tenant}{seq}", endpoint="/v1/q",
                              seq=seq, tenant=tenant))

            async def producer_a():
                for seq in seqs_a:
                    _put(seq, "a")
                    await yield_point()

            async def producer_b():
                for seq in seqs_b:
                    _put(seq, "b")
                    await yield_point()

            async def consumer():
                for _ in range(len(seqs_a) + len(seqs_b)):
                    msg = await q.receive(timeout=5.0)
                    assert msg is not None, (
                        "an enqueued message was never delivered — the "
                        "reweight lost it")
                    delivered.append(msg.seq)
                    q.complete(msg)

            async def updater():
                await yield_point()
                if rebuild:
                    await q.apply_weights(tenancy.registry, "a", 4.0)
                else:
                    # Shipped path: one synchronous dict write; the very
                    # next _pop_fair ring visit reads the new quantum.
                    tenancy.registry.set_weight("a", 4.0)

            def check():
                assert sorted(delivered) == sorted(seqs_a + seqs_b), (
                    f"exactly-once broken: delivered {sorted(delivered)}")
                for key, credit in q.deficits().items():
                    assert 0.0 <= credit < 1.0 + 4.0, (
                        f"deficit for lane {key!r} escaped its bound: "
                        f"{credit}")
                assert q.lane_depths() == {}

            return ([producer_a(), producer_b(), consumer(), updater()],
                    check)

        return make

    def test_live_weight_read_race_free(self):
        report = explore_interleavings(self._scenario(rebuild=False),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_snapshot_rebuild_replica_caught(self):
        report = explore_interleavings(self._scenario(rebuild=True),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the snapshot-rebuild lost-put window was not reachable — "
            "either the replica stopped rebuilding across an await or "
            "the schedule budget is too small")


# -- PR 17: mesh poisoned-row redelivery vs duplicate completion --------------


async def _reverted_whole_batch_fail(tm, batch):
    """Verbatim pre-mesh batch failure path: any bad row fails EVERY task
    in the batch, unconditionally — no per-row attribution and no
    terminal re-check before the write (the behavior
    ``runtime/mesh/redelivery.py`` replaced). A duplicate delivery that
    completed one of those tasks concurrently gets its COMPLETED
    clobbered to FAILED — a client-visible double outcome."""
    for tid in batch:
        await yield_point()  # the per-task store hop
        await tm.update_task_status(tid, "failed: mesh host degraded",
                                    TaskStatus.FAILED)


class TestMeshPoisonedRowRedelivery:
    """PR 17's degraded-batch contract (``docs/mesh_serving.md``): a
    poisoned row redelivers exactly its own task; the other rows
    complete; a concurrently-finishing duplicate delivery is suppressed
    against the terminal record — never a duplicate client-visible
    completion, never a whole-batch fail. Three racers: the worker's
    poison handling (REAL ``redeliver_poisoned``), a duplicate delivery
    completing the poisoned task on another replica, and the mesh
    coordinator flipping endpoint health over the same degrade."""

    @staticmethod
    def _scenario(fixed: bool):
        from ai4e_tpu.runtime.mesh import (EndpointHealth, MeshCoordinator,
                                           MeshLayout, RowPoisoned,
                                           redeliver_poisoned)

        def make():
            store = InMemoryTaskStore()
            tm = TracedTaskManager(LocalTaskManager(store))
            _seeded_task(store, None, task_id="t1")  # the poisoned row
            _seeded_task(store, None, task_id="t2")  # a clean row, same batch
            invariant = TerminalInvariant(store)
            health = EndpointHealth()
            coordinator = MeshCoordinator(MeshLayout(dp=2), health=health,
                                          process_count=2, unhealthy_after=2)
            completions = {"t1": 0, "t2": 0}

            async def _complete_if_fresh(tid):
                # Every completer is a redelivery consumer: conditional
                # transition, duplicate-suppressed against a record a
                # concurrent path may already have finished.
                res = await tm.update_task_status_if(
                    tid, TaskStatus.CREATED, "completed",
                    TaskStatus.COMPLETED)
                if res is not None:
                    completions[tid] += 1

            async def mesh_batch():
                # The worker's async path over a degraded batch: t1's
                # future failed with RowPoisoned, t2's row is clean.
                poison = RowPoisoned()
                assert "invalidated" in str(poison)
                if not fixed:
                    await _reverted_whole_batch_fail(tm, ("t1", "t2"))
                    return
                await _complete_if_fresh("t2")
                republished = await redeliver_poisoned(tm, "t1", "/v1/q/op")
                if republished:
                    # The broker redelivers; the consumer's completion is
                    # conditional like any redelivery consumer's.
                    await yield_point()
                    await _complete_if_fresh("t1")

            async def duplicate_completer():
                # A duplicate delivery of t1 finishing on another replica,
                # concurrent with the poison handling — its response hop
                # is the one suspension before the completion.
                await yield_point()
                await _complete_if_fresh("t1")

            async def health_flip():
                # The coordinator's view of the same degrade: two
                # consecutive poisoned gathers flip the endpoint
                # unhealthy (admission starts answering 500 so breakers
                # eject it); one clean gather heals it.
                for flags in ([0, 1], [0, 1], [0, 0]):
                    await yield_point()
                    coordinator.observe_poison(flags)

            def check():
                invariant.check()
                assert health.healthy, (
                    f"clean gather did not heal the endpoint: "
                    f"{health.reason}")
                if fixed:
                    assert completions == {"t1": 1, "t2": 1}, (
                        f"client-visible completions drifted (want exactly "
                        f"one per task): {completions}")

            return ([mesh_batch(), duplicate_completer(), health_flip()],
                    check)

        return make

    def test_fixed_poisoned_row_race_free(self):
        report = explore_interleavings(self._scenario(fixed=True),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_reverted_whole_batch_fail_caught(self):
        report = explore_interleavings(self._scenario(fixed=False),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok
        assert "clobbered" in str(report.failures[0].error)


# -- rollout drain: the two flip windows (PR 18) ------------------------------
#
# The drain state machine (rollout/drain.py, docs/deployment.md#drain)
# keeps two suspension-point-atomicity contracts, both stdlib-only so
# this job explores them against the REAL code: (1) the drain flip and
# the pending sweep are one synchronous step with the take-and-clear,
# so a concurrently scheduled batch cut can never deliver a device
# result into a future the sweep already failed; (2) the reload
# admission check and the in-flight registration are one synchronous
# step, so a weight swap can never complete on a worker that already
# reported itself drained.

from ai4e_tpu.rollout.drain import (ACTIVE, DRAINED, DrainingError,
                                    DrainState, drain_worker, retire_pending)


class _PendingEntry:
    __slots__ = ("task_id", "future")

    def __init__(self, task_id, future):
        self.task_id = task_id
        self.future = future


async def _reverted_retire_pending(pending_by_model):
    """The pre-fix sweep, verbatim: snapshot the queue, flush the pending
    gauge (an await), then clear and fail — the take-and-clear straddles
    a suspension point (AIL007's shape), so a batch cut landing inside
    the window owns futures this sweep is about to fail."""
    retired = 0
    for entries in list(pending_by_model.values()):
        taken = list(entries)
        await yield_point()  # the pending-gauge flush hop
        entries[:] = []
        for entry in taken:
            fut = getattr(entry, "future", entry)
            fut.set_exception(DrainingError())
            retired += 1
    return retired


class TestDrainFlipVsBatchCut:
    """Drain-flip vs in-flight batch completion: the flusher cuts a
    batch (synchronous take-and-clear, then the device hop, then results
    land in the taken futures) while the drain verb sweeps the same
    pending queues. Fixed (``retire_pending``: synchronous take-and-
    clear, ``done()``-guarded fail): every task gets exactly one client
    outcome — completed on this worker, redelivered to a peer, or
    refused at admission — and a redelivered task was never ALSO
    executed here. Reverted (await between snapshot and clear): a cut
    inside the window either double-resolves a future the sweep failed
    (InvalidStateError mid-drain) or executes a batch whose tasks the
    broker is simultaneously redelivering — a duplicate delivery."""

    @staticmethod
    def _scenario(fixed: bool):
        def make():
            pending = {"echo": []}
            state = DrainState(clock=lambda: 0.0)
            outcomes = {"t1": [], "t2": []}
            executed = []

            async def submitter():
                # Two submits through the batcher's admission gate: a
                # draining worker refuses (503 + X-Draining -> the
                # caller retries a peer), an active one queues.
                futs = {}
                for task_id in ("t1", "t2"):
                    if state.is_draining:
                        outcomes[task_id].append("refused")
                    else:
                        fut = asyncio.get_running_loop().create_future()
                        pending["echo"].append(_PendingEntry(task_id, fut))
                        futs[task_id] = fut
                    if task_id == "t1":
                        await yield_point()
                for task_id, fut in futs.items():
                    try:
                        await fut
                        outcomes[task_id].append("completed")
                    except DrainingError:
                        outcomes[task_id].append("redelivered")

            async def flusher():
                # One batch cut racing the drain: the take-and-clear is
                # one synchronous step (the real flusher's shape), the
                # device hop suspends, then results deliver.
                while True:
                    if pending["echo"]:
                        taken, pending["echo"][:] = (
                            list(pending["echo"]), [])
                        await yield_point()  # the device execute hop
                        for entry in taken:
                            executed.append(entry.task_id)
                            if not entry.future.done():
                                entry.future.set_result("ok")
                        return
                    if state.is_draining:
                        return
                    await yield_point()

            async def drainer():
                await yield_point()  # the drain verb arrives mid-traffic
                state.begin()
                if fixed:
                    retire_pending(pending)
                else:
                    await _reverted_retire_pending(pending)
                state.mark_drained()

            def check():
                for task_id, outs in outcomes.items():
                    assert len(outs) == 1, (
                        f"client outcome for {task_id} clobbered: {outs}")
                    if outs == ["redelivered"]:
                        assert task_id not in executed, (
                            f"{task_id} redelivered AND executed on the "
                            "draining worker — a duplicate delivery")

            return [submitter(), flusher(), drainer()], check

        return make

    def test_fixed_sweep_race_free(self):
        report = explore_interleavings(self._scenario(fixed=True),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_reverted_sweep_caught(self):
        report = explore_interleavings(self._scenario(fixed=False),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the snapshot-await-clear window was not reachable — either "
            "the scenario no longer models the sweep or the budget is "
            "too small")


async def _reverted_try_begin_reload(state):
    """The pre-fix reload admission, verbatim: the drain check and the
    in-flight registration straddled the reload-lock acquisition — one
    suspension between guard and guarded write (AIL007's shape). A drain
    that lands inside the window reads ``reloads_in_flight == 0``,
    reports the worker drained, and the swap then completes on a worker
    the rollout controller already moved past."""
    if state.is_draining:
        return False
    await yield_point()  # acquiring the reload serial lock
    state._reloads += 1
    return True


class TestDrainFlipVsReload:
    """Drain-flip vs concurrent hot reload: the reload verb races the
    drain verb on one worker. Fixed (``try_begin_reload``: check +
    register in one synchronous step): the reload either registers fully
    before the drain — which then waits for it — or is refused with 409
    while draining; ``drain_worker`` never reports a worker drained with
    a swap still in flight. Reverted (await between check and register):
    the drain completes inside the window and the swap lands on a worker
    that already reported itself drained."""

    @staticmethod
    def _scenario(fixed: bool):
        def make():
            state = DrainState(clock=lambda: 0.0)
            events = []

            async def reloader():
                await yield_point()  # the reload POST arrives
                if fixed:
                    admitted = state.try_begin_reload()
                else:
                    admitted = await _reverted_try_begin_reload(state)
                if not admitted:
                    events.append(("refused", state.state))  # the 409
                    return
                await yield_point()  # the weight swap itself
                events.append(("swapped", state.state))
                state.end_reload()

            async def drainer():
                res = await drain_worker(state, timeout_s=30.0,
                                         poll_s=0.01, clock=lambda: 0.0)
                events.append(("drained", res["clean"]))

            def check():
                assert ("drained", True) in events, (
                    f"drain never completed clean: {events}")
                for kind, detail in events:
                    if kind == "swapped":
                        assert detail != DRAINED, (
                            "weight swap completed on a worker that "
                            "already reported itself drained")
                    if kind == "refused":
                        assert detail != ACTIVE, (
                            "reload 409'd on an active worker")

            return [reloader(), drainer()], check

        return make

    def test_fixed_interlock_race_free(self):
        report = explore_interleavings(self._scenario(fixed=True),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_reverted_interlock_caught(self):
        report = explore_interleavings(self._scenario(fixed=False),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the check-await-register window was not reachable — either "
            "the scenario no longer models the admission or the budget "
            "is too small")
        assert "drained" in str(report.failures[0].error)


# -- PR 20: the drain-handler flush (AIL020 ledger-buffer-flush) --------------


class TestReplayDrainFlushLoss:
    """The PR 8/PR 18 composite AIL020 now pins statically: the worker's
    DrainingError handler stamps RETRY into the request's buffered
    hop-ledger and must flush it before redelivering. The reverted
    replica (stamp, redeliver, no flush) loses the draining timeline of
    exactly the retried task — the flight recorder's 100%% guarantee is
    about failed-and-retried requests above all. AIL020 catches the
    deletion syntactically (tests/test_analysis.py
    TestVerbatimRevertCaught); this replay shows the lost-timeline
    behavior it encodes."""

    def _scenario(self, flush_before_redeliver: bool):
        from ai4e_tpu.observability.ledger import RETRY, HopLedger

        def make():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            task = store.upsert(APITask(endpoint="/v1/x", body=b"{}"))
            draining = {"on": False}
            redelivered: list[str] = []

            async def handler():
                buf = HopLedger()
                await yield_point()       # submit races the drain flip
                if draining["on"]:
                    buf.stamp(RETRY, "worker", reason="draining")
                    if flush_before_redeliver:
                        events = buf.drain()
                        if events:
                            await tm.append_ledger(task.task_id, events)
                    redelivered.append(task.task_id)
                    return

            async def drain_flip():
                await yield_point()
                draining["on"] = True

            def check():
                if not redelivered:
                    return  # this interleaving never saw the drain
                events = store.get_ledger(task.task_id)
                assert any(ev.get("e") == RETRY
                           and ev.get("r") == "draining"
                           for ev in events), (
                    "draining timeline lost: the task was redelivered "
                    "but its RETRY stamp never reached the store")

            return [handler(), drain_flip()], check

        return make

    def test_fixed_handler_keeps_the_timeline(self):
        report = explore_interleavings(self._scenario(True),
                                       schedules=SCHEDULES, seed=SEED)
        assert report.ok, report.describe()

    def test_reverted_flush_deletion_caught(self):
        report = explore_interleavings(self._scenario(False),
                                       schedules=SCHEDULES, seed=SEED)
        assert not report.ok, (
            "the drain flip never interleaved before the handler's "
            "check — scenario no longer models the race")
        assert "timeline lost" in str(report.failures[0].error)
