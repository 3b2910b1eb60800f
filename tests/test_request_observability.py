"""Request observability (docs/observability.md): hop ledger, flight
recorder, SLO burn-rate engine, device phases, exemplars — unit and
end-to-end over the platform assembly."""

import asyncio
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.metrics import MetricsRegistry
from ai4e_tpu.observability.flight import FlightRecorder
from ai4e_tpu.observability.hub import RequestObservability
from ai4e_tpu.observability.ledger import (HopLedger, ledger_event,
                                           render_ledger, validate_events)
from ai4e_tpu.observability.slo import (SloEngine, parse_objectives)
from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
from ai4e_tpu.taskstore import APITask, InMemoryTaskStore, TaskNotFound


def run(coro):
    return asyncio.run(coro)


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def poll_until(client, task_id, predicate, tries=200, delay=0.02,
                     params=None):
    body = None
    for _ in range(tries):
        resp = await client.get(f"/v1/taskmanagement/task/{task_id}",
                                params=params or {})
        body = await resp.json()
        if predicate(body):
            return body
        await asyncio.sleep(delay)
    return body


# -- ledger unit --------------------------------------------------------------


class TestLedger:
    def test_event_shape_and_optional_fields(self):
        ev = ledger_event("popped", "dispatcher", reason="delivery 1")
        assert ev["e"] == "popped" and ev["h"] == "dispatcher"
        assert ev["r"] == "delivery 1" and "ms" not in ev
        ev2 = ledger_event("h2d", "device", t=123.0, ms=4.5)
        assert ev2["t"] == 123.0 and ev2["ms"] == 4.5 and "r" not in ev2

    def test_hop_ledger_buffers_and_snapshots(self):
        buf = HopLedger()
        buf.stamp("batched", "batcher", reason="size 3")
        buf.stamp("execute", "device", ms=10.0)
        events = buf.events()
        assert [e["e"] for e in events] == ["batched", "execute"]
        # Snapshot is a copy.
        events.clear()
        assert len(buf.events()) == 2
        # drain() takes AND clears — the flush primitive's idempotence:
        # a finally backstop after an already-flushed path is a no-op,
        # never a duplicated timeline.
        assert len(buf.drain()) == 2
        assert buf.drain() == [] and buf.events() == []

    def test_validate_events_drops_malformed(self):
        good = ledger_event("popped", "dispatcher")
        out = validate_events([
            good, "junk", {"e": "x"}, {"e": 1, "h": "y", "t": 2.0},
            {"e": "ok", "h": "z", "t": "NaNstr"},
            {"e": "ok", "h": "z", "t": 5.0, "r": 7, "ms": "oops"},
        ])
        assert len(out) == 2
        assert out[0]["e"] == "popped"
        assert out[1] == {"e": "ok", "h": "z", "t": 5.0, "r": "7"}

    def test_store_append_get_and_cap(self):
        store = InMemoryTaskStore()
        task = store.upsert(APITask(endpoint="/v1/x", body=b"b"))
        kept = store.append_ledger(task.task_id,
                                   [ledger_event("admitted", "gateway")])
        assert kept == 1
        assert store.get_ledger(task.task_id)[0]["e"] == "admitted"
        # Unknown task raises; unknown read answers empty.
        with pytest.raises(TaskNotFound):
            store.append_ledger("nope", [ledger_event("x", "y")])
        assert store.get_ledger("nope") == []
        # Cap: overflow drops with ONE truncated marker — the same
        # bound the worker-side HopLedger buffers to.
        from ai4e_tpu.observability.ledger import MAX_EVENTS
        many = [ledger_event("e", "h") for _ in range(MAX_EVENTS * 3)]
        store.append_ledger(task.task_id, many)
        store.append_ledger(task.task_id, many)
        timeline = store.get_ledger(task.task_id)
        assert len(timeline) == MAX_EVENTS + 1
        assert timeline[-1]["e"] == "truncated"
        assert sum(1 for e in timeline if e["e"] == "truncated") == 1

    def test_eviction_drops_timeline(self):
        store = InMemoryTaskStore()
        task = store.upsert(APITask(endpoint="/v1/x", body=b"b"))
        store.append_ledger(task.task_id, [ledger_event("admitted", "gw")])
        store.update_status(task.task_id, "completed")
        assert store.evict_terminal_older_than(-1.0) == 1
        assert store.get_ledger(task.task_id) == []
        assert task.task_id not in store._ledgers

    def test_follower_refuses_append(self, tmp_path):
        from ai4e_tpu.taskstore import NotPrimaryError
        from ai4e_tpu.taskstore.store import FollowerTaskStore
        primary = FollowerTaskStore(str(tmp_path / "p.jsonl"),
                                    start_as_primary=True)
        task = primary.upsert(APITask(endpoint="/v1/x", body=b"b"))
        assert primary.append_ledger(task.task_id,
                                     [ledger_event("a", "g")]) == 1
        primary.demote(5)
        with pytest.raises(NotPrimaryError):
            primary.append_ledger(task.task_id, [ledger_event("b", "g")])

    def test_render_ledger_offsets_and_deltas(self):
        events = [
            ledger_event("admitted", "gateway", t=100.0),
            ledger_event("popped", "dispatcher", t=100.1),
            ledger_event("execute", "device", t=100.2, ms=50.0),
            ledger_event("completed", "store", t=100.3,
                         reason="completed"),
        ]
        out = render_ledger("tid-1", events, status="completed - ok")
        assert "tid-1" in out and "4 events" in out
        assert "+0.0ms" in out and "+100.0ms" in out
        assert "execute 50.0ms" in out and "[dispatcher]" in out
        # Empty timeline renders a helpful message, not a crash.
        assert "no ledger events" in render_ledger("tid-2", [])


# -- flight recorder ----------------------------------------------------------


class TestFlightRecorder:
    def test_interesting_always_kept(self):
        fr = FlightRecorder(capacity=8, sample=0.0, slow_ms=100.0,
                            metrics=MetricsRegistry())
        assert fr.record("t1", "/v1/x", status="failed - boom",
                         duration_ms=1.0)
        assert fr.record("t2", "/v1/x", status="expired - dispatcher",
                         duration_ms=1.0)
        assert fr.record(None, "/v1/x", refusal="brownout")
        assert fr.record("t3", "/v1/x", status="completed",
                         duration_ms=500.0)  # slow
        assert fr.record("t4", "/v1/x", status="completed", duration_ms=1.0,
                         events=[ledger_event("failover", "dispatcher")])
        reasons = {e["reason"] for e in fr.entries()}
        assert reasons == {"failed", "expired", "shed", "slow", "failover"}

    def test_boring_sampled_at_stride(self):
        fr = FlightRecorder(capacity=100, sample=0.25, slow_ms=1e9,
                            metrics=MetricsRegistry())
        kept = sum(
            fr.record(f"t{i}", "/v1/x", status="completed", duration_ms=1.0)
            for i in range(40))
        assert kept == 10  # deterministic stride, exactly the fraction
        assert all(e["reason"] == "sampled" for e in fr.entries())

    def test_stride_counts_boring_only_during_incidents(self):
        """The sample fraction applies to BORING traffic — interesting
        requests (kept at 100%) must not advance the stride, or an
        incident's failure flood would inflate the boring keep-rate and
        churn the ring with baseline noise."""
        fr = FlightRecorder(capacity=1000, sample=0.25, slow_ms=1e9,
                            metrics=MetricsRegistry())
        boring_kept = 0
        for i in range(200):
            if i % 10 == 0:  # 10% boring, 90% failing — an incident
                boring_kept += fr.record(f"b{i}", "/v1/x",
                                         status="completed",
                                         duration_ms=1.0)
            else:
                fr.record(f"f{i}", "/v1/x", status="failed",
                          duration_ms=1.0)
        assert boring_kept == 5  # 25% of the 20 boring, not of the 200

    def test_backpressure_keeps_its_own_reason(self):
        fr = FlightRecorder(capacity=8, sample=0.0, metrics=MetricsRegistry())
        assert fr.record("t1", "/v1/x", status="completed", duration_ms=1.0,
                         events=[ledger_event("backpressure", "dispatcher")])
        (entry,) = fr.entries()
        assert entry["reason"] == "backpressure"
        assert fr.entries(reason="failover") == []

    def test_ring_bound_and_dump(self):
        fr = FlightRecorder(capacity=4, sample=1.0, metrics=MetricsRegistry())
        for i in range(10):
            fr.record(f"t{i}", "/v1/x", status="failed", duration_ms=1.0)
        dump = fr.dump()
        assert len(dump["entries"]) == 4
        assert dump["seen"] == 10
        assert dump["by_reason"] == {"failed": 4}
        assert [e["task_id"] for e in dump["entries"]] == [
            "t6", "t7", "t8", "t9"]

    def test_entries_filters(self):
        fr = FlightRecorder(capacity=8, sample=0.0, metrics=MetricsRegistry())
        fr.record("a", "/v1/x", status="failed", duration_ms=1.0)
        fr.record("b", "/v1/x", status="expired", duration_ms=1.0)
        assert [e["task_id"] for e in fr.entries(reason="failed")] == ["a"]
        assert [e["task_id"] for e in fr.entries(task_id="b")] == ["b"]


# -- hub ----------------------------------------------------------------------


class TestHub:
    def test_terminal_transition_stamps_and_counts(self):
        reg = MetricsRegistry()
        store = InMemoryTaskStore()
        flight = FlightRecorder(capacity=8, sample=0.0, metrics=reg)
        hub = RequestObservability(store, metrics=reg, flight=flight)
        task = store.upsert(APITask(endpoint="http://h/v1/x", body=b"b"))
        hub.stamp(task.task_id, ledger_event("admitted", "gateway"))
        store.update_status(task.task_id, "failed - boom")
        timeline = store.get_ledger(task.task_id)
        assert [e["e"] for e in timeline] == ["admitted", "completed"]
        assert timeline[-1]["r"] == "failed"
        assert reg.counter("ai4e_request_outcomes_total", "").value(
            route="/v1/x", outcome="failed") == 1
        # e2e histogram observed (route label) with a task exemplar.
        (collected,) = reg.histogram("ai4e_request_e2e_seconds",
                                     "").collect()
        assert collected[2] == {"route": "/v1/x"}
        assert collected[3]["count"] == 1
        exemplars = collected[3]["exemplars"]
        (ex_labels, _v, _ts) = next(iter(exemplars.values()))
        assert ex_labels == {"task_id": task.task_id}
        # Failed task reached the flight recorder with its timeline.
        (entry,) = flight.entries()
        assert entry["task_id"] == task.task_id
        assert entry["reason"] == "failed"
        assert [e["e"] for e in entry["events"]] == ["admitted", "completed"]

    def test_late_completion_counts_late(self):
        reg = MetricsRegistry()
        store = InMemoryTaskStore()
        hub = RequestObservability(store, metrics=reg)
        assert hub is not None
        task = store.upsert(APITask(endpoint="/v1/x", body=b"b",
                                    deadline_at=time.time() - 5.0))
        store.update_status(task.task_id, "completed")
        assert reg.counter("ai4e_request_outcomes_total", "").value(
            route="/v1/x", outcome="late") == 1

    def test_stamp_is_fail_open(self):
        reg = MetricsRegistry()
        store = InMemoryTaskStore()
        hub = RequestObservability(store, metrics=reg)
        hub.stamp("unknown-task", ledger_event("popped", "dispatcher"))
        assert reg.counter("ai4e_ledger_events_total", "").value(
            event="popped") == 0  # dropped, not raised, not counted

    def test_route_map_unifies_backend_and_published_labels(self):
        """Async outcomes (task endpoint = BACKEND path) and edge
        refusals (published prefix) must share one route label, or an
        SLO objective sees only half of its route's traffic — goodput
        pinned at 0 during shedding."""
        reg = MetricsRegistry()
        store = InMemoryTaskStore()
        hub = RequestObservability(store, metrics=reg)
        hub.map_route("/v1/be/x", "/v1/pub/x")
        task = store.upsert(APITask(endpoint="http://w:1/v1/be/x",
                                    body=b"b"))
        store.update_status(task.task_id, "completed")
        hub.record_refusal("/v1/pub/x", "pressure")
        outcomes = reg.counter("ai4e_request_outcomes_total", "")
        assert outcomes.value(route="/v1/pub/x", outcome="ok") == 1
        assert outcomes.value(route="/v1/pub/x", outcome="shed") == 1
        assert outcomes.value(route="/v1/be/x", outcome="ok") == 0
        # Operation tails resolve to the same label (longest prefix).
        tail = store.upsert(APITask(endpoint="http://w:1/v1/be/x/crop?q=1",
                                    body=b"b"))
        store.update_status(tail.task_id, "completed")
        assert outcomes.value(route="/v1/pub/x", outcome="ok") == 2

    def test_record_refusal(self):
        reg = MetricsRegistry()
        store = InMemoryTaskStore()
        flight = FlightRecorder(capacity=8, sample=0.0, metrics=reg)
        hub = RequestObservability(store, metrics=reg, flight=flight)
        hub.record_refusal("/v1/x", "pressure", priority=2)
        assert reg.counter("ai4e_request_outcomes_total", "").value(
            route="/v1/x", outcome="shed") == 1
        (entry,) = flight.entries()
        assert entry["refusal"] == "pressure" and entry["priority"] == 2

    def test_observe_sync_outcome_classes(self):
        """5xx = platform failure, 429 = shed (overload SHOULD burn the
        budget), other 4xx = the CLIENT's error — excluded from the SLO
        bad set, so a misbehaving client cannot page a healthy route."""
        reg = MetricsRegistry()
        flight = FlightRecorder(capacity=16, sample=0.0, metrics=reg)
        hub = RequestObservability(InMemoryTaskStore(), metrics=reg,
                                   flight=flight)
        for status in (200, 400, 404, 429, 500, 502):
            hub.observe_sync("/v1/x", 0.01, status)
        outcomes = reg.counter("ai4e_request_outcomes_total", "")
        assert outcomes.value(route="/v1/x", outcome="ok") == 1
        assert outcomes.value(route="/v1/x", outcome="client_error") == 2
        assert outcomes.value(route="/v1/x", outcome="shed") == 1
        assert outcomes.value(route="/v1/x", outcome="failed") == 2
        from ai4e_tpu.observability.slo import BAD_OUTCOMES
        assert "client_error" not in BAD_OUTCOMES
        # Flight: failures + the 429 shed are interesting; client
        # errors are not (sample=0 → only interesting ones kept).
        reasons = sorted(e["reason"] for e in flight.entries())
        assert reasons == ["failed", "failed", "shed"]


# -- SLO engine ---------------------------------------------------------------


class TestSloParsing:
    def test_grammar(self):
        objs = parse_objectives("/v1/a=250:99, /v1/b=goodput:99.9")
        assert objs[0].kind == "latency" and objs[0].latency_s == 0.25
        assert objs[0].target == pytest.approx(0.99)
        assert objs[1].kind == "goodput"
        assert objs[1].target == pytest.approx(0.999)
        assert parse_objectives(None) == []

    @pytest.mark.parametrize("bad", [
        "noslash=250:99", "/v1/a", "/v1/a=250", "/v1/a=abc:99",
        "/v1/a=250:0", "/v1/a=250:100", "/v1/a=-5:99", "/v1/a=250:xx",
    ])
    def test_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            parse_objectives(bad)

    def test_rejects_duplicate_route_kind(self):
        """The engine keys snapshots and gauges by (route, kind): two
        latency objectives on one route would silently share a ring
        (mixed-threshold baselines) and flap the burn gauge per tick —
        refused loudly instead."""
        with pytest.raises(ValueError, match="duplicate"):
            parse_objectives("/v1/a=250:99,/v1/a=1000:99.9")
        # Different kinds on one route are fine.
        assert len(parse_objectives("/v1/a=250:99,/v1/a=goodput:99")) == 2
        # Direct construction guards too.
        with pytest.raises(ValueError, match="duplicate"):
            SloEngine(parse_objectives("/v1/a=250:99")
                      + parse_objectives("/v1/a=500:90"),
                      metrics=MetricsRegistry())


class TestSloEngine:
    def _engine(self, reg, spec="/v1/x=250:90", **kw):
        kw.setdefault("fast_window_s", 10.0)
        kw.setdefault("slow_window_s", 40.0)
        kw.setdefault("tick_s", 1.0)
        clock = {"t": 0.0}
        eng = SloEngine(parse_objectives(spec), metrics=reg,
                        clock=lambda: clock["t"], **kw)
        return eng, clock

    def test_burn_rate_responds_to_latency_regression(self):
        reg = MetricsRegistry()
        eng, clock = self._engine(reg)
        hist = reg.histogram("ai4e_request_e2e_seconds", "")
        # Healthy: everything well under 250 ms → burn 0.
        for _ in range(50):
            hist.observe(0.05, route="/v1/x")
        clock["t"] = 5.0
        burns = eng.tick()[("/v1/x", "latency")]
        assert burns["fast"] == 0.0
        # Regression: every request now 2 s → bad ratio 1.0, burn 1/0.1.
        for _ in range(50):
            hist.observe(2.0, route="/v1/x")
        clock["t"] = 8.0
        burns = eng.tick()[("/v1/x", "latency")]
        assert burns["fast"] == pytest.approx(5.0, rel=0.01)  # 0.5/0.1
        assert reg.gauge("ai4e_slo_burn_rate", "").value(
            route="/v1/x", kind="latency", window="fast") == burns["fast"]
        # Window delta, not cumulative: once the healthy era rolls out
        # of the FAST window, fast burn reflects pure bad traffic while
        # the slow window still blends both — the multi-window shape.
        for _ in range(50):
            hist.observe(2.0, route="/v1/x")
        clock["t"] = 16.0
        burns = eng.tick()[("/v1/x", "latency")]
        assert burns["fast"] == pytest.approx(10.0, rel=0.01)
        assert burns["slow"] == pytest.approx(100 / 150 / 0.1, rel=0.01)

    def test_goodput_objective_and_breach_counter(self):
        reg = MetricsRegistry()
        eng, clock = self._engine(reg, spec="/v1/x=goodput:90")
        outcomes = reg.counter("ai4e_request_outcomes_total", "")
        for _ in range(8):
            outcomes.inc(route="/v1/x", outcome="ok")
        for _ in range(8):
            outcomes.inc(route="/v1/x", outcome="expired")
        clock["t"] = 1.0
        burns = eng.tick()[("/v1/x", "goodput")]
        assert burns["fast"] == pytest.approx(5.0)  # 0.5 bad / 0.1 budget
        assert burns["slow"] == pytest.approx(5.0)
        assert reg.counter("ai4e_slo_breaches_total", "").value(
            route="/v1/x", kind="goodput") == 1

    def test_idle_route_burns_zero(self):
        reg = MetricsRegistry()
        eng, clock = self._engine(reg)
        clock["t"] = 1.0
        burns = eng.tick()[("/v1/x", "latency")]
        assert burns == {"fast": 0.0, "slow": 0.0}

    def test_ladder_feed_notes_miss_only_with_traffic(self):
        reg = MetricsRegistry()
        eng, clock = self._engine(reg, spec="/v1/x=goodput:90")
        notes = []

        class FakeLadder:
            def note(self, miss, n=1.0):
                notes.append((miss, n))

        eng.attach_ladder(FakeLadder())
        clock["t"] = 1.0
        eng.tick()
        assert notes == []  # idle: no evidence either way
        reg.counter("ai4e_request_outcomes_total", "").inc(
            route="/v1/x", outcome="expired")
        clock["t"] = 2.0
        eng.tick()
        assert notes == [(True, 1.0)]
        # Evidence scales to the TICK's event count — one bare note per
        # multi-second tick would decay below the ladder's min_rate
        # evidence floor and never move it.
        for _ in range(40):
            reg.counter("ai4e_request_outcomes_total", "").inc(
                route="/v1/x", outcome="expired")
        clock["t"] = 3.0
        eng.tick()
        assert notes[-1] == (True, 40.0)

    def test_ladder_feed_clears_the_real_evidence_floor(self):
        """End-to-end against the REAL DegradationLadder at default
        min_rate: sustained breaches on a modestly busy route must
        actually climb the ladder (the unscaled one-note-per-tick feed
        converged to 0.2 ev/s < min_rate 1.0 and never moved it)."""
        from ai4e_tpu.orchestration.ladder import DegradationLadder
        reg = MetricsRegistry()
        clock = {"t": 0.0}
        eng = SloEngine(parse_objectives("/v1/x=goodput:90"),
                        metrics=reg, fast_window_s=10.0,
                        slow_window_s=40.0, tick_s=5.0,
                        clock=lambda: clock["t"])
        ladder = DegradationLadder(hold_s=5.0, metrics=reg,
                                   clock=lambda: clock["t"])
        eng.attach_ladder(ladder)
        outcomes = reg.counter("ai4e_request_outcomes_total", "")
        # 10 req/s, all bad, ticked every 5 s for 30 s of sustained burn.
        for step in range(1, 7):
            for _ in range(50):
                outcomes.inc(route="/v1/x", outcome="expired")
            clock["t"] = 5.0 * step
            eng.tick()
        assert ladder.level >= 1, ladder.level

    def test_window_validation(self):
        with pytest.raises(ValueError):
            SloEngine(parse_objectives("/v1/x=250:99"),
                      metrics=MetricsRegistry(),
                      fast_window_s=100.0, slow_window_s=10.0)
        with pytest.raises(ValueError):
            SloEngine([], metrics=MetricsRegistry())


# -- histogram exemplars ------------------------------------------------------


class TestExemplars:
    def test_exemplar_rendered_as_comment_line(self):
        """Exemplars ride a standalone COMMENT line under their bucket:
        the classic Prometheus text format (what /metrics serves) has
        no exemplar syntax, and appending OpenMetrics' `# {…}` after
        the value would fail the whole scrape — every value line must
        stay parseable."""
        reg = MetricsRegistry()
        hist = reg.histogram("ai4e_request_e2e_seconds", "e2e")
        hist.observe(0.03, route="/v1/x", exemplar={"task_id": "tid-9"})
        text = reg.render_prometheus()
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith("# exemplar ")]
        assert 'task_id="tid-9"' in line
        assert "ai4e_request_e2e_seconds_bucket" in line
        assert " 0.03 " in line
        # EVERY non-comment line still parses as `name{labels} value`
        # (the classic-format invariant the scrape depends on).
        for ln in text.splitlines():
            if ln and not ln.startswith("#"):
                assert " # " not in ln
                float(ln.rsplit(" ", 1)[1])

    def test_no_exemplar_keeps_exposition_identical(self):
        plain, carrying = MetricsRegistry(), MetricsRegistry()
        plain.histogram("h", "x").observe(0.2, route="/r")
        carrying.histogram("h", "x").observe(0.2, route="/r")
        assert plain.render_prometheus() == carrying.render_prometheus()
        assert "# exemplar" not in plain.render_prometheus()

    def test_last_exemplar_per_bucket_wins(self):
        reg = MetricsRegistry()
        hist = reg.histogram("h", "x")
        hist.observe(0.03, exemplar={"task_id": "a"})
        hist.observe(0.04, exemplar={"task_id": "b"})
        (collected,) = hist.collect()
        ((labels, value, _ts),) = collected[3]["exemplars"].values()
        assert labels == {"task_id": "b"} and value == 0.04


# -- assembly wiring ----------------------------------------------------------


class TestAssembly:
    def test_off_by_default_byte_identical(self):
        platform = LocalPlatform(PlatformConfig())
        assert platform.observability is None
        assert platform.slo is None
        assert platform.gateway._observability is None
        assert platform.dispatchers.observability is None
        # The flight-dump route is not even registered.
        paths = {r.resource.canonical
                 for r in platform.gateway.app.router.routes()
                 if r.resource is not None}
        assert "/v1/debug/flight" not in paths
        assert platform.store._ledgers == {}

    def test_on_wires_gateway_and_dispatchers(self):
        platform = LocalPlatform(PlatformConfig(observability=True))
        assert platform.observability is not None
        assert platform.gateway._observability is platform.observability
        assert platform.dispatchers.observability is platform.observability
        assert platform.observability.flight is not None
        d = platform.dispatchers.register("/v1/q", "http://h/v1/q")
        assert d.observability is platform.observability
        paths = {r.resource.canonical
                 for r in platform.gateway.app.router.routes()
                 if r.resource is not None}
        assert "/v1/debug/flight" in paths

    def test_native_store_refused(self):
        with pytest.raises(ValueError, match="Python store"):
            LocalPlatform(PlatformConfig(observability=True,
                                         native_store=True))

    def test_slo_requires_observability(self):
        with pytest.raises(ValueError, match="observability"):
            LocalPlatform(PlatformConfig(slo_objectives="/v1/x=250:99"))
        platform = LocalPlatform(PlatformConfig(
            observability=True, slo_objectives="/v1/x=250:99"))
        assert platform.slo is not None
        assert len(platform.slo.objectives) == 1

    def test_slo_ladder_requires_orchestration(self):
        with pytest.raises(ValueError, match="orchestration"):
            LocalPlatform(PlatformConfig(
                observability=True, slo_objectives="/v1/x=250:99",
                slo_ladder=True))
        platform = LocalPlatform(PlatformConfig(
            observability=True, slo_objectives="/v1/x=250:99",
            slo_ladder=True, admission=True, resilience=True,
            orchestration=True))
        assert platform.slo._ladder is platform.orchestration.ladder

    def test_config_env_round_trip(self):
        from ai4e_tpu.config import PlatformSection
        section = PlatformSection.from_env(env={
            "AI4E_PLATFORM_OBSERVABILITY": "1",
            "AI4E_PLATFORM_FLIGHT_CAPACITY": "64",
            "AI4E_PLATFORM_FLIGHT_SAMPLE": "0.5",
            "AI4E_PLATFORM_FLIGHT_SLOW_MS": "200",
            "AI4E_PLATFORM_SLO_OBJECTIVES": "/v1/x=250:99",
            "AI4E_PLATFORM_SLO_TICK_S": "0.5",
            "AI4E_PLATFORM_SLO_FAST_WINDOW_S": "30",
            "AI4E_PLATFORM_SLO_SLOW_WINDOW_S": "120",
            "AI4E_PLATFORM_SLO_LADDER": "0",
        })
        pc = section.to_platform_config()
        assert pc.observability is True and pc.flight_capacity == 64
        assert pc.slo_objectives == "/v1/x=250:99"
        assert pc.slo_fast_window_s == 30.0
        from ai4e_tpu.config import ObservabilitySection
        obs = ObservabilitySection.from_env(
            env={"AI4E_OBSERVABILITY_HOP_LEDGER": "true"})
        assert obs.hop_ledger is True


# -- end-to-end over the platform --------------------------------------------


class TestEndToEnd:
    def test_async_lifecycle_builds_full_ledger(self):
        async def main():
            platform = LocalPlatform(PlatformConfig(retry_delay=0.05,
                                                    observability=True))
            svc = platform.make_service("echo", prefix="v1/echo")

            @svc.api_async_func("/run")
            def handler(taskId, body, content_type):
                # The 2xx is answered before this runs; `delivered` is
                # stamped when the dispatcher has read it. A handler that
                # completes at once can beat that stamp on a loaded host.
                time.sleep(0.05)
                asyncio.run(platform.task_manager.complete_task(
                    taskId, "completed - ok"))

            svc_client = await serve(svc.app)
            backend = str(svc_client.make_url("/v1/echo/run"))
            platform.publish_async_api("/v1/public/run", backend)
            gw = await serve(platform.gateway.app)
            await platform.start()
            try:
                resp = await gw.post("/v1/public/run", data=b"x")
                task_id = (await resp.json())["TaskId"]
                final = await poll_until(
                    gw, task_id, lambda b: "completed" in b["Status"],
                    params={"ledger": "1"})
                events = [e["e"] for e in final["Ledger"]]
                for expected in ("admitted", "published", "popped",
                                 "delivered", "completed"):
                    assert expected in events, (expected, events)
                # Chronological: admitted first, completed last.
                ordered = sorted(final["Ledger"], key=lambda e: e["t"])
                assert ordered[0]["e"] == "admitted"
                assert ordered[-1]["e"] == "completed"
                # Default poll (no ?ledger) stays wire-identical.
                resp = await gw.get(f"/v1/taskmanagement/task/{task_id}")
                assert "Ledger" not in await resp.json()
            finally:
                await platform.stop()
                await gw.close()
                await svc_client.close()

        run(main())

    def test_pipeline_handoff_stamps_stage_boundary(self):
        """The hop-to-hop handoff (rewrite-to-`created` with a NEW
        endpoint, AddPipelineTask) used to produce an indistinguishable
        `created` in the timeline — it must stamp an explicit `stage`
        event carrying the boundary, so `trace` shows where one DAG
        stage ended and the next began (docs/pipelines.md satellite)."""
        async def main():
            platform = LocalPlatform(PlatformConfig(retry_delay=0.05,
                                                    observability=True))
            await platform.start()
            try:
                from ai4e_tpu.taskstore import APITask
                task = platform.store.upsert(APITask(
                    endpoint="http://h/v1/det/run", body=b"x",
                    publish=False))
                await platform.task_manager.add_pipeline_task(
                    task.task_id, "http://h/v1/cls/run")
                events = platform.store.get_ledger(task.task_id)
                stages = [e for e in events if e["e"] == "stage"]
                assert stages, events
                assert stages[0]["r"] == "/v1/det/run -> /v1/cls/run"
                # A same-endpoint requeue (reaper rescue shape) is NOT a
                # stage boundary — no second stamp.
                platform.store.requeue_if(task.task_id, "created")
                events = platform.store.get_ledger(task.task_id)
                assert len([e for e in events if e["e"] == "stage"]) == 1
            finally:
                await platform.stop()

        run(main())

    def test_deadline_missed_task_lands_in_flight_dump(self):
        async def main():
            # An unreachable backend + a redelivery backoff longer than
            # the request's budget: the first delivery attempt fails to
            # connect, the message backs off (>= retry_delay/2 with the
            # half-jitter), and the redelivery pop finds the deadline
            # spent — a DETERMINISTIC expiry whichever way the
            # scheduler leans (a too-tight budget alone can race the
            # first delivery under CPU contention).
            platform = LocalPlatform(PlatformConfig(
                retry_delay=0.6, observability=True, admission=True,
                flight_sample=0.0))
            platform.publish_async_api("/v1/public/slow",
                                       "http://127.0.0.1:9/v1/slow/run")
            gw = await serve(platform.gateway.app)
            await platform.start()
            try:
                resp = await gw.post("/v1/public/slow", data=b"x",
                                     headers={"X-Deadline-Ms": "250"})
                assert resp.status == 200
                task_id = (await resp.json())["TaskId"]
                final = await poll_until(
                    gw, task_id, lambda b: "expired" in b["Status"])
                assert "expired" in final["Status"]
                dump = await (await gw.get("/v1/debug/flight")).json()
                entries = [e for e in dump["entries"]
                           if e.get("task_id") == task_id]
                assert entries, dump
                assert entries[0]["reason"] == "expired"
                events = [e["e"] for e in entries[0]["events"]]
                assert "expired" in events and "completed" in events
                assert "backpressure" in events  # the failed attempt
            finally:
                await platform.stop()
                await gw.close()

        run(main())

    def test_flight_endpoint_404_when_off(self):
        async def main():
            platform = LocalPlatform(PlatformConfig())
            gw = await serve(platform.gateway.app)
            try:
                assert (await gw.get("/v1/debug/flight")).status == 404
            finally:
                await gw.close()

        run(main())

    def test_taskstore_http_ledger_surface(self):
        async def main():
            from ai4e_tpu.taskstore.http import make_app
            store = InMemoryTaskStore()
            task = store.upsert(APITask(endpoint="/v1/x", body=b"b"))
            client = await serve(make_app(store))
            try:
                resp = await client.post(
                    "/v1/taskstore/ledger",
                    json={"TaskId": task.task_id,
                          "Events": [ledger_event("h2d", "device",
                                                  ms=3.0),
                                     "garbage"]})
                assert resp.status == 200
                assert (await resp.json())["appended"] == 1
                resp = await client.get("/v1/taskstore/ledger",
                                        params={"taskId": task.task_id})
                events = (await resp.json())["Events"]
                assert events[0]["e"] == "h2d" and events[0]["ms"] == 3.0
                resp = await client.post(
                    "/v1/taskstore/ledger",
                    json={"TaskId": "unknown", "Events": []})
                assert resp.status == 404
            finally:
                await client.close()

        run(main())

    def test_worker_ledger_flushes_over_http(self):
        """Cross-process shape: an HttpTaskManager-backed worker flush
        lands on the control-plane store through the HTTP surface."""
        async def main():
            from ai4e_tpu.service.task_manager import HttpTaskManager
            from ai4e_tpu.taskstore.http import make_app
            store = InMemoryTaskStore()
            task = store.upsert(APITask(endpoint="/v1/x", body=b"b"))
            client = await serve(make_app(store))
            try:
                tm = HttpTaskManager(str(client.make_url("")))
                buf = HopLedger()
                buf.stamp("batched", "batcher", reason="size 1")
                buf.stamp("execute", "device", ms=12.0)
                kept = await tm.append_ledger(task.task_id, buf.events())
                assert kept == 2
                assert [e["e"] for e in store.get_ledger(task.task_id)] \
                    == ["batched", "execute"]
                await tm.close()
            finally:
                await client.close()

        run(main())


# -- device phases ------------------------------------------------------------


class TestDevicePhases:
    class PhasedRuntime:
        """Duck-typed runtime with a deterministic phase report."""

        class _Servable:
            input_shape = (4,)
            input_dtype = "float32"
            max_bucket = 8
            batch_buckets = (1, 8)

            def bucket_for(self, n):
                return 1 if n <= 1 else 8

            def postprocess(self, out):
                return {"ok": True}

        def __init__(self):
            self.models = {"m": self._Servable()}

        def run_batch_phases(self, name, padded):
            import numpy as np
            time.sleep(0.002)
            return (np.zeros_like(padded), frozenset(),
                    {"h2d": 0.001, "execute": 0.004, "d2h": 0.0005})

    def test_phases_land_in_histograms_and_ledger(self):
        async def main():
            import numpy as np
            from ai4e_tpu.runtime.batcher import MicroBatcher
            reg = MetricsRegistry()
            batcher = MicroBatcher(self.PhasedRuntime(), max_wait_ms=0,
                                   metrics=reg, measure_phases=True)
            await batcher.start()
            try:
                buf = HopLedger()
                await batcher.submit("m", np.zeros(4, np.float32),
                                     ledger=buf)
            finally:
                await batcher.stop()
            events = buf.events()
            names = [e["e"] for e in events]
            assert names == ["batched", "h2d", "execute", "d2h"]
            by_name = {e["e"]: e for e in events}
            assert by_name["h2d"]["ms"] == 1.0
            assert by_name["execute"]["ms"] == 4.0
            hist = reg.histogram("ai4e_device_phase_seconds", "")
            collected = {tuple(sorted(labels.items())): data["count"]
                         for _k, _n, labels, data in hist.collect()}
            assert collected[(("model", "m"), ("phase", "h2d"))] == 1
            assert collected[(("model", "m"), ("phase", "execute"))] == 1

        run(main())

    def test_overlap_accounting(self):
        """Two concurrent batches: the second's h2d overlaps the first's
        execute window → overlap counter moves and the ratio lands in
        (0, 1]."""
        async def main():
            import numpy as np
            from ai4e_tpu.runtime.batcher import MicroBatcher

            class SlowRuntime(self.PhasedRuntime):
                class _Servable(self.PhasedRuntime._Servable):
                    # Batch-of-1 buckets so concurrent submits become
                    # CONCURRENT batches in the pipeline window (one big
                    # batch would have nothing to overlap with).
                    max_bucket = 1
                    batch_buckets = (1,)

                    def bucket_for(self, n):
                        return 1

                def run_batch_phases(self, name, padded):
                    time.sleep(0.05)
                    return (np.zeros_like(padded), frozenset(),
                            {"h2d": 0.02, "execute": 0.03, "d2h": 0.001})

            reg = MetricsRegistry()
            batcher = MicroBatcher(SlowRuntime(), max_wait_ms=0,
                                   metrics=reg, measure_phases=True,
                                   pipeline_depth=2)
            await batcher.start()
            try:
                await asyncio.gather(
                    batcher.submit("m", np.zeros(4, np.float32)),
                    batcher.submit("m", np.zeros(4, np.float32)),
                    batcher.submit("m", np.zeros(4, np.float32)))
            finally:
                await batcher.stop()
            overlap = sum(v for *_, v in reg.counter(
                "ai4e_batch_h2d_overlap_seconds_total", "").collect())
            ratio = reg.gauge("ai4e_batch_overlap_ratio", "").value()
            assert overlap > 0.0
            assert 0.0 < ratio <= 1.0

        run(main())

    def test_off_by_default_no_phase_metrics(self):
        async def main():
            import numpy as np
            from ai4e_tpu.runtime.batcher import MicroBatcher

            class Plain(self.PhasedRuntime):
                def run_batch(self, name, padded):
                    return np.zeros_like(padded)

            reg = MetricsRegistry()
            batcher = MicroBatcher(Plain(), max_wait_ms=0, metrics=reg)
            await batcher.start()
            try:
                await batcher.submit("m", np.zeros(4, np.float32))
            finally:
                await batcher.stop()
            assert "ai4e_device_phase_seconds" not in \
                reg.render_prometheus()

        run(main())

    def test_real_runtime_phase_decomposition(self):
        """ModelRuntime.run_batch_phases on the CPU backend: phases
        measured, first execution labeled compile, outputs correct."""
        import numpy as np
        from ai4e_tpu.runtime import ModelRuntime, ServableModel
        runtime = ModelRuntime()
        runtime.register(ServableModel(
            name="double",
            apply_fn=lambda params, batch: batch * 2.0,
            params={},
            input_shape=(4,),
            preprocess=lambda body, ct: np.frombuffer(body, np.float32),
            postprocess=lambda out: out,
            batch_buckets=(8,),
        ))
        batch = np.ones((8, 4), np.float32)
        out, poisoned, phases = runtime.run_batch_phases("double", batch)
        np.testing.assert_allclose(out, 2.0 * batch)
        assert poisoned == frozenset()
        assert set(phases) == {"h2d", "compile", "d2h"}
        out2, _p, phases2 = runtime.run_batch_phases("double", batch)
        assert "execute" in phases2 and "compile" not in phases2
        assert all(v >= 0 for v in phases2.values())

    def test_warmup_builds_the_program_the_phased_path_dispatches(self):
        """Warmup goes through run_batch; serving with the hop ledger on
        goes through run_batch_phases or the split-phase calls. All of
        them must hit ONE jit dispatch-cache entry per bucket — when
        warmup passed raw numpy and the phased path a sharded device
        array, every bucket was traced and compiled a second time on its
        first request (7-12 s each for the deployed UNet on a v5e), under
        an ``execute`` label. The label now reads the cache itself."""
        import numpy as np
        from ai4e_tpu.runtime import ModelRuntime, ServableModel
        runtime = ModelRuntime()
        servable = runtime.register(ServableModel(
            name="double", apply_fn=lambda params, batch: batch * 2.0,
            params={}, input_shape=(4,),
            preprocess=lambda body, ct: np.frombuffer(body, np.float32),
            postprocess=lambda out: out, batch_buckets=(8,)))
        runtime.warmup()
        assert servable._compiled._cache_size() == 1
        batch = np.ones((8, 4), np.float32)
        _out, _p, phases = runtime.run_batch_phases("double", batch)
        assert set(phases) == {"h2d", "execute", "d2h"}
        resident, _w = runtime.h2d_resident("double", batch)
        _out, label, _w = runtime.execute_resident("double", resident)
        assert label == "execute"
        assert servable._compiled._cache_size() == 1


class TestWorkerFlushOnFailure:
    def test_execution_failure_still_flushes_buffered_events(self):
        """A device failure surfacing through the batch future must not
        drop the request's buffered stamps — exactly the failed tasks
        the flight recorder keeps at 100% need their worker-side
        timeline. The worker flushes BEFORE re-raising (the shell fails
        the task after, so the append still lands)."""
        async def main():
            import numpy as np

            from ai4e_tpu.runtime import (InferenceWorker, MicroBatcher,
                                          ModelRuntime, ServableModel)
            from ai4e_tpu.service.task_manager import LocalTaskManager
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            runtime = ModelRuntime()
            servable = runtime.register(ServableModel(
                name="boom",
                apply_fn=lambda params, batch: batch,
                params={},
                input_shape=(4,),
                preprocess=lambda body, ct: np.frombuffer(
                    body, np.float32),
                postprocess=lambda out: out,
                batch_buckets=(4,),
            ))
            assert servable is not None
            batcher = MicroBatcher(runtime, max_wait_ms=0,
                                   metrics=MetricsRegistry(),
                                   measure_phases=True)

            def explode(name, padded):
                raise RuntimeError("device on fire")

            runtime.run_batch_phases = explode
            worker = InferenceWorker(
                "w", runtime, batcher, task_manager=tm, store=store,
                metrics=MetricsRegistry(), hop_ledger=True)
            worker.serve_model(servable, sync_path="/s", async_path="/a")
            task = store.upsert(APITask(endpoint="/v1/a", body=b"b"))
            await batcher.start()
            client = await serve(worker.service.app)
            try:
                payload = np.arange(4, dtype=np.float32).tobytes()
                resp = await client.post(
                    "/v1/a", data=payload,
                    headers={"taskId": task.task_id,
                             "Content-Type": "application/octet-stream"})
                assert resp.status == 200  # async shell adopts, fails inside
                for _ in range(100):
                    if "failed" in store.get(task.task_id).status:
                        break
                    await asyncio.sleep(0.02)
                assert "failed" in store.get(task.task_id).status
                events = [e["e"] for e in store.get_ledger(task.task_id)]
                assert "batched" in events, events
            finally:
                await client.close()
                await batcher.stop()

        run(main())


class TestPlacementNote:
    def test_place_note_receives_outcome_and_backend(self):
        """Orchestrator.place(note=) hands the observability layer BOTH
        the outcome and the chosen backend — a probe event without the
        probed host would carry no diagnostic value."""
        from ai4e_tpu.orchestration import (OrchestrationPolicy,
                                            Orchestrator)
        from ai4e_tpu.resilience import BackendHealth, ResiliencePolicy
        health = BackendHealth(policy=ResiliencePolicy(),
                               metrics=MetricsRegistry())
        orch = Orchestrator(health, policy=OrchestrationPolicy(),
                            metrics=MetricsRegistry())
        seen = []
        chosen = orch.place([("http://a:1/v1/x", 1.0)],
                            note=lambda outcome, uri: seen.append(
                                (outcome, uri)))
        assert seen == [("confident", chosen)]
        # A raising sink never fails the placement.
        def bad_note(outcome, uri):
            raise RuntimeError("sink broken")
        assert orch.place([("http://a:1/v1/x", 1.0)], note=bad_note)


# -- chaos dump ---------------------------------------------------------------


class TestChaosDump:
    def test_invariant_violation_dumps_artifacts(self, tmp_path):
        from ai4e_tpu.chaos import InvariantChecker
        reg = MetricsRegistry()
        flight = FlightRecorder(capacity=8, sample=0.0, metrics=reg)
        flight.record("t1", "/v1/x", status="failed", duration_ms=1.0)
        checker = InvariantChecker(flight=flight, dump_dir=str(tmp_path))
        checker.attach(InMemoryTaskStore())
        checker.note_accepted("t1")  # never terminal → violation
        with pytest.raises(AssertionError, match="debug artifacts"):
            checker.assert_ok()
        names = sorted(p.name for p in tmp_path.iterdir())
        assert any(n.startswith("violations-") for n in names)
        assert any(n.startswith("flight-") for n in names)
        import json
        flight_file = next(p for p in tmp_path.iterdir()
                           if p.name.startswith("flight-"))
        dump = json.loads(flight_file.read_text())
        assert dump["entries"][0]["task_id"] == "t1"
