"""ai4e-lint tests (docs/analysis.md).

Three layers:

- per-rule fixtures: at least one true positive, one near-miss negative,
  and one suppression case for each of AIL001-AIL006;
- framework semantics: noqa parsing, baseline matching/justification
  enforcement, fingerprint stability under line moves, CLI exit codes;
- the whole-repo smoke test: ``ai4e_tpu/`` must be clean modulo the
  checked-in baseline — the same gate CI runs;

plus behavioral regression tests for the real defects the analyzer
surfaced and this PR fixed (terminal-status clobbers on the push/expired/
cache paths, the dropped dead-letter task handles, span metrics leaking
into DEFAULT_REGISTRY, the rejected AI4E_FEED_* namespace).
"""

import asyncio
import os
import textwrap

import pytest

from ai4e_tpu.analysis import Analyzer, Baseline, BaselineError
from ai4e_tpu.analysis.rules import ALL_RULES
from ai4e_tpu.analysis.rules.blocking import BlockingCallInAsync
from ai4e_tpu.analysis.rules.config_drift import ConfigDrift
from ai4e_tpu.analysis.rules.fire_and_forget import FireAndForgetTask
from ai4e_tpu.analysis.rules.registry_leak import MetricsRegistryLeak
from ai4e_tpu.analysis.rules.status_clobber import TerminalStatusClobber
from ai4e_tpu.analysis.rules.swallowed import SwallowedException

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_rule(tmp_path, rule, source, filename="mod.py"):
    """Run one rule over a snippet; returns active findings."""
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return Analyzer([rule], root=str(tmp_path)).run([str(f)]).findings


def run_analysis(coro):
    return asyncio.run(coro)


# -- AIL001 blocking-call-in-async -------------------------------------------


class TestBlockingCallInAsync:
    def test_true_positive_time_sleep(self, tmp_path):
        findings = run_rule(tmp_path, BlockingCallInAsync(), """
            import time
            async def handler():
                time.sleep(1)
        """)
        assert [f.rule for f in findings] == ["AIL001"]
        assert "time.sleep" in findings[0].message

    def test_true_positive_requests_and_alias(self, tmp_path):
        findings = run_rule(tmp_path, BlockingCallInAsync(), """
            import requests
            import time as t
            async def handler():
                requests.get("http://x")
                t.sleep(0.1)
        """)
        assert len(findings) == 2

    def test_near_miss_negatives(self, tmp_path):
        # asyncio.sleep, sync def, nested sync helper (executor-bound), and
        # time.sleep passed as a CALLABLE to to_thread are all fine.
        findings = run_rule(tmp_path, BlockingCallInAsync(), """
            import asyncio
            import time
            async def ok():
                await asyncio.sleep(1)
                await asyncio.to_thread(time.sleep, 1)
                def helper():
                    time.sleep(1)   # runs in an executor, not on the loop
                await asyncio.to_thread(helper)
            def sync_path():
                time.sleep(1)
        """)
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, BlockingCallInAsync(), """
            import time
            async def handler():
                time.sleep(0.001)  # ai4e: noqa[AIL001] — sub-ms, measured
        """)
        assert findings == []


# -- AIL002 metrics-registry-leak --------------------------------------------


class TestMetricsRegistryLeak:
    def test_true_positive_direct_call(self, tmp_path):
        findings = run_rule(tmp_path, MetricsRegistryLeak(), """
            from ai4e_tpu.metrics import DEFAULT_REGISTRY
            class Pool:
                def __init__(self, metrics=None):
                    self.metrics = metrics
                def work(self):
                    DEFAULT_REGISTRY.counter("x").inc()
        """)
        assert [f.rule for f in findings] == ["AIL002"]
        assert "DEFAULT_REGISTRY" in findings[0].message

    def test_true_positive_conditional_rebinding(self, tmp_path):
        # The exact shape the replication/tracing leaks hid in.
        findings = run_rule(tmp_path, MetricsRegistryLeak(), """
            class Replicator:
                def __init__(self, metrics=None):
                    if metrics is None:
                        from ai4e_tpu.metrics import DEFAULT_REGISTRY
                        metrics = DEFAULT_REGISTRY
                    self._gauge = metrics.gauge("lag")
        """)
        assert [f.rule for f in findings] == ["AIL002"]

    def test_near_miss_blessed_idiom(self, tmp_path):
        findings = run_rule(tmp_path, MetricsRegistryLeak(), """
            from ai4e_tpu.metrics import DEFAULT_REGISTRY
            class Pool:
                def __init__(self, metrics=None):
                    self.metrics = metrics or DEFAULT_REGISTRY
                    self._c = (metrics or DEFAULT_REGISTRY).counter("x")
                def work(self):
                    self.metrics.counter("y").inc()
            class NoInjection:
                def work(self):
                    DEFAULT_REGISTRY.counter("z").inc()  # no metrics param
        """)
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, MetricsRegistryLeak(), """
            from ai4e_tpu.metrics import DEFAULT_REGISTRY
            class Pool:
                def __init__(self, metrics=None):
                    DEFAULT_REGISTRY.counter("x").inc()  # ai4e: noqa[AIL002] — process-wide by design
        """)
        assert findings == []


# -- AIL003 terminal-status-clobber ------------------------------------------


class TestTerminalStatusClobber:
    def test_true_positive_unguarded_write(self, tmp_path):
        findings = run_rule(tmp_path, TerminalStatusClobber(), """
            async def deliver(tm, task_id):
                await tm.update_task_status(task_id, "Awaiting")
        """)
        assert [f.rule for f in findings] == ["AIL003"]

    def test_near_miss_guarded_variants(self, tmp_path):
        findings = run_rule(tmp_path, TerminalStatusClobber(), """
            from ai4e_tpu.taskstore import TaskStatus

            async def guarded(tm, task_id, record):
                if TaskStatus.canonical(record) not in TaskStatus.TERMINAL:
                    await tm.update_task_status(task_id, "Awaiting")

            async def via_helper(self, store, task_id):
                if await self._suppress_duplicate(task_id):
                    return
                await self.task_manager.fail_task(task_id, "failed")

            async def conditional(store, task_id):
                store.update_status_if(task_id, "running", "completed")
        """)
        assert findings == []

    def test_shell_guarded_decorator(self, tmp_path):
        # api_async_func handlers (and callbacks nested in them) are
        # guarded by the service shell's adoption-time terminal check.
        findings = run_rule(tmp_path, TerminalStatusClobber(), """
            def register(svc, tm):
                @svc.api_async_func("/x")
                async def handler(taskId, body):
                    await tm.update_task_status(taskId, "running")
                    async def on_progress(done):
                        await tm.update_task_status(taskId, f"running {done}")
                    return on_progress
        """)
        assert findings == []

    def test_taskstore_layer_exempt(self, tmp_path):
        findings = run_rule(tmp_path, TerminalStatusClobber(), """
            def sweep(store, task_id):
                store.update_status(task_id, "failed - lease expired")
        """, filename="taskstore/reaper.py")
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, TerminalStatusClobber(), """
            async def deliver(tm, task_id):
                await tm.update_task_status(task_id, "Awaiting")  # ai4e: noqa[AIL003] — task created this call, cannot be terminal
        """)
        assert findings == []


# -- AIL004 fire-and-forget-task ---------------------------------------------


class TestFireAndForgetTask:
    def test_true_positive(self, tmp_path):
        findings = run_rule(tmp_path, FireAndForgetTask(), """
            import asyncio
            def spawn(loop, coro):
                loop.create_task(coro)
                asyncio.ensure_future(coro)
        """)
        assert [f.rule for f in findings] == ["AIL004", "AIL004"]

    def test_near_miss_stored_awaited_chained(self, tmp_path):
        findings = run_rule(tmp_path, FireAndForgetTask(), """
            import asyncio
            async def spawn(loop, coro, holder):
                t = loop.create_task(coro)
                holder.add(t)
                t.add_done_callback(holder.discard)
                await asyncio.ensure_future(coro)
                loop.create_task(coro).add_done_callback(print)
                holder.track(loop.create_task(coro))
        """)
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, FireAndForgetTask(), """
            def spawn(loop, coro):
                loop.create_task(coro)  # ai4e: noqa[AIL004] — test scaffolding, loop torn down next line
        """)
        assert findings == []


# -- AIL005 swallowed-exception ----------------------------------------------


class TestSwallowedException:
    def test_true_positive_silent_pass(self, tmp_path):
        findings = run_rule(tmp_path, SwallowedException(), """
            def f():
                try:
                    work()
                except Exception:
                    pass
                try:
                    work()
                except:
                    return None
        """)
        assert [f.rule for f in findings] == ["AIL005", "AIL005"]

    def test_near_miss_logged_counted_raised(self, tmp_path):
        findings = run_rule(tmp_path, SwallowedException(), """
            def f(log, errors):
                try:
                    work()
                except Exception:
                    log.exception("work failed")
                try:
                    work()
                except Exception:
                    errors.inc(kind="work")
                try:
                    work()
                except Exception as exc:
                    raise RuntimeError("wrapped") from exc
                try:
                    work()
                except ValueError:
                    pass   # narrow except is out of scope for AIL005
        """)
        assert findings == []

    def test_event_set_is_not_metric_evidence(self, tmp_path):
        """A bare .set() is asyncio/threading Event signalling, not
        telemetry — it must not satisfy the rule; Gauge.set(value) does."""
        findings = run_rule(tmp_path, SwallowedException(), """
            def f(self, gauge):
                try:
                    work()
                except Exception:
                    self._stopped.set()
                try:
                    work()
                except Exception:
                    gauge.set(1.0)
        """)
        assert len(findings) == 1 and findings[0].line == 5

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, SwallowedException(), """
            def f():
                try:
                    work()
                except Exception:  # ai4e: noqa[AIL005] — destructor-time best effort
                    pass
        """)
        assert findings == []


# -- AIL006 config-drift ------------------------------------------------------


class TestConfigDrift:
    def _project(self, tmp_path, doc_text):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "config.md").write_text(doc_text)
        (tmp_path / "config.py").write_text(textwrap.dedent("""
            import os
            def _env_section(prefix):
                def deco(cls):
                    return cls
                return deco
            @_env_section("AI4E_DEMO_")
            class DemoSection:
                port: int = 1
                host: str = "x"
            TOKEN = os.environ.get("AI4E_DEMO_EXTRA_TOKEN", "")
        """))
        return Analyzer([ConfigDrift()], root=str(tmp_path)).run(
            [str(tmp_path / "config.py")]).findings

    def test_true_positive_undocumented_and_stale(self, tmp_path):
        findings = self._project(
            tmp_path, "Only `AI4E_DEMO_PORT` and `AI4E_DEMO_GONE` here.\n")
        msgs = {f.message.split(" ", 1)[0]: f for f in findings}
        # host + direct read undocumented; AI4E_DEMO_GONE stale in docs.
        assert "AI4E_DEMO_HOST" in msgs
        assert "AI4E_DEMO_EXTRA_TOKEN" in msgs
        stale = [f for f in findings if "AI4E_DEMO_GONE" in f.message]
        assert stale and stale[0].path == "docs/config.md"

    def test_near_miss_fully_documented(self, tmp_path):
        findings = self._project(
            tmp_path,
            "`AI4E_DEMO_PORT`, `AI4E_DEMO_HOST`, `AI4E_DEMO_EXTRA_TOKEN`;\n"
            "out-of-band: `AI4E_FAULT_SOMETHING`, `AI4E_CHAOS_SEED`.\n")
        assert findings == []

    def test_prefix_mention_covers_family(self, tmp_path):
        findings = self._project(
            tmp_path,
            "All `AI4E_DEMO` knobs (AI4E_DEMO_*) are demo-only.\n")
        assert findings == []

    def test_unstarred_mention_does_not_cover_extensions(self, tmp_path):
        """Documenting AI4E_DEMO_PORT must not silently 'document' a later
        AI4E_DEMO_PORT_FOO — family coverage needs an explicit star."""
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "config.md").write_text(
            "`AI4E_DEMO_PO` is documented (no star).\n")
        (tmp_path / "config.py").write_text(textwrap.dedent("""
            def _env_section(prefix):
                def deco(cls):
                    return cls
                return deco
            @_env_section("AI4E_DEMO_")
            class DemoSection:
                port: int = 1
        """))
        findings = Analyzer([ConfigDrift()], root=str(tmp_path)).run(
            [str(tmp_path / "config.py")]).findings
        assert any("AI4E_DEMO_PORT" in f.message for f in findings)


# -- framework: noqa, baseline, fingerprints, CLI -----------------------------


class TestFramework:
    def test_fingerprint_stable_across_line_moves(self, tmp_path):
        src1 = "import time\nasync def h():\n    time.sleep(1)\n"
        src2 = ("import time\n\n# a comment pushing everything down\n\n"
                "async def h():\n    time.sleep(1)\n")
        f1 = run_rule(tmp_path, BlockingCallInAsync(), src1, "a/m.py")
        f2 = run_rule(tmp_path, BlockingCallInAsync(), src2, "a/m.py")
        assert f1[0].line != f2[0].line
        assert f1[0].fingerprint == f2[0].fingerprint

    def test_baseline_grandfathers_and_reports_stale(self, tmp_path):
        src = "import time\nasync def h():\n    time.sleep(1)\n"
        (tmp_path / "m.py").write_text(src)
        raw = Analyzer([BlockingCallInAsync()], root=str(tmp_path)).run(
            [str(tmp_path / "m.py")]).findings
        entries = [{"rule": "AIL001", "path": "m.py",
                    "fingerprint": raw[0].fingerprint,
                    "justification": "legacy warmup sleep; tracked in #42"},
                   {"rule": "AIL001", "path": "gone.py",
                    "fingerprint": "feedfeedfeedfeed",
                    "justification": "file was deleted"}]
        result = Analyzer(
            [BlockingCallInAsync()], root=str(tmp_path),
            baseline=Baseline(entries)).run([str(tmp_path / "m.py")])
        assert result.findings == [] and len(result.baselined) == 1
        assert [e["path"] for e in result.stale_baseline] == ["gone.py"]

    def test_identical_findings_get_distinct_fingerprints(self, tmp_path):
        """Two byte-identical flagged lines in one symbol must not share a
        fingerprint — else one baseline entry would grandfather NEW
        identical findings nobody justified."""
        src = ("import time\n"
               "async def h():\n"
               "    time.sleep(1)\n"
               "    time.sleep(1)\n")
        (tmp_path / "m.py").write_text(src)
        raw = Analyzer([BlockingCallInAsync()], root=str(tmp_path)).run(
            [str(tmp_path / "m.py")]).findings
        assert len(raw) == 2
        assert raw[0].fingerprint != raw[1].fingerprint
        # Baselining only the first leaves the second ACTIVE.
        entries = [{"rule": "AIL001", "path": "m.py",
                    "fingerprint": raw[0].fingerprint,
                    "justification": "first sleep is grandfathered"}]
        result = Analyzer([BlockingCallInAsync()], root=str(tmp_path),
                          baseline=Baseline(entries)).run(
            [str(tmp_path / "m.py")])
        assert len(result.findings) == 1 and len(result.baselined) == 1

    def test_baseline_without_justification_refused(self, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text('{"version": 1, "findings": [{"rule": "AIL001", '
                     '"fingerprint": "abc", "justification": "  "}]}')
        with pytest.raises(BaselineError):
            Baseline.load(str(p))

    def test_parse_error_is_a_finding(self, tmp_path):
        (tmp_path / "bad.py").write_text("def broken(:\n")
        result = Analyzer([BlockingCallInAsync()],
                          root=str(tmp_path)).run([str(tmp_path / "bad.py")])
        assert [f.rule for f in result.findings] == ["AIL000"]

    def test_cli_exit_codes_and_json(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text(
            "import time\nasync def h():\n    time.sleep(1)\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL001"]) == 1
        capsys.readouterr()
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL004"]) == 0
        capsys.readouterr()
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL001", "--json"]) == 1
        out = capsys.readouterr().out
        import json as _json
        data = _json.loads(out)
        assert data["findings"][0]["rule"] == "AIL001"

    def test_cli_write_baseline_then_requires_justification(self, tmp_path,
                                                            capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text(
            "import time\nasync def h():\n    time.sleep(1)\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--write-baseline"]) == 0
        # The freshly-seeded baseline has empty justifications: the gate
        # refuses it (exit 2) until a human writes them.
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path)]) == 2


# -- the repo gate ------------------------------------------------------------


class TestRepoClean:
    def test_ai4e_tpu_clean_modulo_baseline(self):
        """The same check CI runs: the production tree must be clean —
        every rule, empty-or-justified baseline."""
        baseline_path = os.path.join(REPO, "analysis_baseline.json")
        baseline = Baseline.load(baseline_path)
        analyzer = Analyzer([cls() for cls in ALL_RULES], root=REPO,
                            baseline=baseline)
        result = analyzer.run([os.path.join(REPO, "ai4e_tpu")])
        assert result.findings == [], "\n".join(
            f.render() for f in result.findings)
        assert result.stale_baseline == []
        assert result.files_scanned > 50


# -- behavioral regressions for defects the analyzer surfaced -----------------


class TestTerminalClobberFixes:
    """AIL003 true positives fixed in this PR, each with the scenario that
    used to corrupt task state."""

    def test_push_forward_suppresses_terminal_duplicate(self):
        """A RETRIED push event (attempts > 1, e.g. after a lost response)
        for a completed task must not re-execute, and must not clobber the
        completion (the queue side fixed this in PR 3; the push side was
        still open). The attempt ordinal rides X-AI4E-Event-Attempt."""
        from ai4e_tpu.broker.push import PushEvent, WebhookDispatcher
        from ai4e_tpu.service import LocalTaskManager
        from ai4e_tpu.taskstore import APITask, InMemoryTaskStore

        async def main():
            store = InMemoryTaskStore()
            wd = WebhookDispatcher(LocalTaskManager(store))
            wd.add_route("/v1/x", "http://127.0.0.1:1/v1/x")  # unreachable
            task = store.upsert(APITask(endpoint="/v1/x", body=b"{}"))
            store.update_status(task.task_id, "completed - 3 found")
            status = await wd._forward(PushEvent(
                id=task.task_id, subject="/v1/x", data=b"{}", attempts=2))
            assert status == 200  # acked, not retried
            assert store.get(task.task_id).status == "completed - 3 found"
            assert wd._forwarded.value(outcome="duplicate") == 1
            # First delivery (attempts <= 1) skips the probe — hot path
            # unchanged: the unreachable backend surfaces as a retryable
            # 429, and the completion still isn't clobbered (the
            # failure-path writes carry their own terminal guard).
            status = await wd._forward(PushEvent(
                id=task.task_id, subject="/v1/x", data=b"{}", attempts=1))
            assert status == 429
            assert store.get(task.task_id).status == "completed - 3 found"

        run_analysis(main())

    def test_push_event_attempt_rides_the_wire(self):
        """headers_for_attempt stamps the ordinal; from_headers restores
        it — the signal the webhook's duplicate suppression keys on."""
        from ai4e_tpu.broker.push import PushEvent

        ev = PushEvent(id="t1", subject="/v1/x", data=b"payload")
        headers = ev.headers_for_attempt(3)
        back = PushEvent.from_headers(headers, b"payload")
        assert back.attempts == 3 and back.id == "t1"
        assert PushEvent.from_headers(ev.to_headers(), b"x").attempts == 0

    def test_dispatcher_drop_expired_skips_terminal(self):
        """An expired redelivery of an already-completed task must not
        flip the completion to `expired` (dispatch-side AIL003)."""
        from ai4e_tpu.broker import InMemoryBroker
        from ai4e_tpu.broker.dispatcher import Dispatcher
        from ai4e_tpu.broker.queue import Message
        from ai4e_tpu.service import LocalTaskManager
        from ai4e_tpu.taskstore import APITask, InMemoryTaskStore

        async def main():
            store = InMemoryTaskStore()
            broker = InMemoryBroker()
            broker.register_queue("/v1/x")
            d = Dispatcher(broker, "/v1/x", "http://127.0.0.1:1/v1/x",
                           LocalTaskManager(store))
            task = store.upsert(APITask(endpoint="/v1/x", body=b"{}"))
            store.update_status(task.task_id, "completed - done")
            msg = Message(task_id=task.task_id, endpoint="/v1/x",
                          deadline_at=1.0, queue_name="/v1/x")
            assert await d._drop_expired(msg) is True
            assert store.get(task.task_id).status == "completed - done"

        run_analysis(main())

    def test_async_shell_suppresses_terminal_duplicate(self):
        """Service-shell adoption guard: a redelivered taskId whose task is
        already terminal acks without invoking the handler."""
        from aiohttp.test_utils import TestClient, TestServer
        from ai4e_tpu.service import APIService, LocalTaskManager
        from ai4e_tpu.taskstore import APITask, InMemoryTaskStore

        store = InMemoryTaskStore()
        svc = APIService("svc", prefix="v1/test",
                         task_manager=LocalTaskManager(store))
        calls = []

        @svc.api_async_func("/run")
        async def run_ep(taskId, body, content_type):
            calls.append(taskId)
            await svc.task_manager.complete_task(taskId, "completed - ran")

        async def main():
            task = store.upsert(APITask(endpoint="/v1/test/run", body=b""))
            store.update_status(task.task_id, "completed - first run")
            client = TestClient(TestServer(svc.app))
            await client.start_server()
            try:
                resp = await client.post("/v1/test/run", data=b"{}",
                                         headers={"taskId": task.task_id})
                assert resp.status == 200
                await svc.drain(timeout=2.0)
            finally:
                await client.close()
            assert calls == []  # handler never invoked
            assert store.get(task.task_id).status == "completed - first run"

        run_analysis(main())

    def test_handler_failure_after_completion_keeps_completion(self):
        """_execute_async must not stamp `failed` over a completion the
        handler already wrote (cleanup-error-after-complete)."""
        from aiohttp.test_utils import TestClient, TestServer
        from ai4e_tpu.service import APIService, LocalTaskManager
        from ai4e_tpu.taskstore import InMemoryTaskStore

        store = InMemoryTaskStore()
        svc = APIService("svc", prefix="v1/test",
                         task_manager=LocalTaskManager(store))

        @svc.api_async_func("/run")
        async def run_ep(taskId, body, content_type):
            await svc.task_manager.complete_task(taskId, "completed - ok")
            raise RuntimeError("cleanup exploded after completion")

        async def main():
            client = TestClient(TestServer(svc.app))
            await client.start_server()
            try:
                resp = await client.post("/v1/test/run", data=b"{}")
                task_id = (await resp.json())["TaskId"]
                await svc.drain(timeout=2.0)
            finally:
                await client.close()
            assert store.get(task_id).status == "completed - ok"

        run_analysis(main())


class TestFireAndForgetFix:
    def test_dead_letter_spawn_holds_strong_ref(self):
        """AIL004 fix: the assembly keeps strong refs to dead-letter
        transitions until done (the loop's weak ref alone permits GC
        mid-flight)."""
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig

        async def main():
            platform = LocalPlatform(PlatformConfig())
            loop = asyncio.get_running_loop()
            started = asyncio.Event()
            release = asyncio.Event()

            async def work():
                started.set()
                await release.wait()

            t = platform._spawn_bg(loop, work())
            await started.wait()
            assert t in platform._bg_tasks  # strong ref while running
            release.set()
            await t
            await asyncio.sleep(0)
            assert t not in platform._bg_tasks  # discarded when done

        run_analysis(main())


class TestRegistryLeakFixes:
    def test_span_metrics_land_in_component_registry(self):
        """AIL002 fix: gateway/dispatcher/webhook tracers observe
        ai4e_span_seconds into the assembly's registry, and an
        assembly-driven span leaves NO new series in DEFAULT_REGISTRY."""
        from ai4e_tpu.broker import InMemoryBroker
        from ai4e_tpu.broker.dispatcher import Dispatcher
        from ai4e_tpu.gateway import Gateway
        from ai4e_tpu.metrics import DEFAULT_REGISTRY, MetricsRegistry
        from ai4e_tpu.service import LocalTaskManager
        from ai4e_tpu.taskstore import InMemoryTaskStore

        before = set(DEFAULT_REGISTRY._metrics)
        reg = MetricsRegistry()
        store = InMemoryTaskStore()
        gw = Gateway(store, metrics=reg)
        broker = InMemoryBroker(metrics=reg)
        broker.register_queue("/v1/x")
        d = Dispatcher(broker, "/v1/x", "http://127.0.0.1:1/v1/x",
                       LocalTaskManager(store), metrics=reg)
        with gw.tracer.span("create_task"):
            pass
        with d.tracer.span("dispatch"):
            pass
        hist = reg.histogram("ai4e_span_seconds")
        assert hist.quantile(0.5, name="create_task",
                             service="gateway") >= 0
        assert hist.quantile(0.5, name="dispatch",
                             service="dispatcher") >= 0
        assert set(DEFAULT_REGISTRY._metrics) == before

    def test_replication_gauges_land_in_injected_registry(self, tmp_path):
        """AIL002 fix (satellite): replication gauges ride the injected
        registry — visible in the assembly's /metrics, absent from
        DEFAULT_REGISTRY."""
        from ai4e_tpu.metrics import DEFAULT_REGISTRY, MetricsRegistry
        from ai4e_tpu.taskstore.replication import JournalReplicator
        from ai4e_tpu.taskstore.store import FollowerTaskStore

        async def main():
            before = set(DEFAULT_REGISTRY._metrics)
            reg = MetricsRegistry()
            # The store takes the same injected registry (its
            # ai4e_journal_* family follows the identical AIL002 idiom
            # since the durability PR).
            store = FollowerTaskStore(str(tmp_path / "journal.jsonl"),
                                      metrics=reg)
            repl = JournalReplicator(store, "http://127.0.0.1:1",
                                     metrics=reg)
            assert "ai4e_replication_offset_bytes" in reg._metrics
            assert "ai4e_replication_lag_bytes" in reg._metrics
            assert "ai4e_journal_fsyncs_total" in reg._metrics
            assert set(DEFAULT_REGISTRY._metrics) == before
            await repl.aclose()

        run_analysis(main())


class TestConfigDriftFix:
    def test_out_of_band_namespaces_boot(self):
        """AIL006 fix: AI4E_FEED_*/AI4E_CHAOS_* are out-of-band namespaces
        — FrameworkConfig.from_env used to REJECT AI4E_FEED_ADVERTISE_IP,
        so a multihost deployment pinning its feed IP could not boot."""
        from ai4e_tpu.config import ConfigError, FrameworkConfig

        cfg = FrameworkConfig.from_env(env={
            "AI4E_FEED_ADVERTISE_IP": "10.0.0.7",
            "AI4E_CHAOS_SEED": "123",
            "AI4E_FAULT_FETCH_FAIL_NTHS": "2",
        })
        assert cfg.platform.transport == "queue"
        # Misspellings still fail loudly — the exemption is namespaces,
        # not a hole.
        with pytest.raises(ConfigError):
            FrameworkConfig.from_env(env={"AI4E_PLATFROM_TRANSPORT": "push"})


# -- AIL007 stale-read-across-await -------------------------------------------


class TestStaleReadAcrossAwait:
    def setup_method(self):
        from ai4e_tpu.analysis.rules.stale_read import StaleReadAcrossAwait
        self.rule = StaleReadAcrossAwait()

    def test_true_positive_suspension_between_guard_and_write(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def drop(tm, tid):
                if not await tm.is_terminal(tid):
                    await asyncio.sleep(1)
                    await tm.update_task_status(tid, "expired")
        """)
        assert [f.rule for f in findings] == ["AIL007"]
        assert "suspension" in findings[0].message

    def test_true_positive_exact_deadletter_shape(self, tmp_path):
        # The dispatcher._backpressure defect this PR's first run found:
        # entry guard, AWAITING write, backoff sleep, then the dead-letter
        # write acting on the entry guard.
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def backpressure(self, msg):
                if await self._suppress_duplicate(msg):
                    return
                await self._try_update(msg.task_id, "awaiting")
                await asyncio.sleep(5)
                if not self.broker.abandon(msg):
                    await self._try_update(msg.task_id, "dead-letter")
        """)
        assert len(findings) == 1
        assert "dead-letter" in findings[0].snippet

    def test_true_positive_guarded_state_attr_write(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def probe(breaker, session):
                if breaker.state == "open":
                    await session.post("http://b")
                    breaker.state = "half_open"
        """)
        assert [f.rule for f in findings] == ["AIL007"]

    def test_near_miss_probe_after_await_idiom(self, tmp_path):
        # The blessed shape: the probe IS the last suspension before the
        # write (the residual one-hop window is the documented contract).
        findings = run_rule(tmp_path, self.rule, """
            async def forward(tm, tid):
                if not await tm.is_terminal(tid):
                    await tm.update_task_status(tid, "awaiting")
        """)
        assert findings == []

    def test_near_miss_recheck_after_last_suspension(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def drop(tm, tid):
                if not await tm.is_terminal(tid):
                    await asyncio.sleep(1)
                    if not await tm.is_terminal(tid):
                        await tm.update_task_status(tid, "expired")
        """)
        assert findings == []

    def test_conditional_recheck_does_not_suppress(self, tmp_path):
        # A re-check nested inside `if cond:` leaves the cond-False path
        # acting on the stale guard — exists-path semantics: still flagged.
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def drop(tm, tid, cond):
                if not await tm.is_terminal(tid):
                    await asyncio.sleep(1)
                    if cond:
                        if await tm.is_terminal(tid):
                            return
                    await tm.update_task_status(tid, "expired")
        """)
        assert [f.rule for f in findings] == ["AIL007"]

    def test_near_miss_unguarded_write_is_ail003s_domain(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def blind(tm, tid):
                await asyncio.sleep(1)
                await tm.update_task_status(tid, "failed")
        """)
        assert findings == []

    def test_near_miss_guard_in_other_branch_does_not_count(self, tmp_path):
        # The guard inside an except handler does not dominate the write
        # on the success path — no guard, so no AIL007 (AIL003's domain).
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def deliver(tm, tid, session):
                try:
                    await session.post("http://b")
                except OSError:
                    if await tm.is_terminal(tid):
                        return
                    await asyncio.sleep(1)
                    return
                await tm.update_task_status(tid, "failed")
        """)
        assert findings == []

    def test_loop_back_edge_counts_as_suspension(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def retry_loop(tm, tid, session):
                if await tm.is_terminal(tid):
                    return
                while True:
                    resp = await session.post("http://b")
                    if resp == 200:
                        return
                    await tm.update_task_status(tid, "failed")
        """)
        assert len(findings) == 1

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            async def drop(tm, tid):
                if not await tm.is_terminal(tid):
                    await asyncio.sleep(1)
                    await tm.update_task_status(tid, "expired")  # ai4e: noqa[AIL007] — single-writer path, measured
        """)
        assert findings == []


# -- AIL008 lock-across-slow-await --------------------------------------------


class TestLockAcrossSlowAwait:
    def setup_method(self):
        from ai4e_tpu.analysis.rules.lock_await import LockAcrossSlowAwait
        self.rule = LockAcrossSlowAwait()

    def test_true_positive_post_under_lock(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            class C:
                async def deliver(self, session):
                    async with self._lock:
                        async with session.post("http://b") as resp:
                            await resp.read()
        """)
        assert findings and all(f.rule == "AIL008" for f in findings)
        assert "holding self._lock" in findings[0].message

    def test_true_positive_sleep_under_threading_lock(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def wait(self):
                    with self._state_lock:
                        await asyncio.sleep(1)
        """)
        assert [f.rule for f in findings] == ["AIL008"]

    def test_near_miss_block_is_not_a_lock(self, tmp_path):
        # "block"/"backlog" contain the substring "lock" but hold none —
        # the name heuristic matches word segments, not substrings.
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def run(self, session):
                    async with self._dispatch_block:
                        await session.post("http://b")
                    async with self._backlog_lock:
                        await asyncio.sleep(1)
        """)
        assert len(findings) == 1  # only the real lock fires
        assert "_backlog_lock" in findings[0].message

    def test_near_miss_fast_work_under_lock(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def create(self):
                    async with self._create_lock:
                        self._session = object()
                async def reload(self):
                    async with self._reload_lock:
                        await asyncio.to_thread(self._swap)
        """)
        assert findings == []

    def test_near_miss_slow_await_outside_lock(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            class C:
                async def deliver(self, session):
                    with self._lock:
                        decision = self._decide()
                    await session.post("http://b")
        """)
        assert findings == []

    def test_lock_order_drift_flagged(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            class C:
                async def ab(self):
                    async with self._a_lock:
                        async with self._b_lock:
                            pass
                async def ba(self):
                    async with self._b_lock:
                        async with self._a_lock:
                            pass
        """)
        assert len(findings) == 1
        assert "opposite" in findings[0].message

    def test_lock_order_drift_via_multi_item_with(self, tmp_path):
        # `async with a, b:` enters left-to-right — it establishes a->b
        # exactly like nesting, and must conflict with a nested b->a.
        findings = run_rule(tmp_path, self.rule, """
            class C:
                async def ab(self):
                    async with self._a_lock, self._b_lock:
                        pass
                async def ba(self):
                    async with self._b_lock:
                        async with self._a_lock:
                            pass
        """)
        assert len(findings) == 1
        assert "opposite" in findings[0].message

    def test_consistent_lock_order_clean(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            class C:
                async def one(self):
                    async with self._a_lock:
                        async with self._b_lock:
                            pass
                async def two(self):
                    async with self._a_lock:
                        async with self._b_lock:
                            pass
        """)
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def wait(self):
                    with self._lock:
                        await asyncio.sleep(0.001)  # ai4e: noqa[AIL008] — sub-ms tick under a private lock
        """)
        assert findings == []


# -- AIL009 nonatomic-read-modify-write ---------------------------------------


class TestNonatomicReadModifyWrite:
    def setup_method(self):
        from ai4e_tpu.analysis.rules.rmw import NonatomicReadModifyWrite
        self.rule = NonatomicReadModifyWrite()

    def test_true_positive_split_rmw(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def bump(self):
                    n = self._busy
                    await asyncio.sleep(0)
                    self._busy = n + 1
                async def other(self):
                    self._busy = 0
        """)
        assert [f.rule for f in findings] == ["AIL009"]
        assert "self._busy" in findings[0].message

    def test_true_positive_one_statement_form(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            class C:
                async def bump(self):
                    self._busy = await self._next(self._busy)
                async def other(self):
                    self._busy = 0
        """)
        assert [f.rule for f in findings] == ["AIL009"]

    def test_near_miss_single_writer_attribute(self, tmp_path):
        # Only one method ever writes it: nobody to race with.
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def bump(self):
                    n = self._busy
                    await asyncio.sleep(0)
                    self._busy = n + 1
        """)
        assert findings == []

    def test_near_miss_same_segment_rmw(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def bump(self):
                    self._busy += 1
                    await asyncio.sleep(0)
                    self._busy -= 1
                async def other(self):
                    self._busy = 0
        """)
        assert findings == []

    def test_near_miss_reread_after_await(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def bump(self):
                    n = self._busy
                    await asyncio.sleep(0)
                    n = self._busy
                    self._busy = n + 1
                async def other(self):
                    self._busy = 0
        """)
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_rule(tmp_path, self.rule, """
            import asyncio
            class C:
                async def bump(self):
                    n = self._busy
                    await asyncio.sleep(0)
                    self._busy = n + 1  # ai4e: noqa[AIL009] — the await cannot interleave a writer (startup only)
                async def other(self):
                    self._busy = 0
        """)
        assert findings == []


# -- CLI satellites: unknown rule ids, JSON baseline authoring ----------------


class TestCliRuleIdValidation:
    def test_unknown_select_id_exits_2_and_names_it(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text("x = 1\n")
        # The CI-job-typo scenario: before this PR, --select AIL999
        # silently filtered to an EMPTY rule list and exited 0 — a typo
        # could disable the whole gate.
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL999"]) == 2
        err = capsys.readouterr().err
        assert "AIL999" in err and "--select" in err

    def test_unknown_ignore_id_exits_2(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text("x = 1\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--ignore", "AIL001,AILOOPS"]) == 2
        assert "AILOOPS" in capsys.readouterr().err

    def test_known_ids_still_select(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text(
            "import time\nasync def h():\n    time.sleep(1)\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "ail001"]) == 1  # case-folded

    def test_list_rules_shows_the_concurrency_family(self, capsys):
        from ai4e_tpu.analysis.cli import main
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule_id in ("AIL007", "AIL008", "AIL009"):
            assert rule_id in out


class TestCliJsonBaselineAuthoring:
    def test_json_findings_carry_paste_ready_baseline_entries(
            self, tmp_path, capsys):
        import json as _json
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text(
            "import time\nasync def h():\n    time.sleep(1)\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--json"]) == 1
        data = _json.loads(capsys.readouterr().out)
        assert data["version"] == 1
        finding = data["findings"][0]
        assert finding["fingerprint"]
        entry = finding["baseline_entry"]
        # The paste-ready shape: exactly what Baseline.load consumes, with
        # the justification left for a human.
        assert entry["fingerprint"] == finding["fingerprint"]
        assert entry["justification"] == ""
        assert set(entry) == {"rule", "path", "symbol", "snippet",
                              "fingerprint", "justification"}
        # Round-trip: a baseline authored from the JSON (plus a written
        # justification) grandfathers the finding.
        entry["justification"] = "known blocking call, measured sub-ms"
        baseline_path = tmp_path / "analysis_baseline.json"
        baseline_path.write_text(_json.dumps(
            {"version": 1, "findings": [entry]}))
        capsys.readouterr()
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path)]) == 0


# -- behavioral regressions for the AIL007 dispatcher fixes -------------------


class TestStaleGuardFixes:
    """The three stale-guard windows AIL007's first run found in the
    dispatcher, fixed in this PR. The full interleaving regression suite
    lives in tests/test_race_regressions.py (every schedule in the budget);
    here: the single decisive interleaving per defect, as a plain unit
    test that needs no explorer."""

    def _fixture(self, **kw):
        import random as _random
        from ai4e_tpu.broker.dispatcher import Dispatcher
        from ai4e_tpu.broker.queue import InMemoryBroker
        from ai4e_tpu.metrics.registry import MetricsRegistry
        from ai4e_tpu.resilience.health import BackendHealth
        from ai4e_tpu.service.task_manager import LocalTaskManager
        from ai4e_tpu.taskstore import APITask, InMemoryTaskStore

        store = InMemoryTaskStore()
        broker = InMemoryBroker(max_delivery_count=1)
        broker.register_queue("/v1/q")
        d = Dispatcher(broker, "/v1/q", "http://b",
                       LocalTaskManager(store), retry_delay=0.0,
                       metrics=MetricsRegistry(), rng=_random.Random(0),
                       resilience=BackendHealth(metrics=MetricsRegistry()),
                       **kw)
        store.upsert(APITask(task_id="t1", endpoint="/v1/q/op",
                             body=b"x", publish=False))
        return store, broker, d

    def test_deadletter_write_rechecks_terminality(self):
        from ai4e_tpu.taskstore import TaskStatus

        async def main():
            store, broker, d = self._fixture()
            task = store.get("t1")
            broker.publish(task)
            msg = await broker.receive("/v1/q", timeout=1.0)
            # The lost-response completion lands during the backoff sleep:
            # emulated by completing after the AWAITING write via a store
            # listener hooked on that exact transition.
            def complete_on_awaiting(t):
                if t.task_id == "t1" and t.status == "Awaiting service availability":
                    store.update_status("t1", "completed",
                                        TaskStatus.COMPLETED)
            store.add_listener(complete_on_awaiting)
            await d._backpressure(msg, "b")
            assert store.get("t1").canonical_status == TaskStatus.COMPLETED
            assert d._dispatched.value(outcome="duplicate", queue="/v1/q",
                                       backend="b") == 1

        run_analysis(main())

    def test_failure_paths_tolerate_no_task_manager(self):
        """The new re-probes must not break the task_manager=None
        configuration the cache path documents: a 4xx permanent failure
        and a dead-letter exhaustion both finish without raising."""
        import random as _random
        from ai4e_tpu.broker.dispatcher import Dispatcher
        from ai4e_tpu.broker.queue import InMemoryBroker, Message
        from ai4e_tpu.metrics.registry import MetricsRegistry

        class FakeResp:
            status = 400
            headers = {}  # the dispatcher consults X-Draining
            async def read(self):
                return b""

        class FakePost:
            async def __aenter__(self):
                return FakeResp()
            async def __aexit__(self, *exc):
                return False

        class FakeSessions:
            async def get(self):
                return self
            def post(self, url, **kw):
                return FakePost()

        async def main():
            broker = InMemoryBroker(max_delivery_count=1)
            broker.register_queue("/v1/q")
            d = Dispatcher(broker, "/v1/q", "http://b", task_manager=None,
                           retry_delay=0.0, metrics=MetricsRegistry(),
                           rng=_random.Random(0))
            d._sessions = FakeSessions()
            msg = Message(task_id="t1", endpoint="/v1/q/op", body=b"x",
                          queue_name="/v1/q", seq=1)
            broker.queue("/v1/q").put(msg)
            popped = await broker.receive("/v1/q", timeout=1.0)
            await d._dispatch_one(popped)  # 4xx permanent-fail path
            assert d._dispatched.value(outcome="failed", queue="/v1/q",
                                       backend="b") == 1
            msg2 = Message(task_id="t2", endpoint="/v1/q/op", body=b"x",
                           queue_name="/v1/q", seq=2)
            broker.queue("/v1/q").put(msg2)
            popped2 = await broker.receive("/v1/q", timeout=1.0)
            await d._backpressure(popped2, "b")  # dead-letter path
            assert d._dispatched.value(outcome="dead_letter", queue="/v1/q",
                                       backend="b") == 1

        run_analysis(main())

    def test_cache_complete_rechecks_after_result_hop(self):
        from ai4e_tpu.metrics.registry import MetricsRegistry
        from ai4e_tpu.rescache.cache import ResultCache
        from ai4e_tpu.taskstore import TaskStatus

        class HopStore:
            def __init__(self, store, on_hop):
                self.store, self.on_hop = store, on_hop
            async def set_result(self, task_id, payload,
                                 content_type="application/json"):
                self.on_hop()
                self.store.set_result(task_id, payload,
                                      content_type=content_type)

        async def main():
            cache = ResultCache(metrics=MetricsRegistry())
            cache.put("/v1/q|k", b"r")
            store = None

            def fail_during_hop():
                store.update_status_if("t1", TaskStatus.RUNNING,
                                       "failed - no progress",
                                       backend_status=TaskStatus.FAILED)

            s, broker, d = self._fixture(
                result_cache=cache)
            store = s
            d.result_store = HopStore(store, fail_during_hop)
            store.update_status("t1", TaskStatus.RUNNING, TaskStatus.RUNNING)
            task = store.get("t1")
            broker.publish(task)
            msg = await broker.receive("/v1/q", timeout=1.0)
            msg.cache_key = "/v1/q|k"
            assert await d._complete_from_cache(msg) is True
            # The reaper's failure landed mid-hop and must survive.
            assert store.get("t1").canonical_status == TaskStatus.FAILED
            assert d._dispatched.value(outcome="duplicate", queue="/v1/q",
                                       backend="") == 1

        run_analysis(main())


# -- AIL010 metrics-drift -----------------------------------------------------


class TestMetricsDrift:
    def _project(self, tmp_path, doc_text, code=None):
        from ai4e_tpu.analysis.rules.metrics_drift import MetricsDrift
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "METRICS.md").write_text(doc_text)
        (tmp_path / "mod.py").write_text(code or textwrap.dedent("""
            class Svc:
                def __init__(self, metrics):
                    self._hits = metrics.counter(
                        "ai4e_demo_hits_total", "hits")
                    self._depth = metrics.gauge("ai4e_demo_depth", "d")
                    self._lat = metrics.histogram(
                        "ai4e_demo_seconds", "lat")
        """))
        return Analyzer([MetricsDrift()], root=str(tmp_path)).run(
            [str(tmp_path / "mod.py")]).findings

    def test_true_positive_undocumented_and_stale(self, tmp_path):
        findings = self._project(
            tmp_path,
            "| `ai4e_demo_hits_total` | counter |\n"
            "| `ai4e_demo_gone` | gauge |\n")
        undocumented = {f.message.split(" ", 2)[1] for f in findings
                        if "registered in code" in f.message}
        assert undocumented == {"ai4e_demo_depth", "ai4e_demo_seconds"}
        stale = [f for f in findings if "ai4e_demo_gone" in f.message]
        assert stale and stale[0].path == "docs/METRICS.md"
        assert stale[0].line == 2

    def test_near_miss_fully_documented(self, tmp_path):
        assert self._project(
            tmp_path,
            "| `ai4e_demo_hits_total` | `ai4e_demo_depth` |\n"
            "| `ai4e_demo_seconds` | histogram |\n") == []

    def test_starred_family_covers_code_names(self, tmp_path):
        assert self._project(
            tmp_path, "All `ai4e_demo_*` metrics are demo-only.\n") == []

    def test_unstarred_prefix_does_not_cover(self, tmp_path):
        findings = self._project(
            tmp_path, "The `ai4e_demo` family (no star) is mentioned.\n")
        assert any("ai4e_demo_hits_total" in f.message for f in findings)
        # The bare prefix itself is stale too (nothing registers it).
        assert any("documents ai4e_demo " in f.message for f in findings)

    def test_exposition_suffixes_and_paths_excluded(self, tmp_path):
        """Docs may spell a histogram's _bucket/_sum/_count exposition
        and name files under ai4e_tpu/ without tripping the rule."""
        assert self._project(
            tmp_path,
            "`ai4e_demo_seconds_bucket` and `ai4e_demo_seconds_count`\n"
            "rendered by `ai4e_tpu/metrics/registry.py`; see also\n"
            "`ai4e_demo_hits_total`, `ai4e_demo_depth`,\n"
            "`ai4e_demo_seconds`.\n") == []

    def test_dynamic_names_ignored(self, tmp_path):
        """Only literal first arguments register: a computed name cannot
        be matched against docs and must not crash the rule."""
        assert self._project(
            tmp_path, "nothing documented\n",
            code=textwrap.dedent("""
                def make(metrics, name):
                    return metrics.counter(name, "dyn")
                def other(metrics):
                    return metrics.counter("not_ai4e_prefixed", "x")
            """)) == []

    def test_a_declared_step_report_series_registers_its_names(
            self, tmp_path):
        """A decode model names its step's figures in a
        ``step_report_series`` dict literal and the engine registers
        ``ai4e_decode_<name>`` for each: both checks follow the
        declaration."""
        findings = self._project(
            tmp_path,
            "| `ai4e_decode_window_fill` | histogram |\n"
            "| `ai4e_decode_gone` | histogram |\n",
            code=textwrap.dedent("""
                class LM:
                    step_report_series = {
                        "window_fill": ("help", (0.5, 1.0)),
                        "state_fill": ("help", (0.5, 1.0)),
                    }
            """))
        assert sorted(f.message.split(" ", 2)[1] for f in findings
                      if "registered in code" in f.message) == [
            "ai4e_decode_state_fill"]
        assert [f.line for f in findings
                if "documents ai4e_decode_gone" in f.message] == [2]
        assert len(findings) == 2

    def test_whole_repo_in_sync(self):
        """The real tree: every registered ai4e_* metric documented in
        docs/METRICS.md and vice versa — the gate CI now enforces (the
        rule's first run found ai4e_trace_current documented but never
        registered; fixed in this PR)."""
        from ai4e_tpu.analysis.rules.metrics_drift import MetricsDrift
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg = os.path.join(root, "ai4e_tpu")
        paths = []
        for dirpath, _dirs, files in os.walk(pkg):
            paths.extend(os.path.join(dirpath, f)
                         for f in files if f.endswith(".py"))
        result = Analyzer([MetricsDrift()], root=root).run(sorted(paths))
        assert [f.render() for f in result.findings] == []


# -- AIL011 ledger-vocabulary drift -------------------------------------------


class TestLedgerVocabularyDrift:
    DOC_OK = textwrap.dedent("""\
        # Observability

        <!-- ai4e:ledger-vocabulary -->
        | event | stamped by |
        |---|---|
        | `admitted` | gateway |
        | `h2d`, `execute` | device |
        <!-- /ai4e:ledger-vocabulary -->

        Prose mentioning `popped` outside the table never counts.

        <!-- ai4e:flight-reasons -->
        | reason | kept because |
        |---|---|
        | `failed` | terminal failed |
        | `sampled` | baseline stride |
        <!-- /ai4e:flight-reasons -->
        """)

    LEDGER_OK = textwrap.dedent("""\
        ADMITTED = "admitted"
        H2D = "h2d"
        EXECUTE = "execute"
        MAX_EVENTS = 128
        """)

    FLIGHT_OK = textwrap.dedent("""\
        REASON_FAILED = "failed"
        REASON_SAMPLED = "sampled"
        """)

    def _project(self, tmp_path, doc=None, ledger=None, flight=None,
                 extra=None):
        from ai4e_tpu.analysis.rules.ledger_vocab import \
            LedgerVocabularyDrift
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "observability.md").write_text(
            self.DOC_OK if doc is None else doc)
        obs = tmp_path / "observability"
        obs.mkdir()
        (obs / "ledger.py").write_text(
            self.LEDGER_OK if ledger is None else ledger)
        (obs / "flight.py").write_text(
            self.FLIGHT_OK if flight is None else flight)
        paths = [str(obs / "ledger.py"), str(obs / "flight.py")]
        if extra is not None:
            (tmp_path / "caller.py").write_text(extra)
            paths.append(str(tmp_path / "caller.py"))
        return Analyzer([LedgerVocabularyDrift()],
                        root=str(tmp_path)).run(sorted(paths)).findings

    def test_in_sync_project_is_clean(self, tmp_path):
        assert self._project(tmp_path) == []

    def test_undocumented_event_and_reason(self, tmp_path):
        findings = self._project(
            tmp_path,
            ledger=self.LEDGER_OK + 'POPPED = "popped"\n',
            flight=self.FLIGHT_OK + 'REASON_SLOW = "slow"\n')
        msgs = [f.message for f in findings]
        assert any("'popped'" in m and "absent from" in m for m in msgs)
        assert any("'slow'" in m and "absent from" in m for m in msgs)
        assert len(findings) == 2

    def test_stale_doc_rows_both_tables(self, tmp_path):
        doc = self.DOC_OK.replace("| `admitted` | gateway |",
                                  "| `admitted` | gateway |\n"
                                  "| `vanished` | nowhere |")
        doc = doc.replace("| `failed` | terminal failed |",
                          "| `failed` | terminal failed |\n"
                          "| `gone` | nothing |")
        findings = self._project(tmp_path, doc=doc)
        msgs = [f.message for f in findings]
        assert any("'vanished'" in m and "no code defines" in m
                   for m in msgs)
        assert any("'gone'" in m and "no code defines" in m for m in msgs)
        stale = [f for f in findings if "'vanished'" in f.message]
        assert stale[0].path == "docs/observability.md"

    def test_literal_stamp_outside_vocabulary(self, tmp_path):
        findings = self._project(tmp_path, extra=textwrap.dedent("""\
            from observability.ledger import ledger_event

            def f(buf, hub, tid, e):
                buf.stamp("admitted", "gateway")     # vocabulary: fine
                buf.stamp("typo_event", "gateway")   # NOT vocabulary
                ledger_event("execute", "device")    # fine
                hub.stamp(tid, e)                    # non-literal: fine
            """))
        assert len(findings) == 1
        assert "'typo_event'" in findings[0].message
        assert findings[0].path == "caller.py"

    def test_missing_marked_region_is_itself_a_finding(self, tmp_path):
        findings = self._project(
            tmp_path, doc="# Observability\n\nno markers at all\n")
        msgs = [f.message for f in findings]
        assert any("ai4e:ledger-vocabulary" in m and "no" in m
                   for m in msgs)
        assert any("ai4e:flight-reasons" in m for m in msgs)
        assert len(findings) == 2

    def test_prose_outside_markers_never_counts(self, tmp_path):
        # `popped` appears in prose — neither documented (code side
        # would flag it if the constant existed) nor stale (doc side
        # must not read it as a table row).
        assert self._project(tmp_path) == []

    def test_non_vocabulary_project_is_silent(self, tmp_path):
        from ai4e_tpu.analysis.rules.ledger_vocab import \
            LedgerVocabularyDrift
        (tmp_path / "plain.py").write_text("x = 1\n")
        findings = Analyzer([LedgerVocabularyDrift()],
                            root=str(tmp_path)).run(
            [str(tmp_path / "plain.py")]).findings
        assert findings == []

    def test_whole_repo_in_sync(self):
        """The real tree: the observability.md vocabulary tables and
        the ledger/flight constants agree both directions, and every
        literal stamp in the codebase uses a vocabulary event — the
        gate CI now enforces."""
        from ai4e_tpu.analysis.rules.ledger_vocab import \
            LedgerVocabularyDrift
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg = os.path.join(root, "ai4e_tpu")
        paths = []
        for dirpath, _dirs, files in os.walk(pkg):
            paths.extend(os.path.join(dirpath, f)
                         for f in files if f.endswith(".py"))
        result = Analyzer([LedgerVocabularyDrift()],
                          root=root).run(sorted(paths))
        assert [f.render() for f in result.findings] == []


# -- AIL012 static-bucket-ladder ---------------------------------------------


class TestStaticBucketLadder:
    """A literal bucket/tile ladder under ``runtime/`` outside the
    deriver module is a finding — the static ladder PR 13 retired must
    not silently come back (docs/device_path.md)."""

    def _run(self, tmp_path, source, filename):
        from ai4e_tpu.analysis.rules.bucket_literal import \
            StaticBucketLadder
        return run_rule(tmp_path, StaticBucketLadder(), source,
                        filename=filename)

    def test_true_positive_in_runtime(self, tmp_path):
        findings = self._run(tmp_path, """
            BUCKETS = (1, 2, 4, 8)
        """, "ai4e_tpu/runtime/batcher2.py")
        assert [f.rule for f in findings] == ["AIL012"]
        assert "(1, 2, 4, 8)" in findings[0].message

    def test_trailing_inf_sentinel_does_not_exempt(self, tmp_path):
        # The exact pre-PR-13 exposition shape: int ladder + float("inf").
        findings = self._run(tmp_path, """
            hist = registry.histogram(
                "x", "", buckets=(1, 2, 4, 8, 16, float("inf")))
        """, "ai4e_tpu/runtime/metrics_shim.py")
        assert [f.rule for f in findings] == ["AIL012"]

    def test_list_literal_flagged_too(self, tmp_path):
        findings = self._run(tmp_path, """
            ladder = [1, 16, 64]
        """, "ai4e_tpu/runtime/worker_extra.py")
        assert [f.rule for f in findings] == ["AIL012"]

    def test_deriver_module_exempt(self, tmp_path):
        findings = self._run(tmp_path, """
            DEFAULT_BUCKETS = (1, 2, 4, 8)
            IMAGE_BUCKETS = (1, 16, 64)
        """, "ai4e_tpu/runtime/ladder.py")
        assert findings == []

    def test_outside_runtime_not_flagged(self, tmp_path):
        findings = self._run(tmp_path, """
            buckets = (1, 8, 32, 64)
        """, "ai4e_tpu/models/config.py")
        assert findings == []

    def test_shape_and_width_tuples_not_flagged(self, tmp_path):
        findings = self._run(tmp_path, """
            stage_sizes = (3, 4, 6, 3)      # not ascending
            widths = (32, 64, 128)          # does not start at 1
            pair = (1, 8)                   # too short to be a ladder
            shape = (1, 224, x)             # non-constant tail, run of 2
        """, "ai4e_tpu/runtime/families2.py")
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = self._run(tmp_path, """
            LEGACY = (1, 2, 4)  # ai4e: noqa[AIL012] — fixture for the migration test
        """, "ai4e_tpu/runtime/fixture.py")
        assert findings == []


# -- AIL013 unbounded-metric-label -------------------------------------------


class TestUnboundedMetricLabel:
    """An identity-class metric label fed a dynamic value is a finding —
    caller identity must pass through a bounded-cardinality mapper
    (``TenantRegistry.tenant_label``, docs/tenancy.md) before it becomes
    a series dimension."""

    def _run(self, tmp_path, source, filename="ai4e_tpu/svc/mod.py"):
        from ai4e_tpu.analysis.rules.metric_label import \
            UnboundedMetricLabel
        return run_rule(tmp_path, UnboundedMetricLabel(), source,
                        filename=filename)

    def test_true_positive_raw_tenant_id(self, tmp_path):
        findings = self._run(tmp_path, """
            def note(counter, tenant_id):
                counter.inc(tenant=tenant_id)
        """)
        assert [f.rule for f in findings] == ["AIL013"]
        assert "tenant=" in findings[0].message

    def test_true_positive_header_read(self, tmp_path):
        # The nightmare shape: one rotated header per request = one fresh
        # series per request.
        findings = self._run(tmp_path, """
            def note(counter, request):
                counter.inc(api_key=request.headers.get("X-Api-Key"))
        """)
        assert [f.rule for f in findings] == ["AIL013"]

    def test_observe_and_set_flagged_too(self, tmp_path):
        findings = self._run(tmp_path, """
            def note(hist, gauge, caller_id):
                hist.observe(0.5, caller=caller_id)
                gauge.set(1.0, client_id=caller_id)
        """)
        assert sorted(f.rule for f in findings) == ["AIL013", "AIL013"]

    def test_blessed_inline_mapper_call(self, tmp_path):
        findings = self._run(tmp_path, """
            def note(counter, registry, tenant_id):
                counter.inc(tenant=registry.tenant_label(tenant_id))
        """)
        assert findings == []


# -- AIL014 unplaced-device-transfer ------------------------------------------


class TestUnplacedDeviceTransfer:
    """A device transfer under ``runtime/``/``parallel/`` that does not
    state its placement is a finding — PR 17 made placement declarative
    (NamedSharding batch axes, partition rules, the one blessed fetch
    helper in ``runtime/mesh/placement.py``; docs/mesh_serving.md)."""

    def _run(self, tmp_path, source,
             filename="ai4e_tpu/runtime/mod.py"):
        from ai4e_tpu.analysis.rules.unplaced import UnplacedDeviceTransfer
        return run_rule(tmp_path, UnplacedDeviceTransfer(), source,
                        filename=filename)

    def test_true_positive_bare_device_put(self, tmp_path):
        findings = self._run(tmp_path, """
            import jax
            def stage(batch):
                return jax.device_put(batch)
        """)
        assert [f.rule for f in findings] == ["AIL014"]
        assert "default device" in findings[0].message

    def test_true_positive_bare_device_get(self, tmp_path):
        findings = self._run(tmp_path, """
            import jax
            def fetch(out):
                return jax.device_get(out)
        """, filename="ai4e_tpu/parallel/mod.py")
        assert [f.rule for f in findings] == ["AIL014"]
        assert "fetch_to_host" in findings[0].message

    def test_from_import_alias_resolved(self, tmp_path):
        findings = self._run(tmp_path, """
            from jax import device_put as put
            def stage(batch):
                return put(batch)
        """)
        assert [f.rule for f in findings] == ["AIL014"]

    def test_positional_sharding_is_placed(self, tmp_path):
        findings = self._run(tmp_path, """
            import jax
            def stage(batch, sharding, device):
                a = jax.device_put(batch, sharding)
                b = jax.device_put(batch, device)
                return a, b
        """)
        assert findings == []

    def test_placement_kwargs_are_placed(self, tmp_path):
        findings = self._run(tmp_path, """
            import jax
            def stage(batch, s, d):
                a = jax.device_put(batch, sharding=s)
                b = jax.device_put(batch, device=d)
                return a, b
        """)
        assert findings == []

    def test_blessed_helper_module_exempt(self, tmp_path):
        findings = self._run(tmp_path, """
            import jax
            def fetch_to_host(out):
                return jax.device_get(out)
        """, filename="ai4e_tpu/runtime/mesh/placement.py")
        assert findings == []

    def test_outside_device_path_not_flagged(self, tmp_path):
        findings = self._run(tmp_path, """
            import jax
            def load(x):
                return jax.device_put(x)
        """, filename="ai4e_tpu/bench.py")
        assert findings == []

    def test_whole_repo_baseline_empty(self):
        """The real tree: every transfer on the serving path is placed
        (registry's fetches route through placement.fetch_to_host) —
        the gate CI enforces from this PR on."""
        from ai4e_tpu.analysis.rules.unplaced import UnplacedDeviceTransfer
        pkg = os.path.join(REPO, "ai4e_tpu")
        paths = []
        for dirpath, _dirs, files in os.walk(pkg):
            paths.extend(os.path.join(dirpath, f)
                         for f in files if f.endswith(".py"))
        result = Analyzer([UnplacedDeviceTransfer()],
                          root=REPO).run(sorted(paths))
        assert [f.render() for f in result.findings] == []

    def test_blessed_label_named_variable(self, tmp_path):
        # The two-line idiom: map first, label with the mapped value.
        findings = self._run(tmp_path, """
            def note(counter, registry, tenant_id):
                label = registry.tenant_label(tenant_id)
                counter.inc(tenant=label)
        """)
        assert findings == []

    def test_blessed_string_constant(self, tmp_path):
        findings = self._run(tmp_path, """
            def note(counter):
                counter.inc(tenant="other")
        """)
        assert findings == []

    def test_non_identity_kwarg_not_flagged(self, tmp_path):
        findings = self._run(tmp_path, """
            def note(counter, route_prefix):
                counter.inc(route=route_prefix, outcome="200")
        """)
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = self._run(tmp_path, """
            def note(counter, tenant_id):
                counter.inc(tenant=tenant_id)  # ai4e: noqa[AIL013] — bounded upstream by construction
        """)
        assert findings == []

    def test_whole_repo_clean(self):
        """The real tree ships with zero findings — the tenancy layer was
        born using the bounded mapper (the gate CI now enforces)."""
        from ai4e_tpu.analysis.rules.metric_label import \
            UnboundedMetricLabel
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg = os.path.join(root, "ai4e_tpu")
        paths = []
        for dirpath, _dirs, files in os.walk(pkg):
            paths.extend(os.path.join(dirpath, f)
                         for f in files if f.endswith(".py"))
        result = Analyzer([UnboundedMetricLabel()],
                          root=root).run(sorted(paths))
        assert [f.render() for f in result.findings] == []


# -- the wire family (AIL016-AIL018) ------------------------------------------
#
# Project-rule fixtures: each test writes a tiny multi-module project
# (server modules registering routes, client modules calling them, a
# docs/API.md carrying the two marked tables) and runs exactly one wire
# rule over it, so assertions never entangle the three rules' outputs.


WIRE_DOC_SHELL = """\
# API

<!-- ai4e:routes -->
| Method | Path | Registered in | Callers |
|---|---|---|---|
{routes}
<!-- /ai4e:routes -->

<!-- ai4e:headers -->
| Header | Emitted by | Read by |
|---|---|---|
{headers}
<!-- /ai4e:headers -->
"""


def wire_run(tmp_path, rule, files, routes="", headers="", doc=True):
    """Write a fixture project under ``tmp_path`` and run one wire rule.
    Returns the full AnalysisResult (tests need ``.suppressed`` too)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    for rel, src in files.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(src))
    if doc:
        d = tmp_path / "docs"
        d.mkdir(exist_ok=True)
        (d / "API.md").write_text(
            WIRE_DOC_SHELL.format(routes=routes, headers=headers))
    return Analyzer([rule], root=str(tmp_path)).run([str(pkg)])


_ROUTES_SERVER = """
    from aiohttp import web

    async def upsert(request):
        return web.json_response({})

    async def ping(request):
        return web.json_response({})

    def attach(app):
        app.router.add_post("/v1/store/upsert", upsert)
        app.router.add_get("/v1/store/ping", ping)
"""

_ROUTES_CLIENT = """
    async def save(session, body):
        resp = await session.post("/v1/store/upsert", json=body)
        return await resp.json()

    async def check(session):
        resp = await session.get("/v1/store/ping")
        return resp.status
"""

_ROUTES_ROWS = (
    "| `POST` | `/v1/store/upsert` | `pkg/server.py` | `pkg/client.py` |\n"
    "| `GET` | `/v1/store/ping` | `pkg/server.py` | `pkg/client.py` |")

_TYPO_CLIENT = _ROUTES_CLIENT + """
    async def doomed(session):
        resp = await session.post("/v1/store/upsrt")
        return resp.status
"""

_SUPPRESSED_TYPO_CLIENT = _ROUTES_CLIENT + """
    async def doomed(session):
        resp = await session.post("/v1/store/upsrt")  # ai4e: noqa[AIL016] — exercised here as the rule's own fixture
        return resp.status
"""

_PURGE_SERVER = _ROUTES_SERVER + """
    async def purge(request):
        return web.json_response({})

    def attach_admin(app):
        app.router.add_post("/v1/store/purge", purge)
"""


class TestClientRouteDrift:
    def _rule(self):
        from ai4e_tpu.analysis.rules.wire import ClientRouteDrift
        return ClientRouteDrift()

    def test_in_sync_surface_is_clean(self, tmp_path):
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _ROUTES_SERVER,
                           "pkg/client.py": _ROUTES_CLIENT},
                          routes=_ROUTES_ROWS)
        assert [f.render() for f in result.findings] == []

    def test_typoed_client_path_can_only_404(self, tmp_path):
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _ROUTES_SERVER,
                           "pkg/client.py": _TYPO_CLIENT},
                          routes=_ROUTES_ROWS)
        assert len(result.findings) == 1
        f = result.findings[0]
        assert "no registered route matches" in f.message
        assert f.fingerprint_key == "AIL016|client|POST /v1/store/upsrt"
        assert f.symbol == "doomed"

    def test_dead_route_without_external_row(self, tmp_path):
        rows = _ROUTES_ROWS + (
            "\n| `POST` | `/v1/store/purge` | `pkg/server.py` | — |")
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _PURGE_SERVER,
                           "pkg/client.py": _ROUTES_CLIENT},
                          routes=rows)
        assert len(result.findings) == 1
        f = result.findings[0]
        assert "no client call site" in f.message
        assert f.fingerprint_key == "AIL016|dead-route|POST /v1/store/purge"

    def test_external_caller_row_vouches_for_the_route(self, tmp_path):
        rows = _ROUTES_ROWS + ("\n| `POST` | `/v1/store/purge` | "
                               "`pkg/server.py` | external — operator "
                               "runbook verb |")
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _PURGE_SERVER,
                           "pkg/client.py": _ROUTES_CLIENT},
                          routes=rows)
        assert [f.render() for f in result.findings] == []

    def test_registered_route_absent_from_doc_table(self, tmp_path):
        # ping is called (no dead-route) but its row is missing.
        rows = "| `POST` | `/v1/store/upsert` | `pkg/server.py` | `pkg/client.py` |"
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _ROUTES_SERVER,
                           "pkg/client.py": _ROUTES_CLIENT},
                          routes=rows)
        assert len(result.findings) == 1
        f = result.findings[0]
        assert "absent from docs/API.md" in f.message
        assert f.fingerprint_key == "AIL016|undocumented|GET /v1/store/ping"

    def test_doc_row_nothing_registers_is_stale(self, tmp_path):
        rows = _ROUTES_ROWS + (
            "\n| `DELETE` | `/v1/store/gone` | `pkg/server.py` | — |")
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _ROUTES_SERVER,
                           "pkg/client.py": _ROUTES_CLIENT},
                          routes=rows)
        assert len(result.findings) == 1
        f = result.findings[0]
        assert f.path == "docs/API.md"
        assert "nothing registers it" in f.message
        assert f.fingerprint_key == "AIL016|stale-doc|DELETE /v1/store/gone"

    def test_missing_table_is_one_finding_not_noise(self, tmp_path):
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _ROUTES_SERVER,
                           "pkg/client.py": _ROUTES_CLIENT},
                          doc=False)
        assert [f.fingerprint_key for f in result.findings] == [
            "AIL016|no-table"]
        assert "--dump-wire" in result.findings[0].message

    def test_prefix_registration_matches_full_path_client(self, tmp_path):
        # ``self.prefix + "/models/reload"`` registers as /{**}/models/
        # reload; a client posting base + "/v1/svc/models/reload" must
        # match it (the PR 18 reload verb is wired exactly like this).
        server = """
            from aiohttp import web

            async def reload_weights(request):
                return web.json_response({})

            class Svc:
                def __init__(self, prefix):
                    self.prefix = prefix

                def attach(self, app):
                    app.router.add_post(self.prefix + "/models/reload",
                                        reload_weights)
        """
        client = """
            async def trigger(session, base):
                resp = await session.post(base + "/v1/svc/models/reload")
                return await resp.json()
        """
        rows = ("| `POST` | `/{**}/models/reload` | `pkg/server.py` | "
                "`pkg/client.py` |")
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": server, "pkg/client.py": client},
                          routes=rows)
        assert [f.render() for f in result.findings] == []

    def test_suppression_marker_counts_as_suppressed(self, tmp_path):
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _ROUTES_SERVER,
                           "pkg/client.py": _SUPPRESSED_TYPO_CLIENT},
                          routes=_ROUTES_ROWS)
        assert result.findings == []
        assert result.suppressed == 1

    def test_fingerprint_stable_when_registration_moves_files(self, tmp_path):
        # The contract fingerprint names the CONTRACT, not the file: the
        # same dead route registered from a different module must carry
        # the SAME fingerprint, so refactors don't churn the baseline.
        server = """
            from aiohttp import web

            async def purge(request):
                return web.json_response({})

            def attach(app):
                app.router.add_post("/v1/store/purge", purge)
        """
        rows = "| `POST` | `/v1/store/purge` | `pkg/server.py` | — |"
        a = tmp_path / "a"
        a.mkdir()
        before = wire_run(a, self._rule(), {"pkg/server.py": server},
                          routes=rows)
        b = tmp_path / "b"
        b.mkdir()
        after = wire_run(b, self._rule(), {"pkg/registry.py": server},
                         routes=rows)
        assert len(before.findings) == len(after.findings) == 1
        assert before.findings[0].path != after.findings[0].path
        assert before.findings[0].fingerprint == after.findings[0].fingerprint


_HDR_EMIT = """
    from aiohttp import web

    async def shed(request):
        return web.json_response(
            {}, status=503, headers={"X-Shed-Reason": "quota"})
"""

_HDR_READ = """
    async def watch(session):
        resp = await session.get("http://svc/v1/x")
        return resp.headers.get("X-Shed-Reason")
"""

_HDR_ROWS = "| `X-Shed-Reason` | `pkg/emit.py` | `pkg/read.py` |"


class TestHeaderVocabularyDrift:
    def _rule(self):
        from ai4e_tpu.analysis.rules.wire import HeaderVocabularyDrift
        return HeaderVocabularyDrift()

    def test_round_tripped_header_is_clean(self, tmp_path):
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT,
                           "pkg/read.py": _HDR_READ},
                          headers=_HDR_ROWS)
        assert [f.render() for f in result.findings] == []

    def test_header_outside_vocabulary_is_typo_minted(self, tmp_path):
        # Emitted AND read in code (so only the vocabulary check can
        # fire) but absent from the table: the typo-minted shape.
        emit = """
            async def shed(request, web):
                return web.json_response(
                    {}, status=503, headers={"X-Shed-Reasn": "quota"})
        """
        read = """
            async def watch(session):
                resp = await session.get("http://svc/v1/x")
                return resp.headers.get("X-Shed-Reasn")
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": emit, "pkg/read.py": read},
                          headers=_HDR_ROWS.replace(
                              "X-Shed-Reason", "X-Other"))
        keys = [f.fingerprint_key for f in result.findings]
        assert "AIL017|vocab|X-Shed-Reasn" in keys
        assert any("typo-minted" in f.message for f in result.findings)

    def test_emit_without_reader_and_no_external_row(self, tmp_path):
        rows = _HDR_ROWS + "\n| `X-Cost-Tier` | `pkg/price.py` | — |"
        price = """
            async def price(request, resp):
                resp.headers["X-Cost-Tier"] = "batch"
                return resp
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT, "pkg/read.py": _HDR_READ,
                           "pkg/price.py": price},
                          headers=rows)
        assert [f.fingerprint_key for f in result.findings] == [
            "AIL017|emit-no-reader|X-Cost-Tier"]

    def test_documented_external_reader_vouches(self, tmp_path):
        rows = _HDR_ROWS + ("\n| `X-Cost-Tier` | `pkg/price.py` | "
                            "external — billing scraper |")
        price = """
            async def price(request, resp):
                resp.headers["X-Cost-Tier"] = "batch"
                return resp
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT, "pkg/read.py": _HDR_READ,
                           "pkg/price.py": price},
                          headers=rows)
        assert [f.render() for f in result.findings] == []

    def test_read_without_emitter_and_no_external_row(self, tmp_path):
        rows = _HDR_ROWS + "\n| `X-Deadline-Ms` | — | `pkg/budget.py` |"
        budget = """
            async def deadline(request):
                return request.headers.get("X-Deadline-Ms")
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT, "pkg/read.py": _HDR_READ,
                           "pkg/budget.py": budget},
                          headers=rows)
        assert [f.fingerprint_key for f in result.findings] == [
            "AIL017|read-no-emitter|X-Deadline-Ms"]

    def test_documented_external_emitter_vouches(self, tmp_path):
        rows = _HDR_ROWS + ("\n| `X-Deadline-Ms` | external — load "
                            "clients set the budget | `pkg/budget.py` |")
        budget = """
            async def deadline(request):
                return request.headers.get("X-Deadline-Ms")
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT, "pkg/read.py": _HDR_READ,
                           "pkg/budget.py": budget},
                          headers=rows)
        assert [f.render() for f in result.findings] == []

    def test_doc_row_nothing_uses_is_stale(self, tmp_path):
        rows = _HDR_ROWS + "\n| `X-Gone` | `pkg/emit.py` | `pkg/read.py` |"
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT,
                           "pkg/read.py": _HDR_READ},
                          headers=rows)
        assert [f.fingerprint_key for f in result.findings] == [
            "AIL017|stale-doc|X-Gone"]
        assert result.findings[0].path == "docs/API.md"

    def test_constant_resolved_emit_round_trips(self, tmp_path):
        # ``resp.headers[SHED_HEADER] = …`` resolves through the
        # *_HEADER constant map; the defining assignment itself is a
        # mention, not an emit obligation.
        emit = """
            SHED_HEADER = "X-Shed-Reason"

            async def shed(request, resp):
                resp.headers[SHED_HEADER] = "quota"
                return resp
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": emit, "pkg/read.py": _HDR_READ},
                          headers=_HDR_ROWS)
        assert [f.render() for f in result.findings] == []

    def test_suppression_marker_counts_as_suppressed(self, tmp_path):
        price = """
            async def price(request, resp):
                resp.headers["X-Cost-Tier"] = "batch"  # ai4e: noqa[AIL017] — fixture for this very test
                return resp
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/emit.py": _HDR_EMIT, "pkg/read.py": _HDR_READ,
                           "pkg/price.py": price},
                          headers=_HDR_ROWS)
        assert result.findings == []
        assert result.suppressed >= 1


_REFUSE_SERVER = """
    from aiohttp import web

    def _refuse():
        return web.json_response({"error": "busy"}, status=503)

    async def upsert(request):
        if request.content_length and request.content_length > 1024:
            return _refuse()
        return web.json_response({})

    def attach(app):
        app.router.add_post("/v1/store/upsert", upsert)
"""


class TestUnhandledRefusalStatus:
    def _rule(self):
        from ai4e_tpu.analysis.rules.wire import UnhandledRefusalStatus
        return UnhandledRefusalStatus()

    def test_unbranched_503_is_a_finding(self, tmp_path):
        client = """
            async def save(session, body):
                resp = await session.post("/v1/store/upsert", json=body)
                if resp.status != 200:
                    raise RuntimeError("save failed")
                body = await resp.json()
                return body
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _REFUSE_SERVER,
                           "pkg/client.py": client})
        assert len(result.findings) == 1
        f = result.findings[0]
        assert "503" in f.message and "backpressure" in f.message
        assert f.fingerprint_key == "AIL018|POST /v1/store/upsert|503|save"

    def test_branching_on_the_status_is_clean(self, tmp_path):
        client = """
            async def save(session, body):
                resp = await session.post("/v1/store/upsert", json=body)
                if resp.status in (429, 503):
                    raise TimeoutError("store shed the write; retry later")
                if resp.status != 200:
                    raise RuntimeError("save failed")
                body = await resp.json()
                return body
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _REFUSE_SERVER,
                           "pkg/client.py": client})
        assert [f.render() for f in result.findings] == []

    def test_module_helper_one_hop_counts_as_handled(self, tmp_path):
        # The fix idiom this PR applied everywhere: a module-level
        # ``_raise_refusal(resp)`` the response is passed to. Its
        # compares count for the caller (one hop, symmetric with the
        # server-side handler hop).
        client = """
            def _raise_refusal(resp):
                if resp.status == 503:
                    raise TimeoutError("store refused; retry later")

            async def save(session, body):
                resp = await session.post("/v1/store/upsert", json=body)
                _raise_refusal(resp)
                if resp.status != 200:
                    raise RuntimeError("save failed")
                body = await resp.json()
                return body
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _REFUSE_SERVER,
                           "pkg/client.py": client})
        assert [f.render() for f in result.findings] == []

    def test_raise_for_status_does_not_distinguish(self, tmp_path):
        # ``resp.raise_for_status()`` is generic failure, not a branch on
        # the refusal contract — the exact bug class the first run caught
        # in service/task_manager.py.
        client = """
            async def save(session, body):
                resp = await session.post("/v1/store/upsert", json=body)
                resp.raise_for_status()
                body = await resp.json()
                return body
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _REFUSE_SERVER,
                           "pkg/client.py": client})
        assert [f.fingerprint_key for f in result.findings] == [
            "AIL018|POST /v1/store/upsert|503|save"]

    def test_propagating_transport_helper_is_exempt(self, tmp_path):
        client = """
            async def _request(session, body):
                resp = await session.post("/v1/store/upsert", json=body)
                return resp
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _REFUSE_SERVER,
                           "pkg/client.py": client})
        assert [f.render() for f in result.findings] == []

    def test_http_conflict_constructor_counts_as_409(self, tmp_path):
        server = """
            from aiohttp import web

            async def reload_weights(request):
                if request.app.get("draining"):
                    raise web.HTTPConflict(text="draining")
                return web.json_response({})

            def attach(app):
                app.router.add_post("/v1/models/reload", reload_weights)
        """
        client = """
            async def trigger(session):
                resp = await session.post("/v1/models/reload")
                if resp.status != 200:
                    raise RuntimeError("reload failed")
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": server,
                           "pkg/client.py": client})
        assert len(result.findings) == 1
        assert "409" in result.findings[0].message
        assert "conflict" in result.findings[0].message

    def test_undistinguished_statuses_carry_no_obligation(self, tmp_path):
        # 404 is not part of the refusal contract: no caller obligation.
        server = """
            from aiohttp import web

            async def fetch(request):
                if not request.query.get("id"):
                    return web.json_response({}, status=404)
                return web.json_response({})

            def attach(app):
                app.router.add_get("/v1/store/task", fetch)
        """
        client = """
            async def load(session):
                resp = await session.get("/v1/store/task")
                body = await resp.json()
                return body
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": server,
                           "pkg/client.py": client})
        assert [f.render() for f in result.findings] == []

    def test_suppression_marker_counts_as_suppressed(self, tmp_path):
        client = """
            async def save(session, body):
                resp = await session.post("/v1/store/upsert", json=body)  # ai4e: noqa[AIL018] — fixture for this very test
                if resp.status != 200:
                    raise RuntimeError("save failed")
                body = await resp.json()
                return body
        """
        result = wire_run(tmp_path, self._rule(),
                          {"pkg/server.py": _REFUSE_SERVER,
                           "pkg/client.py": client})
        assert result.findings == []
        assert result.suppressed == 1


# -- AIL019 unused-suppression ------------------------------------------------


class TestUnusedSuppression:
    def _run(self, tmp_path, source, rules):
        f = tmp_path / "m.py"
        f.write_text(textwrap.dedent(source))
        return Analyzer(rules, root=str(tmp_path)).run([str(f)])

    def _rules(self):
        from ai4e_tpu.analysis.rules.unused_noqa import UnusedSuppression
        return [BlockingCallInAsync(), UnusedSuppression()]

    def test_stale_marker_is_a_finding(self, tmp_path):
        result = self._run(tmp_path, """
            x = 1  # ai4e: noqa[AIL001] — the sleep this blessed is long gone
        """, self._rules())
        assert [f.rule for f in result.findings] == ["AIL019"]
        assert "AIL001" in result.findings[0].message
        assert "does not fire on" in result.findings[0].message

    def test_live_marker_suppresses_and_is_not_flagged(self, tmp_path):
        result = self._run(tmp_path, """
            import time
            async def h():
                time.sleep(1)  # ai4e: noqa[AIL001] — fixture: rule genuinely fires here
        """, self._rules())
        assert result.findings == []
        assert result.suppressed == 1

    def test_marker_for_inactive_rule_is_unproven_not_unused(self, tmp_path):
        # Under --select the suppressed rule never ran: flagging the
        # marker as unused would be a lie.
        from ai4e_tpu.analysis.rules.unused_noqa import UnusedSuppression
        result = self._run(tmp_path, """
            x = 1  # ai4e: noqa[AIL001] — AIL001 is not in this run
        """, [UnusedSuppression()])
        assert result.findings == []

    def test_justified_keep_via_ail019_in_the_marker(self, tmp_path):
        result = self._run(tmp_path, """
            x = 1  # ai4e: noqa[AIL001,AIL019] — fires only under the py3.12 parser
        """, self._rules())
        assert result.findings == []
        assert result.suppressed == 1


# -- --sarif / --stats / --dump-wire / --list-rules ---------------------------


class TestSarifOutput:
    def test_findings_emit_sarif_with_matching_fingerprints(self, tmp_path,
                                                            capsys):
        import json as _json
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text(
            "import time\nasync def h():\n    time.sleep(1)\n")
        base = [str(tmp_path / "m.py"), "--root", str(tmp_path),
                "--select", "AIL001"]
        assert main(base + ["--json"]) == 1
        fp = _json.loads(capsys.readouterr().out)["findings"][0]["fingerprint"]
        assert main(base + ["--sarif"]) == 1
        doc = _json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        driver = run["tool"]["driver"]
        assert driver["name"] == "ai4e-lint"
        assert any(r["id"] == "AIL001" for r in driver["rules"])
        res = run["results"][0]
        assert res["ruleId"] == "AIL001"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "m.py"
        assert loc["region"]["startLine"] == 3
        # Same identity as the baseline fingerprint: annotations survive
        # pushes that merely move the finding, exactly like the baseline.
        assert res["partialFingerprints"]["ai4eFingerprint/v1"] == fp

    def test_clean_tree_exits_zero_with_empty_results(self, tmp_path, capsys):
        import json as _json
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text("x = 1\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL001", "--sarif"]) == 0
        doc = _json.loads(capsys.readouterr().out)
        assert doc["runs"][0]["results"] == []


class TestStatsAndParseCache:
    def test_stats_json_carries_per_rule_seconds(self, tmp_path, capsys):
        import json as _json
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text("x = 1\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL001", "--json", "--stats"]) == 0
        stats = _json.loads(capsys.readouterr().out)["stats"]
        assert set(stats) == {"parse_seconds", "total_seconds",
                              "rule_seconds"}
        assert "AIL001" in stats["rule_seconds"]
        assert stats["total_seconds"] >= stats["parse_seconds"] >= 0

    def test_stats_text_total_line_matches_the_lint_sh_scrape(self, tmp_path,
                                                              capsys):
        # scripts/lint.sh extracts the total with
        # ``sed -n 's/^stats: .*total \([0-9][0-9]*\) ms$/\1/p'`` — the
        # stderr format is load-bearing.
        import re
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text("x = 1\n")
        assert main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                     "--select", "AIL001", "--stats"]) == 0
        err = capsys.readouterr().err
        assert re.search(r"(?m)^stats: .*total \d+ ms$", err)
        assert re.search(r"(?m)^stats: AIL001\s+[\d.]+ ms$", err)

    def test_parse_cache_reuses_tree_until_content_changes(self, tmp_path):
        from ai4e_tpu.analysis.core import parse_module
        p = tmp_path / "m.py"
        p.write_text("x = 1\n")
        m1 = parse_module(str(p), "m.py")
        m2 = parse_module(str(p), "m.py")
        assert m2.tree is m1.tree and m2.source is m1.source
        p.write_text("y = 22222\n")
        st = os.stat(p)
        os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 10**9))
        m3 = parse_module(str(p), "m.py")
        assert m3.tree is not m1.tree
        assert "y = 22222" in m3.source

    def test_parse_cache_invalidates_on_mtime_alone(self, tmp_path):
        # Same byte length, newer mtime: the cache must re-read (size
        # alone is not identity).
        from ai4e_tpu.analysis.core import parse_module
        p = tmp_path / "m.py"
        p.write_text("x = 1\n")
        m1 = parse_module(str(p), "m.py")
        p.write_text("x = 2\n")
        st = os.stat(p)
        os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 10**9))
        m3 = parse_module(str(p), "m.py")
        assert "x = 2" in m3.source


class TestDumpWire:
    def test_prints_both_marked_tables_from_the_surface(self, tmp_path,
                                                        capsys):
        from ai4e_tpu.analysis.cli import main
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "server.py").write_text(textwrap.dedent(_ROUTES_SERVER))
        (pkg / "client.py").write_text(textwrap.dedent(_ROUTES_CLIENT))
        (pkg / "emit.py").write_text(textwrap.dedent(_HDR_EMIT))
        (pkg / "read.py").write_text(textwrap.dedent(_HDR_READ))
        assert main([str(pkg), "--root", str(tmp_path), "--dump-wire"]) == 0
        out = capsys.readouterr().out
        assert "<!-- ai4e:routes -->" in out and "<!-- /ai4e:routes -->" in out
        assert "<!-- ai4e:headers -->" in out
        assert "`/v1/store/upsert`" in out
        assert "`X-Shed-Reason`" in out


class TestListRulesFamilies:
    def test_wire_family_is_grouped_and_banners_dodge_the_grep(self, capsys):
        from ai4e_tpu.analysis.cli import main
        assert main(["--list-rules"]) == 0
        lines = capsys.readouterr().out.splitlines()
        banners = [l for l in lines if l.startswith("#")]
        assert "# wire contracts (cross-process)" in banners
        # scripts/lint.sh counts rules with `grep -c '^AIL'`: exactly one
        # line per registered rule, banners excluded.
        ail_lines = [l for l in lines if l.startswith("AIL")]
        assert len(ail_lines) == len(ALL_RULES)
        wire_at = lines.index("# wire contracts (cross-process)")
        first_wire = next(i for i, l in enumerate(lines)
                          if l.startswith("AIL016"))
        assert wire_at < first_wire


# -- the wire gate ships armed ------------------------------------------------


class TestWireGateRegistration:
    def test_wire_and_hygiene_rules_are_registered(self):
        ids = {cls.rule_id for cls in ALL_RULES}
        assert {"AIL016", "AIL017", "AIL018", "AIL019"} <= ids
        assert len(ids) >= 19

    def test_checked_in_baseline_is_empty(self):
        """ISSUE 19 acceptance: the wire family's first-run findings were
        all FIXED in this PR, not baselined — the baseline ships empty."""
        import json as _json
        with open(os.path.join(REPO, "analysis_baseline.json")) as fh:
            data = _json.load(fh)
        assert data["findings"] == []


# -- behavioral regressions for the refusal-contract fixes --------------------


class _FakeResp:
    def __init__(self, status, headers=None):
        self.status = status
        self.headers = headers or {}


class TestTypedRefusalFixes:
    """AIL018's first run flagged every store-client write path for
    swallowing the 503 backpressure / fence-409 refusals; the fix routes
    them through typed module helpers. Pin the helpers' contract."""

    def test_task_manager_types_503_with_retry_after(self):
        from ai4e_tpu.service.task_manager import (StoreRefusalError,
                                                   _raise_refusal)
        with pytest.raises(StoreRefusalError) as ei:
            _raise_refusal(_FakeResp(503, {"Retry-After": "3",
                                           "X-Shed-Reason": "journal-degraded"}))
        assert ei.value.status == 503
        assert ei.value.retry_after == "3"
        assert "journal-degraded" in str(ei.value)

    def test_task_manager_types_fence_409_only(self):
        from ai4e_tpu.service.task_manager import (StoreRefusalError,
                                                   _raise_refusal)
        with pytest.raises(StoreRefusalError) as ei:
            _raise_refusal(_FakeResp(409, {"X-Not-Owner": "1"}))
        assert ei.value.status == 409
        # A bare 409 is the conditional-update precondition branch, not
        # the ring fence: it must pass through untyped.
        _raise_refusal(_FakeResp(409))
        _raise_refusal(_FakeResp(200))
        _raise_refusal(_FakeResp(404))

    def test_store_refusal_rides_the_not_primary_handling(self):
        # The gateway's standby handling (503 + Retry-After) catches
        # NotPrimaryError; the typed refusal must be a subclass so store
        # refusals surface as retryable refusals, not 500s.
        from ai4e_tpu.service.task_manager import StoreRefusalError
        from ai4e_tpu.taskstore import NotPrimaryError
        assert issubclass(StoreRefusalError, NotPrimaryError)

    def test_rig_wire_refusal_helper(self):
        from ai4e_tpu.rig.wire import _raise_refusal
        from ai4e_tpu.taskstore import NotPrimaryError
        with pytest.raises(NotPrimaryError) as ei:
            _raise_refusal(_FakeResp(503, {"Retry-After": "2"}))
        assert "retry after 2s" in str(ei.value)
        with pytest.raises(NotPrimaryError):
            _raise_refusal(_FakeResp(409, {"X-Not-Owner": "1"}))
        _raise_refusal(_FakeResp(409))
        _raise_refusal(_FakeResp(200))


# -- the balance family (AIL020-AIL022) ---------------------------------------
#
# AIL020 per-rule fixtures follow the repo convention: at least one true
# positive per escape class (return, raise, end, suspension-abandonment),
# one near-miss per blessed idiom (finally, context manager,
# close-before-reraise, guard-if, ownership handoff, callback handoff),
# and one suppression case. The engine lives in analysis/balance.py; the
# pair table is PAIR_SPECS (limiter-slot and gauge-updown carry the
# fixtures — no anchor, no receiver constraint).


def balance_run(tmp_path, source, filename="mod.py"):
    from ai4e_tpu.analysis.rules.balance import UnbalancedPairedEffect
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return Analyzer([UnbalancedPairedEffect()],
                    root=str(tmp_path)).run([str(f)])


class TestUnbalancedPairedEffect:
    def test_true_positive_return_escape(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                async def h(self, ok):
                    self.limiter.acquire()
                    if ok:
                        return 1
                    self.limiter.release()
        """)
        assert [f.rule for f in result.findings] == ["AIL020"]
        f = result.findings[0]
        assert "limiter-slot" in f.message and "return path" in f.message
        assert f.symbol == "C.h"

    def test_true_positive_raise_escape_missing_close_before_reraise(
            self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                def h(self):
                    self.limiter.acquire()
                    try:
                        work()
                    except Exception:
                        raise
                    self.limiter.release()
        """)
        assert [f.rule for f in result.findings] == ["AIL020"]
        assert "raise path" in result.findings[0].message

    def test_true_positive_end_escape(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                def h(self, ok):
                    self.limiter.acquire()
                    if ok:
                        self.limiter.release()
        """)
        assert [f.rule for f in result.findings] == ["AIL020"]
        assert "unconditional close" in result.findings[0].message

    def test_true_positive_suspension_abandonment(self, tmp_path):
        """Every textual path closes — but the await between open and
        close abandons the frame on cancellation. The leak mode reviews
        miss; the reason finally/CM are the only full protections."""
        result = balance_run(tmp_path, """
            import asyncio
            class C:
                async def h(self):
                    self.limiter.acquire()
                    await asyncio.sleep(0)
                    self.limiter.release()
        """)
        assert [f.rule for f in result.findings] == ["AIL020"]
        assert "cancelled await" in result.findings[0].message

    def test_near_miss_no_await_in_span_is_clean(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                async def h(self):
                    self.limiter.acquire()
                    x = compute()
                    self.limiter.release()
                    await publish(x)
        """)
        assert result.findings == []

    def test_near_miss_finally_blessed(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                async def h(self):
                    self.limiter.acquire()
                    try:
                        await work()
                    finally:
                        self.limiter.release()
        """)
        assert result.findings == []

    def test_near_miss_guard_if_shape(self, tmp_path):
        """The pervasive production shape: a conditional open paired
        with an identically-guarded close in the finally (dispatcher /
        router orchestration accounting)."""
        result = balance_run(tmp_path, """
            async def h(orch):
                if orch is not None:
                    orch.acquire()
                try:
                    await work()
                finally:
                    if orch is not None:
                        orch.release()
        """)
        assert result.findings == []

    def test_near_miss_close_before_reraise(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                def h(self):
                    self.limiter.acquire()
                    try:
                        work()
                    except Exception:
                        self.limiter.release()
                        raise
                    self.limiter.release()
        """)
        assert result.findings == []

    def test_near_miss_context_manager_blessed(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                async def h(self, ok):
                    with self.pool.acquire() as conn:
                        if ok:
                            return conn
                    slot = self.pool.acquire()
                    try:
                        await work(slot)
                    finally:
                        self.pool.release(slot)
        """)
        assert result.findings == []

    def test_near_miss_ownership_handoff(self, tmp_path):
        """decode.py's _admit shape: the open's result is stored into a
        container — the effect has a new owner with its own lifecycle."""
        result = balance_run(tmp_path, """
            class C:
                def h(self, busy):
                    slot = self.pool.acquire()
                    if busy:
                        self.pool.release(slot)
                        return None
                    self._active[slot] = slot
        """)
        assert result.findings == []

    def test_near_miss_callback_handoff(self, tmp_path):
        """batcher.py's window shape: the close rides the task's done
        callback, not this frame."""
        result = balance_run(tmp_path, """
            class C:
                async def h(self, loop):
                    await self._window.acquire()
                    task = loop.create_task(run())
                    def _done(t):
                        self._window.release()
                    task.add_done_callback(_done)
        """)
        assert result.findings == []

    def test_near_miss_open_without_close_is_cross_function(self, tmp_path):
        """An open whose close lives in a different function is a
        protocol endpoint — out of scope, never flagged."""
        result = balance_run(tmp_path, """
            class C:
                def prologue(self):
                    self._gate._reserve()
                    return True
        """)
        assert result.findings == []

    def test_gauge_requires_same_receiver(self, tmp_path):
        """gauge-updown is same_receiver: another gauge's dec() does not
        close this gauge's inc()."""
        result = balance_run(tmp_path, """
            class C:
                def h(self, ok):
                    self._pending.inc()
                    if ok:
                        return 1
                    self._pending.dec()
        """)
        assert [f.rule for f in result.findings] == ["AIL020"]
        assert "gauge-updown" in result.findings[0].message

    def test_suppression(self, tmp_path):
        result = balance_run(tmp_path, """
            class C:
                def h(self, ok):
                    self.limiter.acquire()  # ai4e: noqa[AIL020] — fixture for this very test
                    if ok:
                        return 1
                    self.limiter.release()
        """)
        assert result.findings == []
        assert result.suppressed == 1

    def test_fingerprint_stable_under_file_move(self, tmp_path):
        """The effect-identity fingerprint is pair name + enclosing
        symbol + escape kind + open snippet — moving the file must not
        churn the baseline."""
        src = """
            class C:
                def h(self, ok):
                    self.limiter.acquire()
                    if ok:
                        return 1
                    self.limiter.release()
        """
        a = balance_run(tmp_path, src, filename="a.py").findings
        b = balance_run(tmp_path, src, filename="moved/deep/b.py").findings
        assert len(a) == len(b) == 1
        assert a[0].path != b[0].path
        assert a[0].fingerprint == b[0].fingerprint


class TestVerbatimRevertCaught:
    """ISSUE 20 acceptance: a verbatim pre-fix revert of a real,
    hand-fixed production bug must be CAUGHT by AIL020. The PR 8 class:
    the worker's DrainingError handler stamps RETRY into the request's
    hop-ledger buffer and must flush before redelivering — deleting the
    flush loses the draining timeline of exactly the retried task."""

    WORKER = os.path.join(REPO, "ai4e_tpu", "runtime", "worker.py")

    def _sources(self):
        with open(self.WORKER) as fh:
            src = fh.read()
        anchor = src.index('reason="draining"')
        cut = src.index("await self._flush_ledger", anchor)
        line_start = src.rindex("\n", 0, cut)
        line_end = src.index("\n", cut)
        broken = src[:line_start] + src[line_end:]
        assert broken != src
        import ast as _ast
        _ast.parse(broken)  # the surgery must leave valid syntax
        return src, broken

    def test_pristine_worker_is_clean(self, tmp_path):
        src, _ = self._sources()
        f = tmp_path / "worker.py"
        f.write_text(src)
        from ai4e_tpu.analysis.rules.balance import UnbalancedPairedEffect
        result = Analyzer([UnbalancedPairedEffect()],
                          root=str(tmp_path)).run([str(f)])
        assert result.findings == []

    def test_deleted_drain_flush_is_caught(self, tmp_path):
        _, broken = self._sources()
        f = tmp_path / "worker.py"
        f.write_text(broken)
        from ai4e_tpu.analysis.rules.balance import UnbalancedPairedEffect
        result = Analyzer([UnbalancedPairedEffect()],
                          root=str(tmp_path)).run([str(f)])
        hits = [x for x in result.findings
                if "ledger-buffer-flush" in x.message]
        assert hits, "\n".join(x.render() for x in result.findings)
        assert 'buf.stamp' in hits[0].snippet


# -- AIL021 journal-replay-round-trip -----------------------------------------


_STORE_CLEAN = """
    class Store:
        def __init__(self):
            self._lines = []
            self._results = {}

        def _append(self, rec):
            self._lines.append(rec)

        def finish(self, task_id, status):
            self._append({"taskId": task_id, "result": True,
                          "status": status})

        def evict(self, task_id):
            self._append({"taskId": task_id, "evict": True,
                          "status": "evicted"})

        def _apply_replay_record(self, rec):
            if rec.get("result"):
                self._results[rec["taskId"]] = rec["status"]
            if rec.get("evict"):
                self._results.pop(rec["taskId"], None)
"""


def journal_run(tmp_path, source):
    from ai4e_tpu.analysis.rules.balance import JournalReplayRoundTrip
    f = tmp_path / "pkg" / "taskstore" / "store.py"
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return Analyzer([JournalReplayRoundTrip()],
                    root=str(tmp_path)).run([str(tmp_path / "pkg")])


class TestJournalReplayRoundTrip:
    def test_clean_round_trip(self, tmp_path):
        result = journal_run(tmp_path, _STORE_CLEAN)
        assert result.findings == []

    def test_writer_without_replay_branch(self, tmp_path):
        """A record marker written but never consulted at replay: that
        record type silently drops durable state at restart."""
        src = _STORE_CLEAN.replace(
            '            if rec.get("evict"):\n'
            '                self._results.pop(rec["taskId"], None)\n', "")
        assert src != _STORE_CLEAN
        result = journal_run(tmp_path, src)
        assert [f.rule for f in result.findings] == ["AIL021"]
        f = result.findings[0]
        assert "'evict' is written" in f.message
        assert f.fingerprint_key == "AIL021|writer-without-replay|evict"
        assert f.symbol == "Store.evict"

    def test_replay_branch_without_writer(self, tmp_path):
        src = _STORE_CLEAN + """
        def _apply_ghost(self):
            pass
"""
        src = src.replace(
            'if rec.get("result"):',
            'if rec.get("ghost"):\n'
            '                pass\n'
            '            if rec.get("result"):')
        result = journal_run(tmp_path, src)
        assert [f.rule for f in result.findings] == ["AIL021"]
        f = result.findings[0]
        assert "consults 'ghost'" in f.message
        assert f.fingerprint_key == "AIL021|replay-without-writer|ghost"

    def test_arming_no_replay_entrypoint(self, tmp_path):
        """The self-honesty arm: renaming _apply_replay_record away must
        fire, not silently disarm the round-trip check."""
        src = _STORE_CLEAN.replace("_apply_replay_record", "_renamed_away")
        result = journal_run(tmp_path, src)
        assert [f.rule for f in result.findings] == ["AIL021"]
        assert "no _apply_replay_record()" in result.findings[0].message

    def test_arming_no_writer_surface(self, tmp_path):
        src = _STORE_CLEAN.replace("self._append(", "self._renamed(")
        result = journal_run(tmp_path, src)
        assert [f.rule for f in result.findings] == ["AIL021"]
        assert "no journal writer calls" in result.findings[0].message

    def test_payload_keys_are_not_protocol(self, tmp_path):
        """taskId/status are payload (not True-valued, dict > 2 keys):
        consulting them outside a test is fine, and NOT consulting a
        payload key is fine too — only markers select replay arms."""
        src = _STORE_CLEAN.replace('"status": status})',
                                   '"status": status, "extra": 1})')
        result = journal_run(tmp_path, src)
        assert result.findings == []

    def test_real_store_round_trip_is_clean(self):
        """The production journal protocol (Slim/Result/Offloaded/Evict/
        KeepBlobs/Epoch) round-trips — the same surface AIL021 audits in
        the repo gate."""
        from ai4e_tpu.analysis.rules.balance import JournalReplayRoundTrip
        result = Analyzer([JournalReplayRoundTrip()], root=REPO).run(
            [os.path.join(REPO, "ai4e_tpu", "taskstore")])
        assert result.findings == []


# -- AIL022 pair-spec drift ---------------------------------------------------


class TestPairSpecDrift:
    def test_missing_close_symbol_fires(self, tmp_path):
        """The anchor module is in the scan but a declared close no
        longer resolves anywhere: the rename that would silently disarm
        AIL020's probe-slot conservation."""
        from ai4e_tpu.analysis.rules.balance import PairSpecDrift
        f = tmp_path / "pkg" / "resilience" / "breaker.py"
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent("""
            class CircuitBreaker:
                def begin_probe(self):
                    pass
                def record_success(self):
                    pass
                def record_failure(self):
                    pass
        """))
        result = Analyzer([PairSpecDrift()],
                          root=str(tmp_path)).run([str(tmp_path / "pkg")])
        assert [f.rule for f in result.findings] == ["AIL022"]
        f0 = result.findings[0]
        assert "'record_neutral'" in f0.message
        assert f0.fingerprint_key == "AIL022|probe-slot|record_neutral"

    def test_all_symbols_resolve_is_clean(self, tmp_path):
        from ai4e_tpu.analysis.rules.balance import PairSpecDrift
        f = tmp_path / "pkg" / "resilience" / "breaker.py"
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent("""
            class CircuitBreaker:
                def begin_probe(self):
                    pass
                def record_success(self):
                    pass
                def record_failure(self):
                    pass
                def record_neutral(self):
                    pass
        """))
        result = Analyzer([PairSpecDrift()],
                          root=str(tmp_path)).run([str(tmp_path / "pkg")])
        assert result.findings == []

    def test_anchor_not_in_scan_is_skipped(self, tmp_path):
        """Scanning a slice that doesn't include the pair's home surface
        must not produce drift noise (the --changed-only case is handled
        separately: project rules are skipped entirely there)."""
        from ai4e_tpu.analysis.rules.balance import PairSpecDrift
        f = tmp_path / "pkg" / "other.py"
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text("x = 1\n")
        result = Analyzer([PairSpecDrift()],
                          root=str(tmp_path)).run([str(tmp_path / "pkg")])
        assert result.findings == []


# -- balance-family registration + CLI satellites -----------------------------


class TestBalanceGateRegistration:
    def test_balance_rules_are_registered(self):
        ids = {cls.rule_id for cls in ALL_RULES}
        assert {"AIL020", "AIL021", "AIL022"} <= ids
        assert len(ids) >= 22

    def test_list_rules_shows_balance_family(self, capsys):
        from ai4e_tpu.analysis.cli import main
        assert main(["--list-rules"]) == 0
        lines = capsys.readouterr().out.splitlines()
        assert "# paired-effect conservation" in lines
        fam_at = lines.index("# paired-effect conservation")
        first = next(i for i, l in enumerate(lines)
                     if l.startswith("AIL020"))
        assert fam_at < first

    def test_checked_in_baseline_still_empty(self):
        """ISSUE 20 acceptance: everything the balance family's first
        run found was fixed (or was a blessed idiom the engine now
        models), not baselined — the baseline ships empty."""
        import json as _json
        with open(os.path.join(REPO, "analysis_baseline.json")) as fh:
            data = _json.load(fh)
        assert data.get("findings", data if isinstance(data, list)
                        else []) == []


class TestChangedOnly:
    def _git(self, cwd, *args):
        import subprocess
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=cwd, check=True, capture_output=True)

    def test_scopes_to_changed_files_and_skips_project_rules(
            self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "clean.py").write_text(
            "import time\nasync def old():\n    time.sleep(1)\n")
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "add", ".")
        self._git(tmp_path, "commit", "-qm", "seed")
        (pkg / "fresh.py").write_text(
            "import time\nasync def h():\n    time.sleep(2)\n")
        rc = main([str(pkg), "--root", str(tmp_path), "--no-baseline",
                   "--changed-only", "HEAD"])
        out = capsys.readouterr().out
        # Only the changed file is scanned: the committed TP in clean.py
        # does not gate the pre-commit loop (CI's full run still does).
        assert rc == 1
        assert "1 file(s)" in out
        assert "fresh.py" in out and "clean.py" not in out

    def test_no_changes_is_a_clean_pass(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "clean.py").write_text("x = 1\n")
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "add", ".")
        self._git(tmp_path, "commit", "-qm", "seed")
        rc = main([str(pkg), "--root", str(tmp_path), "--no-baseline",
                   "--changed-only", "HEAD"])
        assert rc == 0
        assert "nothing to scan" in capsys.readouterr().out

    def test_bad_ref_is_a_loud_config_error(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "m.py").write_text("x = 1\n")
        self._git(tmp_path, "init", "-q")
        rc = main([str(pkg), "--root", str(tmp_path), "--no-baseline",
                   "--changed-only", "no-such-ref"])
        assert rc == 2
        assert "git" in capsys.readouterr().err


class TestBudgetMs:
    def test_over_budget_exits_4(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text("x = 1\n")
        rc = main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                   "--no-baseline", "--budget-ms", "0"])
        assert rc == 4
        assert "exceeds --budget-ms" in capsys.readouterr().err

    def test_within_budget_keeps_findings_exit(self, tmp_path, capsys):
        from ai4e_tpu.analysis.cli import main
        (tmp_path / "m.py").write_text(
            "import time\nasync def h():\n    time.sleep(1)\n")
        rc = main([str(tmp_path / "m.py"), "--root", str(tmp_path),
                   "--no-baseline", "--budget-ms", "600000"])
        assert rc == 1
        assert "exceeds" not in capsys.readouterr().err
