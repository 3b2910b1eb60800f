"""Declared pipeline DAGs end-to-end (``ai4e_tpu/pipeline/``,
docs/pipelines.md): the coordinator drives stages as sub-tasks through
the ordinary store/broker/dispatcher fabric under ONE client TaskId —
linear chains, fan-out/fan-in joins with a failure quorum, per-stage
deadline budgets shedding dead stages before dispatch, stage-level
result-cache reuse on re-runs, and the SSE streaming surface delivering
a stage-1 partial before stage 2 completes."""

import asyncio
import json
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.pipeline import PipelineSpec, StageSpec, sub_task_id
from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
from ai4e_tpu.taskstore import APITask, TaskStatus


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


class StageHost:
    """A worker service hosting trivial pipeline stages over HTTP: each
    stage echoes/annotates its input, records per-stage hit counts, and
    completes its (sub-)task with a JSON result — the minimal stand-in
    for an inference worker."""

    def __init__(self, platform):
        self.platform = platform
        self.svc = platform.make_service("stages", prefix="v1/st")
        self.hits: dict[str, int] = {}
        self.delays: dict[str, float] = {}
        self.fail: set[str] = set()
        self.no_result: set[str] = set()  # complete without storing one
        self.client = None
        self.base = ""

    def add_stage(self, name: str) -> None:
        svc, platform = self.svc, self.platform

        @svc.api_async_func(f"/{name}", maximum_concurrent_requests=64)
        async def handler(taskId, body, content_type, _name=name):
            self.hits[_name] = self.hits.get(_name, 0) + 1
            delay = self.delays.get(_name, 0.0)
            if delay:
                await asyncio.sleep(delay)
            if _name in self.fail:
                await platform.task_manager.fail_task(
                    taskId, f"failed - {_name} exploded")
                return
            try:
                doc = json.loads(body.decode("utf-8"))
            except (ValueError, UnicodeDecodeError):
                doc = {"raw": body.decode("utf-8", "replace")}
            result = {"stage": _name, "saw": doc}
            if _name not in self.no_result:
                platform.store.set_result(
                    taskId, json.dumps(result).encode(),
                    content_type="application/json")
            await platform.task_manager.complete_task(
                taskId, f"completed - {_name}")

    async def start(self, stages) -> None:
        for name in stages:
            self.add_stage(name)
        self.client = await serve(self.svc.app)
        self.base = str(self.client.make_url("")).rstrip("/")
        for name in stages:
            self.platform.register_internal_route(
                f"{self.base}/v1/st/{name}")

    def endpoint(self, name: str) -> str:
        return f"{self.base}/v1/st/{name}"

    async def close(self) -> None:
        if self.client is not None:
            await self.client.close()


async def build(config: PlatformConfig, stages, make_spec):
    """Platform + stage host + registered spec + served gateway."""
    platform = LocalPlatform(config)
    host = StageHost(platform)
    await host.start(stages)
    spec = make_spec(host)
    platform.register_pipeline(spec)
    gw = await serve(platform.gateway.app)
    await platform.start()
    return platform, host, spec, gw


async def wait_terminal(gw, task_id, timeout=30.0):
    resp = await gw.get(f"/v1/taskmanagement/task/{task_id}",
                        params={"wait": str(timeout)})
    return await resp.json()


async def read_sse(gw, task_id, wait=20.0, until_terminal=True):
    """Collect SSE events from the streaming surface."""
    events = []
    async with gw.session.get(
            gw.make_url(f"/v1/taskmanagement/task/{task_id}/events"),
            params={"wait": str(wait)}) as resp:
        assert resp.status == 200, await resp.text()
        assert resp.content_type == "text/event-stream"
        current: dict = {}
        async for raw in resp.content:
            line = raw.decode("utf-8").rstrip("\n")
            if line.startswith(":"):
                continue  # heartbeat
            if line.startswith("event: "):
                current["event"] = line[len("event: "):]
            elif line.startswith("data: "):
                current["data"] = json.loads(line[len("data: "):])
            elif line == "" and current:
                events.append(current)
                if until_terminal and current.get("event") == "terminal":
                    return events
                current = {}
    return events


class TestLinearChain:
    def test_two_stage_chain_single_task_id(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b"],
                lambda h: PipelineSpec("echo2", "/v1/pipe/echo2", [
                    StageSpec("a", h.endpoint("a")),
                    StageSpec("b", h.endpoint("b"), after=("a",)),
                ]))
            try:
                resp = await gw.post("/v1/pipe/echo2",
                                     data=b'{"x": 1}',
                                     headers={"Content-Type":
                                              "application/json"})
                task = await resp.json()
                tid = task["TaskId"]
                final = await wait_terminal(gw, tid)
                assert "completed - pipeline echo2" in final["Status"], final
                # Stage results retrievable under the ONE TaskId.
                sa = json.loads(platform.store.get_result(tid, stage="a")[0])
                assert sa == {"stage": "a", "saw": {"x": 1}}
                sb = json.loads(platform.store.get_result(tid, stage="b")[0])
                assert sb["stage"] == "b"
                # Stage b consumed stage a's result (single-upstream auto
                # input), and the final result IS the sink's.
                assert sb["saw"] == sa
                assert json.loads(
                    platform.store.get_result(tid)[0]) == sb
                assert host.hits == {"a": 1, "b": 1}
                # Sub-task records exist with their own terminal states.
                for st in ("a", "b"):
                    sub = platform.store.get(sub_task_id(tid, st))
                    assert sub.canonical_status == "completed"
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_streaming_partial_before_stage2_completes(self):
        """The acceptance ordering: the SSE surface delivers stage 1's
        partial result while stage 2 is still executing."""
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b"],
                lambda h: PipelineSpec("stream", "/v1/pipe/stream", [
                    StageSpec("a", h.endpoint("a")),
                    StageSpec("b", h.endpoint("b"), after=("a",)),
                ]))
            host.delays["b"] = 0.5  # stage 2 is slow
            try:
                resp = await gw.post("/v1/pipe/stream", data=b'{"q": 2}')
                tid = (await resp.json())["TaskId"]
                events = await read_sse(gw, tid)
                kinds = [(e["event"],
                          e.get("data", {}).get("stage"),
                          e.get("data", {}).get("state")) for e in events]
                a_done = next(i for i, k in enumerate(kinds)
                              if k[0] == "stage" and k[1] == "a"
                              and k[2] == "completed")
                b_done = next(i for i, k in enumerate(kinds)
                              if k[0] == "stage" and k[1] == "b"
                              and k[2] == "completed")
                terminal = next(i for i, k in enumerate(kinds)
                                if k[0] == "terminal")
                assert a_done < b_done < terminal, kinds
                # Stage a's partial rides inline in the event.
                a_event = events[a_done]["data"]
                assert a_event["resultAvailable"] is True
                assert a_event["result"]["stage"] == "a"
                # Terminal event carries the completed record.
                assert "completed" in events[terminal]["data"]["Status"]
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_stream_attach_after_completion_replays(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a"],
                lambda h: PipelineSpec("late", "/v1/pipe/late", [
                    StageSpec("a", h.endpoint("a")),
                ]))
            try:
                resp = await gw.post("/v1/pipe/late", data=b"{}")
                tid = (await resp.json())["TaskId"]
                await wait_terminal(gw, tid)
                events = await read_sse(gw, tid, wait=5.0)
                assert events[-1]["event"] == "terminal"
                assert any(e["event"] == "stage" for e in events)
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_events_404_unknown_and_off_platform_has_no_route(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a"],
                lambda h: PipelineSpec("p404", "/v1/pipe/p404", [
                    StageSpec("a", h.endpoint("a")),
                ]))
            try:
                resp = await gw.get(
                    "/v1/taskmanagement/task/nope/events")
                assert resp.status == 404
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())


class TestFanOutFanIn:
    def make_spec(self, h, quorum=1):
        return PipelineSpec("fan", "/v1/pipe/fan", [
            StageSpec("a", h.endpoint("a")),
            StageSpec("b", h.endpoint("b"), after=("a",)),
            StageSpec("c", h.endpoint("c"), after=("a",)),
            StageSpec("d", h.endpoint("d"), after=("b", "c"),
                      quorum=quorum),
        ])

    def test_join_receives_both_branches(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b", "c", "d"], self.make_spec)
            try:
                resp = await gw.post("/v1/pipe/fan", data=b'{"n": 3}')
                tid = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid)
                assert "completed" in final["Status"], final
                d_saw = json.loads(
                    platform.store.get_result(tid, stage="d")[0])["saw"]
                assert sorted(d_saw["arrived"]) == ["b", "c"]
                assert d_saw["missing"] == []
                assert d_saw["stages"]["b"]["stage"] == "b"
                assert host.hits == {"a": 1, "b": 1, "c": 1, "d": 1}
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_quorum_tolerates_failed_branch(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b", "c", "d"], self.make_spec)
            host.fail.add("c")
            try:
                resp = await gw.post("/v1/pipe/fan", data=b'{"n": 3}')
                tid = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid)
                assert "completed" in final["Status"], final
                assert "tolerated" in final["Status"]
                d_saw = json.loads(
                    platform.store.get_result(tid, stage="d")[0])["saw"]
                assert d_saw["arrived"] == ["b"]
                assert d_saw["missing"] == ["c"]
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_quorum_unsatisfied_fails_run_once(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b", "c", "d"],
                lambda h: self.make_spec(h, quorum=2))
            host.fail.add("c")
            terminal_count = {"n": 0}

            def count_terminal(task, _tid_box=[None]):
                if (task.canonical_status in TaskStatus.TERMINAL
                        and "~" not in task.task_id):
                    terminal_count["n"] += 1

            platform.store.add_listener(count_terminal)
            try:
                resp = await gw.post("/v1/pipe/fan", data=b'{"n": 3}')
                tid = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid)
                assert "failed - pipeline fan" in final["Status"], final
                assert "c" in final["Status"]
                # d never dispatched; exactly ONE root terminal transition.
                assert host.hits.get("d") is None
                assert terminal_count["n"] == 1
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())


class TestNoResultCompletion:
    def test_completed_stage_without_result_fails_not_hollow(self):
        """A stage that completes WITHOUT storing a result must fail the
        branch (code-review finding) — never feed an empty fabricated
        payload downstream and 'complete' the run with a hollow answer."""
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b"],
                lambda h: PipelineSpec("hollow", "/v1/pipe/hollow", [
                    StageSpec("a", h.endpoint("a")),
                    StageSpec("b", h.endpoint("b"), after=("a",)),
                ]))
            host.no_result.add("a")
            try:
                resp = await gw.post("/v1/pipe/hollow", data=b"{}")
                tid = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid)
                assert "failed - pipeline hollow" in final["Status"], final
                assert "without a retrievable result" in final["Status"]
                assert host.hits.get("b") is None  # never dispatched
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())


class TestDeadlineBudgets:
    def test_dead_root_sheds_before_any_dispatch(self):
        """A root whose budget is already spent when the coordinator
        adopts it sheds at the first stage transition — terminal
        ``expired``, no backend POST ever happens."""
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True,
                               admission=True),
                ["a", "b"],
                lambda h: PipelineSpec("dead", "/v1/pipe/dead", [
                    StageSpec("a", h.endpoint("a")),
                    StageSpec("b", h.endpoint("b"), after=("a",)),
                ]))
            try:
                # Bypass the gateway's own expired-check by creating the
                # root directly (the transport-latency window the
                # coordinator's pre-dispatch check exists for).
                task = platform.store.upsert(APITask(
                    endpoint=spec.entry_path, body=b"{}",
                    publish=True, deadline_at=time.time() - 1.0))
                final = await wait_terminal(gw, task.task_id)
                assert "expired" in final["Status"], final
                assert "budget spent" in final["Status"]
                assert host.hits == {}
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_stage_fraction_carves_subtask_deadline(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True,
                               admission=True),
                ["a"],
                lambda h: PipelineSpec("carve", "/v1/pipe/carve", [
                    StageSpec("a", h.endpoint("a"), deadline_fraction=0.5),
                ]))
            try:
                t0 = time.time()
                resp = await gw.post("/v1/pipe/carve", data=b"{}",
                                     headers={"X-Deadline-Ms": "60000"})
                tid = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid)
                assert "completed" in final["Status"], final
                sub = platform.store.get(sub_task_id(tid, "a"))
                root = platform.store.get(tid)
                # Sub-task deadline ≈ half the remaining budget, strictly
                # inside the root's.
                assert 0 < sub.deadline_at < root.deadline_at
                assert sub.deadline_at - t0 < 40.0
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())


class TestStageCache:
    def test_rerun_skips_completed_stages(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True,
                               result_cache=True),
                ["a", "b"],
                lambda h: PipelineSpec("cach", "/v1/pipe/cach", [
                    StageSpec("a", h.endpoint("a")),
                    StageSpec("b", h.endpoint("b"), after=("a",)),
                ]))
            try:
                resp = await gw.post("/v1/pipe/cach", data=b'{"v": 9}')
                tid1 = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid1)
                assert "completed" in final["Status"], final
                assert host.hits == {"a": 1, "b": 1}

                # Re-run with a distinct REQUEST key (?uniq defeats the
                # whole-request cache) but identical stage inputs: every
                # stage satisfied from the stage cache, zero executions.
                resp = await gw.post("/v1/pipe/cach?uniq=1",
                                     data=b'{"v": 9}')
                tid2 = (await resp.json())["TaskId"]
                assert tid2 != tid1
                final2 = await wait_terminal(gw, tid2)
                assert "completed" in final2["Status"], final2
                assert "2 cached" in final2["Status"]
                assert host.hits == {"a": 1, "b": 1}  # nothing re-executed
                assert json.loads(platform.store.get_result(tid2)[0]) \
                    == json.loads(platform.store.get_result(tid1)[0])
                expo = platform.metrics.render_prometheus()
                assert 'outcome="cached"' in expo
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())

    def test_bypass_disables_stage_cache(self):
        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True,
                               result_cache=True),
                ["a"],
                lambda h: PipelineSpec("byp", "/v1/pipe/byp", [
                    StageSpec("a", h.endpoint("a")),
                ]))
            try:
                resp = await gw.post("/v1/pipe/byp", data=b'{"v": 1}')
                tid = (await resp.json())["TaskId"]
                await wait_terminal(gw, tid)
                assert host.hits == {"a": 1}
                resp = await gw.post("/v1/pipe/byp", data=b'{"v": 1}',
                                     headers={"X-Cache-Bypass": "1"})
                tid2 = (await resp.json())["TaskId"]
                final = await wait_terminal(gw, tid2)
                assert "completed" in final["Status"], final
                assert host.hits == {"a": 2}  # bypassed: re-executed
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())


class TestStreamingClients:
    def test_blocking_sdk_iter_task_events(self):
        """clients/python/ai4e_client.iter_task_events consumes the SSE
        surface end to end (stage partials, then terminal)."""
        import importlib.util
        import os
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec_mod = importlib.util.spec_from_file_location(
            "ai4e_client",
            os.path.join(repo, "clients", "python", "ai4e_client.py"))
        ai4e_client = importlib.util.module_from_spec(spec_mod)
        spec_mod.loader.exec_module(ai4e_client)

        async def main():
            platform, host, spec, gw = await build(
                PlatformConfig(retry_delay=0.05, pipeline=True),
                ["a", "b"],
                lambda h: PipelineSpec("sdk", "/v1/pipe/sdk", [
                    StageSpec("a", h.endpoint("a")),
                    StageSpec("b", h.endpoint("b"), after=("a",)),
                ]))
            host.delays["b"] = 0.3
            try:
                resp = await gw.post("/v1/pipe/sdk", data=b'{"k": 1}')
                tid = (await resp.json())["TaskId"]
                gateway_url = str(gw.make_url("")).rstrip("/")

                def consume():
                    client = ai4e_client.AI4EClient(gateway_url)
                    return list(client.iter_task_events(tid, wait=20.0))

                events = await asyncio.to_thread(consume)
                names = [e for e, _ in events]
                assert names[-1] == "terminal"
                stage_states = [(d.get("stage"), d.get("state"))
                                for e, d in events if e == "stage"]
                assert ("a", "completed") in stage_states
                assert ("b", "completed") in stage_states
                assert stage_states.index(("a", "completed")) \
                    < stage_states.index(("b", "completed"))
            finally:
                await platform.stop()
                await gw.close()
                await host.close()

        asyncio.run(main())


class TestAssemblyWiring:
    def test_off_by_default_byte_identical(self):
        platform = LocalPlatform(PlatformConfig())
        assert platform.pipeline is None
        assert platform.task_events is None
        assert platform.gateway._event_hub is None
        paths = {r.resource.canonical
                 for r in platform.gateway.app.router.routes()
                 if r.resource is not None}
        assert "/v1/taskmanagement/task/{task_id}/events" not in paths
        with pytest.raises(ValueError, match="pipeline=True"):
            platform.register_pipeline(
                PipelineSpec("x", "/v1/x",
                             [StageSpec("a", "/v1/a")]))

    def test_on_wires_hub_and_route(self):
        platform = LocalPlatform(PlatformConfig(pipeline=True))
        assert platform.pipeline is not None
        assert platform.gateway._event_hub is platform.task_events
        paths = {r.resource.canonical
                 for r in platform.gateway.app.router.routes()
                 if r.resource is not None}
        assert "/v1/taskmanagement/task/{task_id}/events" in paths

    def test_refusals(self):
        with pytest.raises(ValueError, match="queue transport"):
            LocalPlatform(PlatformConfig(pipeline=True, transport="push"))
        with pytest.raises(ValueError, match="Python store"):
            LocalPlatform(PlatformConfig(pipeline=True, native_store=True,
                                         native_broker=True))

    def test_http_surface_refuses_forged_sub_task_creates(self):
        """A caller must not be able to CREATE a '{root}~{stage}' record
        over the HTTP store surface (it would alias a running pipeline's
        stage sub-task); transitions of records the coordinator minted
        still pass."""
        async def main():
            from ai4e_tpu.taskstore import InMemoryTaskStore
            from ai4e_tpu.taskstore.http import make_app

            store = InMemoryTaskStore()
            client = await serve(make_app(store))
            try:
                resp = await client.post(
                    "/v1/taskstore/upsert",
                    data=json.dumps({"TaskId": "root~stage",
                                     "Endpoint": "/v1/x"}))
                assert resp.status == 400
                assert "reserved" in (await resp.json())["error"]
                # A sub-record the platform minted transitions normally.
                store.upsert(APITask(task_id="r2~s1", endpoint="/v1/x"))
                resp = await client.post(
                    "/v1/taskstore/upsert",
                    data=json.dumps({"TaskId": "r2~s1",
                                     "Endpoint": "/v1/x",
                                     "Status": "running"}))
                assert resp.status == 200
            finally:
                await client.close()

        asyncio.run(main())

    def test_config_env_round_trip(self):
        from ai4e_tpu.config import PlatformSection
        section = PlatformSection.from_env(env={
            "AI4E_PLATFORM_PIPELINE": "1",
            "AI4E_PLATFORM_PIPELINE_EVENT_REPLAY": "32",
            "AI4E_PLATFORM_PIPELINE_STREAM_MAX_S": "60",
        })
        pc = section.to_platform_config()
        assert pc.pipeline is True
        assert pc.pipeline_event_replay == 32
        assert pc.pipeline_stream_max_s == 60.0
