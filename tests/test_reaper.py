"""Stuck-task reaper tests — failure detection for tasks orphaned by a worker
crash after adoption (``taskstore/reaper.py``; SURVEY.md §5 failure-detection
gap: the reference's recovery stops at broker redelivery)."""

import asyncio

from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
from ai4e_tpu.taskstore import APITask, InMemoryTaskStore, TaskStatus
from ai4e_tpu.taskstore.reaper import TaskReaper
from ai4e_tpu.service import LocalTaskManager


def run(coro):
    return asyncio.run(coro)


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


class TestSweep:
    def test_fresh_running_task_left_alone(self):
        async def main():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            task = store.upsert(APITask(endpoint="/v1/x", body=b"B"))
            store.update_status(task.task_id, "running")
            reaper = TaskReaper(store, running_timeout=60.0)
            assert await reaper.sweep() == 0
            assert "running" in store.get(task.task_id).status

        run(main())

    def test_stuck_running_task_republished_with_original_body(self):
        async def main():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            republished = []
            store.set_publisher(lambda t: republished.append(
                (t.task_id, t.body)))
            task = store.upsert(APITask(endpoint="/v1/x", body=b"ORIG"))
            store.update_status(task.task_id, "running")
            # Make it look old.
            store._tasks[task.task_id].timestamp -= 1000

            reaper = TaskReaper(store, running_timeout=60.0)
            assert await reaper.sweep() == 1
            assert republished == [(task.task_id, b"ORIG")]
            assert store.get(task.task_id).canonical_status == TaskStatus.CREATED

        run(main())

    def test_repeatedly_stuck_task_eventually_failed(self):
        async def main():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            store.set_publisher(lambda t: None)
            task = store.upsert(APITask(endpoint="/v1/x", body=b"B"))
            reaper = TaskReaper(store, running_timeout=60.0,
                                max_requeues=2)
            for rescue in range(2):
                store.update_status(task.task_id, "running")
                store._tasks[task.task_id].timestamp -= 1000
                assert await reaper.sweep() == 1
                assert store.get(task.task_id).canonical_status == TaskStatus.CREATED
            # Third time: out of rescues -> terminal failure.
            store.update_status(task.task_id, "running")
            store._tasks[task.task_id].timestamp -= 1000
            assert await reaper.sweep() == 1
            final = store.get(task.task_id)
            assert final.canonical_status == TaskStatus.FAILED
            assert "no progress" in final.status

        run(main())

    def test_completed_task_clears_rescue_budget(self):
        async def main():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            store.set_publisher(lambda t: None)
            task = store.upsert(APITask(endpoint="/v1/x", body=b"B"))
            reaper = TaskReaper(store, running_timeout=60.0)
            store.update_status(task.task_id, "running")
            store._tasks[task.task_id].timestamp -= 1000
            await reaper.sweep()
            store.update_status(task.task_id, "completed")
            await reaper.sweep()
            assert task.task_id not in reaper._requeues

        run(main())


class TestChaosRecovery:
    def test_worker_crash_after_adoption_recovers_on_healthy_replica(self):
        """The chaos scenario the reference cannot survive: the first replica
        adopts the task (200 to the dispatcher — message completed) then
        'dies' mid-inference. The reaper detects the stalled RUNNING task and
        republishes; the broker redelivers to the healthy replica, which
        completes it under the same TaskId with the original body."""
        async def main():
            platform = LocalPlatform(PlatformConfig(
                retry_delay=0.05,
                reaper_running_timeout=0.3,
                reaper_interval=0.1))
            svc = platform.make_service("flaky", prefix="v1/flaky")
            calls = {"n": 0}

            @svc.api_async_func("/work")
            def work(taskId, body, content_type):
                calls["n"] += 1
                if calls["n"] == 1:
                    # First adoption: mark running, then crash (never
                    # complete) — the orphaned-task scenario.
                    asyncio.run(platform.task_manager.update_task_status(
                        taskId, "running - replica-1"))
                    return
                assert body == b"PAYLOAD", body
                asyncio.run(platform.task_manager.complete_task(
                    taskId, "completed - replica-2 rescued"))

            svc_client = await serve(svc.app)
            platform.publish_async_api(
                "/v1/public/work", str(svc_client.make_url("/v1/flaky/work")))
            gw = await serve(platform.gateway.app)
            await platform.start()
            try:
                resp = await gw.post("/v1/public/work", data=b"PAYLOAD")
                tid = (await resp.json())["TaskId"]
                final = None
                for _ in range(400):
                    r = await gw.get(f"/v1/taskmanagement/task/{tid}")
                    final = await r.json()
                    if "completed" in final["Status"] or "failed" in final["Status"]:
                        break
                    await asyncio.sleep(0.02)
                assert final["Status"] == "completed - replica-2 rescued", final
                assert calls["n"] == 2
            finally:
                await platform.stop()
                await gw.close()
                await svc_client.close()

        run(main())


class TestNoResurrection:
    def test_sweep_does_not_clobber_task_completed_mid_sweep(self):
        """Atomic conditional rescue: a task that completes between the
        reaper's snapshot and its action must stay completed."""
        async def main():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            store.set_publisher(lambda t: None)
            task = store.upsert(APITask(endpoint="/v1/x", body=b"B"))
            store.update_status(task.task_id, "running")
            store._tasks[task.task_id].timestamp -= 1000
            reaper = TaskReaper(store, running_timeout=60.0)
            # Simulate completion in the snapshot->action window.
            snapshot = store.snapshot()
            store.update_status(task.task_id, "completed - raced")
            # requeue_if must refuse (status no longer RUNNING).
            assert store.requeue_if(task.task_id, TaskStatus.RUNNING) is None
            assert await reaper.sweep() == 0  # fresh sweep sees terminal
            final = store.get(task.task_id)
            assert final.status == "completed - raced"
            assert snapshot  # silence unused warning

        run(main())

    def test_fail_branch_refuses_completed_task(self):
        async def main():
            store = InMemoryTaskStore()
            tm = LocalTaskManager(store)
            task = store.upsert(APITask(endpoint="/v1/x", body=b"B"))
            store.update_status(task.task_id, "completed")
            assert store.update_status_if(
                task.task_id, TaskStatus.RUNNING, "failed - nope") is None
            assert store.get(task.task_id).canonical_status == TaskStatus.COMPLETED

        run(main())


class TestAutoRetentionDefault:
    """Terminal-history retention defaults (the 20-min soak finding: an
    unevicted control plane grows ~12 MB/min at 200 req/s — scripts/soak.sh). None = AUTO (15 min on the Python store), 0
    keeps its pre-AUTO evict-immediately meaning, negative opts out,
    native store = no eviction support."""

    def test_python_store_gets_auto_retention(self):
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        platform = LocalPlatform(PlatformConfig())
        assert platform.reaper is not None
        assert platform.reaper.terminal_retention == 900.0

    def test_zero_keeps_its_evict_immediately_meaning(self):
        # 0 predates the AUTO default and always meant "evict terminal
        # tasks as soon as the sweep sees them" — the most aggressive
        # valid bound. The opt-out is NEGATIVE, so old configs keep their
        # behavior.
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        platform = LocalPlatform(
            PlatformConfig(reaper_terminal_retention=0))
        assert platform.reaper is not None
        assert platform.reaper.terminal_retention == 0

    def test_negative_opts_out(self):
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        platform = LocalPlatform(
            PlatformConfig(reaper_terminal_retention=-1))
        assert platform.reaper is None

    def test_explicit_retention_respected(self):
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        platform = LocalPlatform(
            PlatformConfig(reaper_terminal_retention=120.0))
        assert platform.reaper.terminal_retention == 120.0

    def test_native_store_auto_disables_explicit_raises(self):
        import pytest

        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        try:
            platform = LocalPlatform(PlatformConfig(native_store=True))
        except (ImportError, OSError):
            pytest.skip("native store unavailable on this host")
        assert platform.reaper is None  # AUTO silently off: no eviction
        with pytest.raises(ValueError, match="requires the Python store"):
            LocalPlatform(PlatformConfig(native_store=True,
                                         reaper_terminal_retention=60.0))
