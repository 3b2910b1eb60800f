"""Control-plane HA tests — journal-follower replication + failover
(VERDICT r3 #3). The reference's availability came from managed network
Redis (``RedisConnection.cs:12-38``, ``deploy_cache_prerequisites.sh:15-31``);
here a standby replica tails the primary's journal stream
(``taskstore/replication.py``), refuses writes until promoted, and a
watchdog promotes it when the primary dies. The headline test is the
kill-the-store e2e: tasks created before the kill complete after failover
with results intact."""

import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.service.task_manager import HttpResultStore, HttpTaskManager
from ai4e_tpu.taskstore import (
    APITask,
    FollowerTaskStore,
    JournaledTaskStore,
    NotPrimaryError,
    TaskStatus,
)
from ai4e_tpu.taskstore.http import make_app
from ai4e_tpu.taskstore.replication import FailoverWatchdog, JournalReplicator


def run(coro):
    return asyncio.run(coro)


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def wait_for(predicate, timeout=10.0, interval=0.02):
    deadline = asyncio.get_event_loop().time() + timeout
    while asyncio.get_event_loop().time() < deadline:
        if predicate():
            return True
        await asyncio.sleep(interval)
    return predicate()


def primary_store(tmp_path, name="primary.jsonl", **kw):
    return JournaledTaskStore(str(tmp_path / name), **kw)


def follower_store(tmp_path, name="follower.jsonl", **kw):
    return FollowerTaskStore(str(tmp_path / name), **kw)


class TestFollowerSync:
    def test_follower_mirrors_tasks_transitions_and_results(self, tmp_path):
        async def main():
            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))
            follower = follower_store(tmp_path)
            repl = JournalReplicator(
                follower, str(pri_client.make_url("")), poll_wait=0.2)
            repl.start()
            try:
                t1 = primary.upsert(APITask(
                    endpoint="http://edge/v1/landcover/classify",
                    body=b"tile-1"))
                t2 = primary.upsert(APITask(
                    endpoint="http://edge/v1/species/classify",
                    body=b"img-2", content_type="image/jpeg"))
                primary.update_status(t1.task_id, "running",
                                      TaskStatus.RUNNING)
                primary.set_result(t1.task_id, b'{"histogram": {"0": 9}}')
                primary.update_status(t1.task_id, "completed",
                                      TaskStatus.COMPLETED)

                ok = await wait_for(
                    lambda: (follower.set_len("/v1/landcover/classify",
                                              "completed") == 1
                             and follower.set_len("/v1/species/classify",
                                                  "created") == 1))
                assert ok, follower.depths()
                assert (follower.get(t1.task_id).to_dict()
                        == primary.get(t1.task_id).to_dict())
                assert follower.get_result(t1.task_id) == (
                    b'{"histogram": {"0": 9}}', "application/json")
                # Original bodies replicate too — the promoted follower must
                # be able to replay payloads for redelivery.
                assert follower.get_original_body(t2.task_id) == b"img-2"
                assert follower.get(t2.task_id).content_type == "image/jpeg"
            finally:
                await repl.aclose()
                await pri_client.close()
                primary.close()
                follower.close()

        run(main())

    def test_generation_change_resyncs_follower(self, tmp_path):
        # Primary compaction rewrites the journal (byte offsets die);
        # the follower detects the generation bump and resyncs from the
        # rewritten snapshot — state identical, nothing duplicated.
        async def main():
            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))
            follower = follower_store(tmp_path)
            repl = JournalReplicator(
                follower, str(pri_client.make_url("")), poll_wait=0.2)
            repl.start()
            try:
                ids = []
                for i in range(5):
                    t = primary.upsert(APITask(
                        endpoint="http://edge/v1/e/run", body=b"x%d" % i))
                    ids.append(t.task_id)
                for tid in ids[:3]:
                    primary.update_status(tid, "completed",
                                          TaskStatus.COMPLETED)
                await wait_for(lambda: follower.set_len("/v1/e/run",
                                                        "completed") == 3)
                gen_before = primary.journal_generation
                primary.compact()
                assert primary.journal_generation == gen_before + 1
                # Post-compaction mutations only exist in the new file.
                t_new = primary.upsert(APITask(
                    endpoint="http://edge/v1/e/run", body=b"after-compact"))
                ok = await wait_for(
                    lambda: (repl.generation == primary.journal_generation
                             and t_new.task_id in
                             {t.task_id for t in follower.snapshot()}))
                assert ok, (repl.generation, primary.journal_generation)
                assert ({t.task_id for t in follower.snapshot()}
                        == {t.task_id for t in primary.snapshot()})
                assert (follower.set_len("/v1/e/run", "completed") == 3)
            finally:
                await repl.aclose()
                await pri_client.close()
                primary.close()
                follower.close()

        run(main())

    def test_follower_restart_replays_its_own_journal(self, tmp_path):
        async def main():
            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))
            follower = follower_store(tmp_path)
            repl = JournalReplicator(
                follower, str(pri_client.make_url("")), poll_wait=0.2)
            repl.start()
            t = primary.upsert(APITask(endpoint="http://edge/v1/e/run",
                                       body=b"payload"))
            primary.set_result(t.task_id, b"res")
            await wait_for(
                lambda: follower.get_result(t.task_id) is not None)
            await repl.aclose()
            follower.close()
            await pri_client.close()
            primary.close()
            # Restart: the absorbed journal is byte-compatible with the
            # ordinary replay machinery.
            reborn = follower_store(tmp_path)
            assert reborn.get(t.task_id).task_id == t.task_id
            assert reborn.get_result(t.task_id) == (
                b"res", "application/json")
            assert reborn.get_original_body(t.task_id) == b"payload"
            reborn.close()

        run(main())


class TestStandbyLongPoll:
    def test_replicated_completion_wakes_standby_waiters(self, tmp_path):
        """A client long-polling the STANDBY's gateway must wake when the
        task completes on the PRIMARY: replicated Slim transitions fire the
        follower's own listeners (absorb_lines → _notify), so standby reads
        are first-class, not poll-until-timeout."""
        async def main():
            from ai4e_tpu.gateway.router import Gateway

            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))
            follower = follower_store(tmp_path)
            gw = Gateway(follower)
            gw_client = await serve(gw.app)
            repl = JournalReplicator(
                follower, str(pri_client.make_url("")), poll_wait=0.2)
            repl.start()
            try:
                t = primary.upsert(APITask(
                    endpoint="http://edge/v1/e/run", body=b"x"))
                ok = await wait_for(lambda: t.task_id in
                                    {x.task_id for x in follower.snapshot()})
                assert ok, "task never replicated to the standby"
                waiter = asyncio.create_task(gw_client.get(
                    f"/v1/taskmanagement/task/{t.task_id}",
                    params={"wait": "20"}))
                await asyncio.sleep(0.1)
                t0 = asyncio.get_event_loop().time()
                primary.update_status(t.task_id, "completed - done",
                                      TaskStatus.COMPLETED)
                resp = await asyncio.wait_for(waiter, timeout=10)
                woke_after = asyncio.get_event_loop().time() - t0
                body = await resp.json()
                assert "completed" in body["Status"], body
                # Event-driven wake, not the 20 s poll timeout.
                assert woke_after < 5.0, woke_after
            finally:
                await repl.aclose()
                await pri_client.close()
                await gw_client.close()
                primary.close()
                follower.close()

        run(main())


class TestWriteFence:
    def test_follower_refuses_writes_until_promoted(self, tmp_path):
        follower = follower_store(tmp_path)
        try:
            with pytest.raises(NotPrimaryError):
                follower.upsert(APITask(endpoint="http://e/v1/x", body=b"b"))
            follower.promote()
            task = follower.upsert(APITask(endpoint="http://e/v1/x",
                                           body=b"b"))
            assert follower.get(task.task_id).status == TaskStatus.CREATED
        finally:
            follower.close()

    def test_http_surface_maps_fence_to_503(self, tmp_path):
        async def main():
            follower = follower_store(tmp_path)
            client = await serve(make_app(follower))
            try:
                resp = await client.post(
                    "/v1/taskstore/upsert",
                    data=json.dumps({"Endpoint": "http://e/v1/x",
                                     "Body": "b"}))
                assert resp.status == 503
                assert (await resp.json())["error"] == "not primary"
                # Manual failover via the surface.
                resp = await client.post("/v1/taskstore/promote")
                assert resp.status == 200
                resp = await client.post(
                    "/v1/taskstore/upsert",
                    data=json.dumps({"Endpoint": "http://e/v1/x",
                                     "Body": "b"}))
                assert resp.status == 200
                role = await (await client.get("/v1/taskstore/role")).json()
                assert role["role"] == "primary"
            finally:
                await client.close()
                follower.close()

        run(main())


class TestStandbyPlatform:
    def test_standby_platform_promotes_and_dispatches(self, tmp_path):
        """Platform-level failover: a standby LocalPlatform (replicate_from)
        refuses edge writes while the primary lives, then — primary killed —
        its watchdog promotes the store, starts the transport, and re-seeds
        every replicated unfinished task into dispatch, which completes them
        end to end."""
        async def main():
            from ai4e_tpu.platform_assembly import (LocalPlatform,
                                                    PlatformConfig)

            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))

            standby = LocalPlatform(PlatformConfig(
                journal_path=str(tmp_path / "standby.jsonl"),
                replicate_from=str(pri_client.make_url("")),
                failover_interval=0.1, failover_down_after=2,
                retry_delay=0.05))
            svc = standby.make_service("echo", prefix="v1/echo")
            completed = []

            @svc.api_async_func("/run")
            def run_endpoint(taskId, body, content_type):
                completed.append(body)
                asyncio.run(standby.task_manager.complete_task(
                    taskId, "completed - echoed"))

            svc_client = await serve(svc.app)
            backend = str(svc_client.make_url("/v1/echo/run"))
            standby.publish_async_api("/v1/public/run", backend)
            gw_client = await serve(standby.gateway.app)
            await standby.start()
            try:
                # While the primary lives: reads OK, writes 503.
                resp = await gw_client.post("/v1/public/run", data=b"x")
                assert resp.status == 503, await resp.text()
                # Two tasks land on the PRIMARY (as the primary's gateway
                # would record them) and replicate over.
                ids = [primary.upsert(APITask(
                    endpoint=backend, body=b"replicated-%d" % i,
                    publish=True)).task_id for i in range(2)]
                await wait_for(
                    lambda: len(standby.store.unfinished_tasks()) == 2)

                await pri_client.close()
                primary.close()
                await asyncio.wait_for(standby.watchdog.promoted.wait(),
                                       timeout=10)

                # Promotion re-seeded dispatch: both tasks complete HERE.
                for tid in ids:
                    ok = await wait_for(
                        lambda t=tid: "completed" in
                        standby.store.get(t).status)
                    assert ok, standby.store.get(tid).to_dict()
                assert sorted(completed) == [b"replicated-0",
                                             b"replicated-1"]
                # And the promoted gateway now accepts new tasks.
                resp = await gw_client.post("/v1/public/run", data=b"new")
                assert resp.status == 200
                tid = (await resp.json())["TaskId"]
                ok = await wait_for(
                    lambda: "completed" in standby.store.get(tid).status)
                assert ok
            finally:
                await standby.stop()
                await gw_client.close()
                await svc_client.close()

        run(main())


class TestMidPipelineFailover:
    def test_handed_off_task_completes_on_promoted_standby(self, tmp_path):
        """A composite task killed MID-PIPELINE survives: stage 1 completed
        on the primary and republished the task to stage 2 (endpoint
        rewrite + empty body), then the primary died. The promoted standby
        must re-seed the stage-2 task WITH the replicated original body
        (the ``{taskId}_ORIG`` replay, ``CacheConnectorUpsert.cs:144-176``)
        so stage 2 receives the real payload."""
        async def main():
            from ai4e_tpu.platform_assembly import (LocalPlatform,
                                                    PlatformConfig)

            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))

            standby = LocalPlatform(PlatformConfig(
                journal_path=str(tmp_path / "standby.jsonl"),
                replicate_from=str(pri_client.make_url("")),
                failover_interval=0.1, failover_down_after=2,
                retry_delay=0.05))
            svc = standby.make_service("cls", prefix="v1/cls")
            stage2_bodies = []

            @svc.api_async_func("/classify")
            def classify(taskId, body, content_type):
                stage2_bodies.append((body, content_type))
                asyncio.run(standby.task_manager.complete_task(
                    taskId, "completed - classified"))

            svc_client = await serve(svc.app)
            stage2_backend = str(svc_client.make_url("/v1/cls/classify"))
            standby.publish_async_api("/v1/public/classify", stage2_backend)
            await standby.start()
            try:
                # On the PRIMARY: stage-1 lifecycle up to the handoff.
                t = primary.upsert(APITask(
                    endpoint="http://edge/v1/det/detect",
                    body=b"ORIGINAL-IMG", content_type="image/jpeg",
                    publish=True))
                primary.update_status(t.task_id, "running - det",
                                      TaskStatus.RUNNING)
                # Handoff: endpoint rewritten to stage 2, empty body →
                # the store replays the original (same upsert the
                # task manager's add_pipeline_task performs).
                primary.upsert(APITask(
                    task_id=t.task_id, endpoint=stage2_backend, body=b"",
                    status=TaskStatus.CREATED,
                    backend_status=TaskStatus.CREATED, publish=True))
                ok = await wait_for(
                    lambda: standby.store.get(t.task_id).endpoint
                    == stage2_backend if t.task_id in
                    {x.task_id for x in standby.store.snapshot()} else False)
                assert ok, "handoff never replicated"

                await pri_client.close()
                primary.close()
                await asyncio.wait_for(standby.watchdog.promoted.wait(),
                                       timeout=10)

                ok = await wait_for(
                    lambda: "completed" in standby.store.get(t.task_id).status)
                assert ok, standby.store.get(t.task_id).to_dict()
                # Stage 2 received the ORIGINAL payload with its type.
                assert stage2_bodies == [(b"ORIGINAL-IMG", "image/jpeg")]
            finally:
                await standby.stop()
                await svc_client.close()

        run(main())


class TestKillTheStore:
    def test_tasks_survive_primary_death_and_complete_on_follower(
            self, tmp_path):
        """THE HA acceptance test (VERDICT r3 #3 done-criterion): tasks
        created before the primary dies complete after failover, results
        from before the kill stay readable."""
        async def main():
            primary = primary_store(tmp_path)
            pri_client = await serve(make_app(primary))
            follower = follower_store(tmp_path)
            fol_client = await serve(make_app(follower))
            repl = JournalReplicator(
                follower, str(pri_client.make_url("")), poll_wait=0.2)
            repl.start()
            promoted_seen = []
            watchdog = FailoverWatchdog(
                repl, interval=0.1, down_after=2,
                on_promote=lambda: promoted_seen.append(True))

            # Store clients with the replica list — gateway/worker view.
            urls = [str(pri_client.make_url("")),
                    str(fol_client.make_url(""))]
            manager = HttpTaskManager(urls, failover_delay=0.1)
            results = HttpResultStore(urls, failover_delay=0.1)
            try:
                # Phase 1 (primary alive): one task completes WITH result,
                # two are still pending when the primary dies.
                done = await manager.add_task(
                    "http://edge/v1/landcover/classify", b"tile-done")
                await results.set_result(done["TaskId"], b'{"ok": 1}')
                await manager.complete_task(done["TaskId"], "completed")
                pending = []
                for i in range(2):
                    rec = await manager.add_task(
                        "http://edge/v1/landcover/classify",
                        b"tile-pending-%d" % i)
                    pending.append(rec["TaskId"])
                await wait_for(
                    lambda: follower.set_len("/v1/landcover/classify",
                                             "created") == 2)
                watchdog.start()

                # Phase 2: kill the primary process outright.
                await pri_client.close()
                primary.close()
                await asyncio.wait_for(watchdog.promoted.wait(), timeout=10)
                assert promoted_seen and follower.role == "primary"

                # Phase 3: the pending tasks are present on the new primary
                # with replayed bodies — what the platform re-dispatches.
                unfinished = {t.task_id: t for t in
                              follower.unfinished_tasks()}
                assert set(pending) <= set(unfinished)
                assert unfinished[pending[0]].body.startswith(b"tile-pending")
                # A worker (store clients fail over) completes them.
                for tid in pending:
                    await results.set_result(tid, b'{"ok": 2}')
                    await manager.complete_task(tid, "completed")
                for tid in pending:
                    rec = await manager.get_task_status(tid)
                    assert "completed" in rec["Status"], rec
                # Results from BEFORE the kill are intact after failover.
                assert (await results.get_result(done["TaskId"]))[0] \
                    == b'{"ok": 1}'
                rec = await manager.get_task_status(done["TaskId"])
                assert "completed" in rec["Status"]
            finally:
                await watchdog.stop()
                await repl.aclose()
                await manager.close()
                await results.close()
                await fol_client.close()
                follower.close()

        run(main())


class TestStoreClientFailoverPatience:
    def test_replica_patience_covers_default_promotion_window(self):
        """The live failover drive measured tasks whose inference succeeded
        being FailTask'd because the store client's replica patience
        (~1.5 s) expired inside the promotion window; patience must cover
        the DEFAULT watchdog's detection (failover_down_after ×
        failover_interval = 6 s) with margin. Lowering these defaults is
        a deliberate act, not a drive-by (scripts/ha_failover_drive.py)."""
        from ai4e_tpu.config import PlatformSection
        from ai4e_tpu.service.task_manager import HttpTaskManager

        tm = HttpTaskManager(["http://a", "http://b"])
        patience = tm._failover_cycles * tm._failover_delay
        section = PlatformSection()
        detection = section.failover_down_after * section.failover_interval
        assert patience > detection + 2.0, (
            f"replica patience {patience}s must exceed watchdog detection "
            f"{detection}s plus promotion margin")
