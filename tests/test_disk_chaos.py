"""Disk-fault chaos + the crash-point sweep (docs/durability.md) — the
storage-layer acceptance scenarios of the durable-truth hardening:

(a) **crash-point sweep** — a journaled store is killed/restarted at
    EVERY record boundary and at seeded mid-record offsets (torn
    writes / lost page cache) across seeds 1/2/3/7/42 + the CI pin:
    every restart boots without crash-looping, 0 acknowledged-task
    loss (``fsync=always`` markers), no conflicting state, and a fresh
    replica absorbing the rebooted journal converges chain-head- and
    snapshot-identically;

(b) **degraded mode at the edge** — seeded ENOSPC mid-append + EIO on
    fsync flip an unsharded control plane to fenced read-only degraded
    mode: task creation answers the typed 503 +
    ``X-Shed-Reason: journal-degraded`` while reads keep serving, and
    ``recover()`` re-admits the node (traffic completes again);

(c) **disk faults composed with failover + rebalance** — on a 4-shard
    store under load with seeded HTTP faults, one shard's primary disk
    faults (torn ENOSPC append): the facade fails over to its replica
    at epoch+1 and traffic completes through it; a SECOND shard's
    primary is SIGKILLed (``kill_shard_primary``) and a slot is
    live-rebalanced (``move_slot``) on top — invariants clean per
    shard AND globally, replicas chain-converged with their primaries.

All seeded; the CI ``durability-smoke`` job runs this file JAX-free with
the pinned ``AI4E_CHAOS_SEED``.
"""

import asyncio
import errno
import os

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.chaos import (DiskFaultInjector, FaultInjector,
                            InvariantChecker, attach_journal_faults,
                            kill_shard_primary, rebalance_slot, sweep,
                            wrap_platform_http)
from ai4e_tpu.metrics import MetricsRegistry
from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
from ai4e_tpu.taskstore import TaskStatus

SEED = int(os.environ.get("AI4E_CHAOS_SEED", "20260803"))
SHARDS = 4


def run(coro):
    return asyncio.run(coro)


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def _drain(checker, deadline_s=30.0):
    deadline = asyncio.get_running_loop().time() + deadline_s
    while asyncio.get_running_loop().time() < deadline:
        if all(tid in checker.terminal for tid in checker.accepted):
            return
        await asyncio.sleep(0.05)


def _completing_backend(platform):
    async def handler(request):
        tid = request.headers["taskId"]
        platform.store.update_status_if(
            tid, "created", f"completed - {len(await request.read())}b",
            TaskStatus.COMPLETED)
        return web.Response(text="ok")

    app = web.Application()
    app.router.add_post("/v1/be/x", handler)
    return app


@pytest.mark.chaos
@pytest.mark.durability
class TestCrashPointSweep:
    @pytest.mark.parametrize("seed", sorted({1, 2, 3, 7, 42, SEED % 1000}))
    def test_every_crash_point_reboots_clean_fsync_always(
            self, tmp_path, seed):
        """fsync=always: the ack marker is durable at ack time, so the
        sweep proves the LITERAL 0-acknowledged-task-loss claim at every
        boundary and mid-record offset."""
        points, violations = sweep(str(tmp_path), seed, fsync="always",
                                   ops=34, mid_points=10)
        assert points > 20
        assert violations == []

    def test_sweep_holds_under_fsync_never_file_shapes(self, tmp_path):
        """fsync=never (the default): the same byte-conditional contract
        — the rebooted state equals exactly the surviving prefix's
        acknowledged history (the residual window is WHICH prefix
        survives, never a half-applied or crash-looping store)."""
        points, violations = sweep(str(tmp_path), SEED, fsync="never",
                                   ops=30, mid_points=10)
        assert points > 20
        assert violations == []


@pytest.mark.chaos
@pytest.mark.durability
class TestDegradedEdge:
    def test_enospc_and_eio_degrade_then_recovery_readmits(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("AI4E_TASKSTORE_FSYNC", "always")

        async def main():
            metrics = MetricsRegistry()
            platform = LocalPlatform(PlatformConfig(
                journal_path=str(tmp_path / "journal"),
                retry_delay=0.01), metrics=metrics)
            checker = InvariantChecker().attach(platform.store)
            be = await serve(_completing_backend(platform))
            platform.publish_async_api("/v1/pub/x",
                                       str(be.make_url("/v1/be/x")))
            gw = await serve(platform.gateway.app)
            await platform.start()
            try:
                # Healthy traffic first.
                resp = await gw.post("/v1/pub/x", data=b"before")
                assert resp.status == 200
                before = (await resp.json())["TaskId"]
                checker.note_accepted(before)
                await _drain(checker)

                # Seeded disk faults: EIO on the next fsync, then ENOSPC
                # torn appends for anything after.
                disk = DiskFaultInjector(seed=SEED)
                disk.add_rule(op="fsync", errno=errno.EIO)
                disk.add_rule(op="write", errno=errno.ENOSPC,
                              torn_bytes=20, times=None)
                attach_journal_faults(platform.store, disk)

                # Task creation now refuses with the TYPED 503 — nothing
                # is created or published (memory never runs ahead).
                resp = await gw.post("/v1/pub/x", data=b"doomed")
                assert resp.status == 503
                assert resp.headers["X-Shed-Reason"] == "journal-degraded"
                assert "X-Not-Primary" not in resp.headers
                assert platform.store.degraded
                assert disk.counts()  # the injector actually fired

                # Reads keep serving through the degradation.
                resp = await gw.get(f"/v1/taskmanagement/task/{before}")
                assert resp.status == 200
                assert metrics.counter(
                    "ai4e_gateway_requests_total", "").value(
                        route="/v1/pub/x",
                        outcome="journal_degraded") >= 1

                # Disk heals → recover() re-admits the node; traffic
                # completes end to end again.
                disk.clear()
                assert platform.store.recover()
                resp = await gw.post("/v1/pub/x", data=b"after")
                assert resp.status == 200
                checker.note_accepted((await resp.json())["TaskId"])
                await _drain(checker)
                checker.assert_ok()
            finally:
                await platform.stop()
                await gw.close()
                await be.close()

        run(main())


@pytest.mark.chaos
@pytest.mark.durability
class TestDegradedCacheHit:
    def test_cache_hit_on_degraded_store_answers_typed_503(self, tmp_path):
        """Review regression: the cache-hit path creates a real (memory-
        only) task record too, and its upsert caught only NotPrimaryError
        — on a journal-degraded store the duplicate request escaped the
        typed handler as a generic 500. It must fall through to the same
        503 + X-Shed-Reason the ordinary create path ships."""
        async def main():
            platform = LocalPlatform(PlatformConfig(
                journal_path=str(tmp_path / "journal"),
                result_cache=True,
                retry_delay=0.01), metrics=MetricsRegistry())

            # The cache fills from a completed task's RESULT — this
            # backend writes one (the shared completer only flips
            # status).
            async def handler(request):
                tid = request.headers["taskId"]
                platform.store.set_result(tid, b"cached-answer")
                platform.store.update_status_if(
                    tid, "created", "completed - ok",
                    TaskStatus.COMPLETED)
                return web.Response(text="ok")

            app = web.Application()
            app.router.add_post("/v1/be/x", handler)
            be = await serve(app)
            platform.publish_async_api("/v1/pub/x",
                                       str(be.make_url("/v1/be/x")))
            gw = await serve(platform.gateway.app)
            await platform.start()
            try:
                # Seed the cache with one completed request, then wait
                # until a duplicate actually rides it.
                resp = await gw.post("/v1/pub/x", data=b"dup-payload")
                assert resp.status == 200
                deadline = asyncio.get_running_loop().time() + 15.0
                hit = False
                while asyncio.get_running_loop().time() < deadline:
                    r = await gw.post("/v1/pub/x", data=b"dup-payload")
                    if r.headers.get("X-Cache") == "hit":
                        hit = True
                        break
                    await asyncio.sleep(0.05)
                assert hit, "cache never served the duplicate request"

                # Degrade the store with a non-cached write.
                disk = DiskFaultInjector(seed=SEED)
                disk.add_rule(op="write", errno=errno.ENOSPC, times=None)
                attach_journal_faults(platform.store, disk)
                r = await gw.post("/v1/pub/x", data=b"not-cached")
                assert r.status == 503
                assert platform.store.degraded

                # The DUPLICATE request — a cache hit — now refuses with
                # the same typed 503, never a 500.
                r = await gw.post("/v1/pub/x", data=b"dup-payload")
                assert r.status == 503
                assert r.headers["X-Shed-Reason"] == "journal-degraded"
                assert "X-Not-Primary" not in r.headers
            finally:
                await platform.stop()
                await gw.close()
                await be.close()

        run(main())


@pytest.mark.chaos
@pytest.mark.durability
class TestDiskFaultsComposedWithFailoverAndRebalance:
    def test_degraded_shard_fails_over_kill_and_rebalance_on_top(
            self, tmp_path):
        async def main():
            platform = LocalPlatform(PlatformConfig(
                task_shards=SHARDS,
                journal_path=str(tmp_path / "journal"),
                shard_tail_interval=0.02,
                resilience=True,
                retry_delay=0.01,
                lease_seconds=2.0,
                resilience_retry_base_s=0.001,
                resilience_failure_threshold=3,
                resilience_recovery_seconds=0.1,
            ), metrics=MetricsRegistry())
            checker = InvariantChecker(
                shard_of=platform.store.shard_for).attach(platform.store)
            be = await serve(_completing_backend(platform))
            platform.publish_async_api("/v1/pub/x",
                                       str(be.make_url("/v1/be/x")))
            injector = FaultInjector(seed=SEED)
            injector.add_rule(error_rate=0.15, error_status=500,
                              drop_rate=0.05)
            wrap_platform_http(platform, injector)
            gw = await serve(platform.gateway.app)
            await platform.start()
            try:
                async def accept(n):
                    for _ in range(n):
                        resp = await gw.post("/v1/pub/x", data=b"payload")
                        assert resp.status == 200
                        checker.note_accepted(
                            (await resp.json())["TaskId"])

                await accept(16)

                # Disk-fault one shard's primary: torn ENOSPC appends +
                # EIO on any fsync. The NEXT write routed there flips it
                # degraded and the facade promotes its replica inline —
                # the journal FILE (all acknowledged writes) is the
                # durable truth the replica drains.
                victim = platform.store.shard_for(
                    sorted(checker.accepted)[0])
                pre_epoch = platform.store.groups[victim].epoch
                disk = DiskFaultInjector(seed=SEED)
                disk.add_rule(op="write", errno=errno.ENOSPC,
                              torn_bytes=25, times=None)
                disk.add_rule(op="fsync", errno=errno.EIO, times=None)
                attach_journal_faults(
                    platform.store.groups[victim].active, disk)

                # Traffic continues: the degraded shard fails over, the
                # other shards never notice. Routing is hash-random, so
                # trickle bounded extra writes until one lands on the
                # victim and trips the inline promotion.
                await accept(12)
                for _ in range(16):
                    if platform.store.groups[victim].epoch > pre_epoch:
                        break
                    await accept(4)
                await _drain(checker)
                assert platform.store.groups[victim].epoch == pre_epoch + 1
                assert not platform.store.groups[victim].dead

                # Compose a PROCESS kill on a second shard mid-traffic.
                others = [i for i in range(SHARDS) if i != victim]
                killed = others[0]
                kill_shard_primary(platform, killed)
                await accept(12)
                for _ in range(16):
                    if platform.store.groups[killed].epoch >= 1:
                        break
                    await accept(4)
                await _drain(checker)
                assert platform.store.groups[killed].epoch >= 1

                # And a live rebalance on top: move one accepted task's
                # slot between the two untouched shards (src may be any
                # shard — including a promoted one, whose journal must
                # accept the migration records).
                store = platform.store
                target = sorted(checker.accepted)[-1]
                slot = store.ring.slot_for(target)
                src = store.ring.shard_of_slot(slot)
                dest = next(i for i in range(SHARDS) if i != src)
                rebalance_slot(platform, slot, dest)
                assert store.ring.shard_of_slot(slot) == dest
                await accept(8)
                await _drain(checker)

                # Verdicts: global + per shard, zero lost / zero dup,
                # and every surviving replica chain-converged with its
                # primary.
                checker.assert_ok()
                for i in range(SHARDS):
                    checker.assert_shard_ok(i)
                per_shard = checker.by_shard()
                assert sum(s["accepted"]
                           for s in per_shard.values()) == len(
                               checker.accepted)
                assert len(checker.accepted) >= 48
                for shard, stats in sorted(per_shard.items()):
                    assert stats["terminal"] == stats["accepted"], (
                        shard, stats)
                    assert stats["duplicates"] == 0, (shard, stats)
                checker.assert_replicas_converged(store)
                # Both injectors actually fired.
                assert injector.counts().get("error", 0) > 0
                assert disk.counts()
            finally:
                await platform.stop()
                await gw.close()
                await be.close()

        run(main())
