"""Unit tests for the task state machine — the test pyramid base SURVEY.md §4
says the reference lacks (created→running→completed/failed transitions +
sorted-set bookkeeping mirroring ``CacheConnectorUpsert.cs:133-142``)."""

import threading

import pytest

from ai4e_tpu.taskstore import (
    APITask,
    InMemoryTaskStore,
    JournaledTaskStore,
    TaskNotFound,
    TaskStatus,
)


def make_task(**kw):
    defaults = dict(endpoint="http://host/v1/landcover/classify", body=b'{"x":1}')
    defaults.update(kw)
    return APITask(**defaults)


class TestLifecycle:
    def test_create_assigns_id_and_created_status(self):
        store = InMemoryTaskStore()
        t = store.upsert(make_task())
        assert t.task_id
        got = store.get(t.task_id)
        assert got.status == TaskStatus.CREATED
        assert got.endpoint_path == "/v1/landcover/classify"

    def test_full_transition_chain(self):
        store = InMemoryTaskStore()
        t = store.upsert(make_task())
        path = t.endpoint_path
        assert store.set_members(path, "created") == [t.task_id]

        store.update_status(t.task_id, "running - model executing")
        assert store.set_len(path, "created") == 0
        assert store.set_members(path, "running") == [t.task_id]
        assert store.get(t.task_id).canonical_status == TaskStatus.RUNNING

        store.update_status(t.task_id, "completed - 3 animals found")
        assert store.set_len(path, "running") == 0
        assert store.set_members(path, "completed") == [t.task_id]

    def test_failure_transition(self):
        store = InMemoryTaskStore()
        t = store.upsert(make_task())
        store.update_status(t.task_id, "failed: boom")
        assert store.get(t.task_id).canonical_status == TaskStatus.FAILED
        assert store.set_len(t.endpoint_path, "failed") == 1

    def test_update_unknown_task_raises(self):
        with pytest.raises(TaskNotFound):
            InMemoryTaskStore().update_status("nope", "running")

    def test_get_unknown_task_raises(self):
        with pytest.raises(TaskNotFound):
            InMemoryTaskStore().get("nope")

    def test_status_canonicalisation(self):
        assert TaskStatus.canonical("Awaiting service availability") == "created"
        assert TaskStatus.canonical("task failed - oom") == "failed"
        assert TaskStatus.canonical("Completed.") == "completed"
        assert TaskStatus.canonical("running (batch 2/5)") == "running"


class TestSortedSets:
    def test_members_ordered_by_score(self):
        store = InMemoryTaskStore()
        ids = [store.upsert(make_task()).task_id for _ in range(5)]
        assert store.set_members("/v1/landcover/classify", "created") == ids

    def test_depths_per_endpoint(self):
        store = InMemoryTaskStore()
        store.upsert(make_task())
        t2 = store.upsert(make_task(endpoint="http://host/v1/detector"))
        store.update_status(t2.task_id, "running")
        d = store.depths()
        assert d["/v1/landcover/classify"]["created"] == 1
        assert d["/v1/detector"]["running"] == 1
        assert d["/v1/detector"]["created"] == 0


class TestPublish:
    def test_publish_true_invokes_publisher(self):
        published = []
        store = InMemoryTaskStore(publisher=published.append)
        t = store.upsert(make_task(publish=True))
        assert [p.task_id for p in published] == [t.task_id]

    def test_publish_false_does_not_invoke(self):
        published = []
        store = InMemoryTaskStore(publisher=published.append)
        store.upsert(make_task(publish=False))
        assert published == []

    def test_publish_failure_fails_task(self):
        # CacheConnectorUpsert.cs:183-199 — broker down must not lose the task
        # silently; it rolls to failed.
        def boom(_):
            raise RuntimeError("broker down")

        store = InMemoryTaskStore(publisher=boom)
        t = store.upsert(make_task(publish=True))
        assert store.get(t.task_id).canonical_status == TaskStatus.FAILED

    def test_pipeline_replays_original_body(self):
        # CacheConnectorUpsert.cs:144-176: empty body on a publishing upsert of
        # an existing task replays {taskId}_ORIG.
        published = []
        store = InMemoryTaskStore(publisher=published.append)
        t = store.upsert(make_task(body=b"ORIGINAL", publish=True))
        hop = APITask(
            task_id=t.task_id, endpoint="http://host/v1/classifier", body=b"", publish=True
        )
        store.upsert(hop)
        assert published[-1].body == b"ORIGINAL"
        assert store.get(t.task_id).endpoint_path == "/v1/classifier"

    def test_handoff_body_becomes_replay_body(self):
        # A handoff WITH a payload (detector passes crops to the classifier)
        # re-bases the replay body: a later empty-body requeue of the new
        # stage must get the stage's own input, not stage 1's.
        published = []
        store = InMemoryTaskStore(publisher=published.append)
        t = store.upsert(make_task(body=b"STAGE1-IMAGE", publish=True))
        store.upsert(APITask(task_id=t.task_id,
                             endpoint="http://host/v1/classifier",
                             body=b"CROPS", publish=True))
        store.upsert(APITask(task_id=t.task_id,
                             endpoint="http://host/v1/classifier",
                             body=b"", publish=True))
        assert published[-1].body == b"CROPS"


class TestConcurrency:
    def test_parallel_transitions_keep_sets_consistent(self):
        store = InMemoryTaskStore()
        tasks = [store.upsert(make_task()) for _ in range(50)]

        def flip(t):
            store.update_status(t.task_id, "running")
            store.update_status(t.task_id, "completed")

        threads = [threading.Thread(target=flip, args=(t,)) for t in tasks]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        path = tasks[0].endpoint_path
        assert store.set_len(path, "created") == 0
        assert store.set_len(path, "running") == 0
        assert store.set_len(path, "completed") == 50


class TestJournal:
    def test_restart_replays_state(self, tmp_path):
        journal = str(tmp_path / "tasks.jsonl")
        store = JournaledTaskStore(journal)
        t1 = store.upsert(make_task(body=b"abc"))
        t2 = store.upsert(make_task())
        store.update_status(t1.task_id, "completed")
        store.close()

        revived = JournaledTaskStore(journal)
        assert revived.get(t1.task_id).canonical_status == TaskStatus.COMPLETED
        assert revived.get(t2.task_id).canonical_status == TaskStatus.CREATED
        assert revived.get_original_body(t1.task_id) == b"abc"
        path = t1.endpoint_path
        assert revived.set_len(path, "completed") == 1
        assert revived.set_len(path, "created") == 1


class TestContentTypeReplay:
    def test_pipeline_replay_restores_original_content_type(self):
        """A JPEG task republished with an empty body must replay both the
        original bytes AND image/jpeg — replaying as application/json would
        make the image preprocess undecodable downstream."""
        from ai4e_tpu.taskstore import APITask, InMemoryTaskStore

        store = InMemoryTaskStore()
        published = []
        store.set_publisher(lambda t: published.append(
            (t.body, t.content_type)))
        task = store.upsert(APITask(endpoint="/v1/detect", body=b"\xff\xd8JPG",
                                    content_type="image/jpeg", publish=True))
        # Pipeline republish (empty body): replay body + content type.
        store.upsert(APITask(task_id=task.task_id, endpoint="/v1/classify",
                             body=b"", publish=True))
        assert published[-1] == (b"\xff\xd8JPG", "image/jpeg")

    def test_unfinished_tasks_restore_content_type(self):
        from ai4e_tpu.taskstore import APITask, InMemoryTaskStore

        store = InMemoryTaskStore()
        task = store.upsert(APITask(endpoint="/v1/detect", body=b"IMG",
                                    content_type="image/png"))
        store.update_status(task.task_id, "running")
        # Simulate the journal-restore path (body emptied on the record).
        store._tasks[task.task_id].body = b""
        restored = store.unfinished_tasks()
        assert restored[0].body == b"IMG"
        assert restored[0].content_type == "image/png"

    def test_journal_round_trips_orig_content_type(self, tmp_path):
        import os

        from ai4e_tpu.taskstore import APITask, JournaledTaskStore

        path = os.path.join(str(tmp_path), "j.jsonl")
        store = JournaledTaskStore(path)
        task = store.upsert(APITask(endpoint="/v1/detect", body=b"RAWJPG",
                                    content_type="image/jpeg"))
        store.close()

        store2 = JournaledTaskStore(path)
        published = []
        store2.set_publisher(lambda t: published.append(
            (t.body, t.content_type)))
        store2.upsert(APITask(task_id=task.task_id, endpoint="/v1/next",
                              body=b"", publish=True))
        assert published == [(b"RAWJPG", "image/jpeg")]
        store2.close()


class TestJournalGrowth:
    def test_transitions_journal_slim_records(self, tmp_path):
        """Status transitions must not re-append the (hex-doubled) payload:
        a big-bodied task with many transitions journals its body exactly
        once."""
        import os

        journal = str(tmp_path / "slim.jsonl")
        store = JournaledTaskStore(journal)
        body = b"\xab" * 50_000
        t = store.upsert(make_task(body=body))
        base = os.path.getsize(journal)
        assert base > len(body)  # create record carries the body (hex)
        for i in range(10):
            store.update_status(t.task_id, f"running - step {i}")
        store.update_status(t.task_id, "completed")
        growth = os.path.getsize(journal) - base
        assert growth < 5_000, (
            f"transitions appended {growth}B — bodies are riding updates")
        store.close()

        revived = JournaledTaskStore(journal)
        assert revived.get(t.task_id).canonical_status == TaskStatus.COMPLETED
        assert revived.get_original_body(t.task_id) == body
        revived.close()

    def test_compaction_shrinks_and_preserves_state(self, tmp_path):
        import os

        journal = str(tmp_path / "compact.jsonl")
        store = JournaledTaskStore(journal)
        tasks = [store.upsert(make_task(body=b"payload-%d" % i))
                 for i in range(5)]
        for t in tasks:
            for k in range(20):
                store.update_status(t.task_id, f"running - {k}")
            store.update_status(t.task_id, "completed")
        before = os.path.getsize(journal)
        store.compact()
        after = os.path.getsize(journal)
        assert after < before
        # One record per live task.
        with open(journal) as f:
            assert sum(1 for line in f if line.strip()) == len(tasks)
        store.close()

        revived = JournaledTaskStore(journal)
        for i, t in enumerate(tasks):
            assert revived.get(t.task_id).canonical_status == "completed"
            assert revived.get_original_body(t.task_id) == b"payload-%d" % i
        revived.close()

    def test_auto_compaction_bounds_journal(self, tmp_path):
        journal = str(tmp_path / "auto.jsonl")
        store = JournaledTaskStore(journal, compact_every=50)
        t = store.upsert(make_task(body=b"x"))
        for i in range(300):
            store.update_status(t.task_id, f"running - {i}")
        # 300 transitions with compact_every=50: the journal was rewritten,
        # so record count stays far below the mutation count.
        with open(journal) as f:
            lines = sum(1 for line in f if line.strip())
        assert lines <= 60, lines
        store.close()

        revived = JournaledTaskStore(journal)
        assert "299" in revived.get(t.task_id).status
        revived.close()

    def test_replay_compacts_bloated_journal_at_open(self, tmp_path):
        import os

        journal = str(tmp_path / "open.jsonl")
        store = JournaledTaskStore(journal)  # default threshold: no runtime compaction
        t = store.upsert(make_task(body=b"y"))
        for i in range(40):
            store.update_status(t.task_id, f"running - {i}")
        store.close()
        bloated = os.path.getsize(journal)

        revived = JournaledTaskStore(journal)  # open-time compaction
        assert os.path.getsize(journal) < bloated
        assert "39" in revived.get(t.task_id).status
        assert revived.get_original_body(t.task_id) == b"y"
        revived.close()


class TestDurableResults:
    """VERDICT r2 #4: completed tasks must survive restart WITH their results,
    and large results must route to the object-store slot instead of store
    memory (the reference's blob-storage role,
    ``APIs/helpers/assign_storage_auth_to_aks.sh:9-17``)."""

    def test_results_survive_restart(self, tmp_path):
        journal = str(tmp_path / "r.jsonl")
        store = JournaledTaskStore(journal)
        t = store.upsert(make_task())
        store.update_status(t.task_id, "completed - done")
        store.set_result(t.task_id, b'{"animals": 3}')
        store.set_result(t.task_id, b"stage-out", stage="detector")
        store.close()

        revived = JournaledTaskStore(journal)
        assert revived.get(t.task_id).canonical_status == "completed"
        assert revived.get_result(t.task_id) == (b'{"animals": 3}',
                                                 "application/json")
        assert revived.get_result(t.task_id, stage="detector") == (
            b"stage-out", "application/json")
        revived.close()

    def test_large_result_offloads_to_backend(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        store = InMemoryTaskStore(result_backend=backend,
                                  result_offload_threshold=1024)
        t = store.upsert(make_task())
        big = b"\x42" * 4096
        store.set_result(t.task_id, big, content_type="application/octet-stream")
        # Memory holds only the pointer; the payload is in the backend.
        assert store._results[t.task_id][0] is None
        assert backend.get(t.task_id) == (big, "application/octet-stream")
        # The read surface is unchanged.
        assert store.get_result(t.task_id) == (big, "application/octet-stream")

    def test_small_result_stays_inline(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        store = InMemoryTaskStore(result_backend=backend,
                                  result_offload_threshold=1024)
        t = store.upsert(make_task())
        store.set_result(t.task_id, b"tiny")
        assert store._results[t.task_id][0] == b"tiny"
        assert backend.get(t.task_id) is None

    def test_offloaded_result_survives_restart(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        journal = str(tmp_path / "r.jsonl")
        blobs = str(tmp_path / "blobs")
        store = JournaledTaskStore(journal,
                                   result_backend=FileResultBackend(blobs),
                                   result_offload_threshold=1024)
        t = store.upsert(make_task())
        big = b"\x7f" * 8192
        store.set_result(t.task_id, big, content_type="image/png")
        store.close()
        # The journal holds a pointer, not the blob (no hex-doubling).
        import os
        assert os.path.getsize(journal) < 4096

        revived = JournaledTaskStore(journal,
                                     result_backend=FileResultBackend(blobs),
                                     result_offload_threshold=1024)
        assert revived.get_result(t.task_id) == (big, "image/png")
        revived.close()

    def test_compaction_preserves_results(self, tmp_path):
        journal = str(tmp_path / "c.jsonl")
        store = JournaledTaskStore(journal)
        t = store.upsert(make_task())
        store.set_result(t.task_id, b"keep me")
        for i in range(20):
            store.update_status(t.task_id, f"running - {i}")
        store.compact()
        store.close()

        revived = JournaledTaskStore(journal)
        assert revived.get_result(t.task_id) == (b"keep me",
                                                 "application/json")
        revived.close()

    def test_stage_key_is_filesystem_safe(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        store = InMemoryTaskStore(result_backend=backend,
                                  result_offload_threshold=0)
        t = store.upsert(make_task())
        store.set_result(t.task_id, b"x" * 10, stage="v1/detect")
        assert store.get_result(t.task_id, stage="v1/detect") == (
            b"x" * 10, "application/json")

    def test_unknown_task_offload_leaves_no_orphan_blob(self, tmp_path):
        import os

        from ai4e_tpu.taskstore import FileResultBackend

        blobs = str(tmp_path / "blobs")
        store = InMemoryTaskStore(result_backend=FileResultBackend(blobs),
                                  result_offload_threshold=0)
        with pytest.raises(TaskNotFound):
            store.set_result("no-such-task", b"x" * 64)
        assert os.listdir(blobs) == []

    def test_distinct_stage_keys_do_not_collide(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        store = InMemoryTaskStore(result_backend=backend,
                                  result_offload_threshold=0)
        t = store.upsert(make_task())
        store.set_result(t.task_id, b"slash", stage="x/y")
        store.set_result(t.task_id, b"under", stage="x_y")
        assert store.get_result(t.task_id, stage="x/y")[0] == b"slash"
        assert store.get_result(t.task_id, stage="x_y")[0] == b"under"

    def test_inline_rewrite_deletes_stale_blob(self, tmp_path):
        import os

        from ai4e_tpu.taskstore import FileResultBackend

        blobs = str(tmp_path / "blobs")
        store = InMemoryTaskStore(result_backend=FileResultBackend(blobs),
                                  result_offload_threshold=100)
        t = store.upsert(make_task())
        store.set_result(t.task_id, b"B" * 200)      # offloaded
        assert len(os.listdir(blobs)) == 2
        store.set_result(t.task_id, b"small")        # superseded inline
        assert os.listdir(blobs) == []
        assert store.get_result(t.task_id)[0] == b"small"

    def test_replay_of_offloaded_pointer_without_backend_fails_fast(
            self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        journal = str(tmp_path / "j.jsonl")
        store = JournaledTaskStore(
            journal, result_backend=FileResultBackend(str(tmp_path / "b")),
            result_offload_threshold=0)
        t = store.upsert(make_task())
        store.set_result(t.task_id, b"blob-bytes")
        store.close()
        with pytest.raises(RuntimeError, match="offloaded result"):
            JournaledTaskStore(journal)  # no backend configured


class TestTerminalEviction:
    """Terminal-history retention: a long-running store must not grow
    forever with finished tasks (the Redis-expiry role the reference's
    store leans on)."""

    def _finish(self, store, body=b"payload", result=None):
        t = store.upsert(make_task(body=body))
        store.update_status(t.task_id, "completed - done")
        if result is not None:
            store.set_result(t.task_id, result)
        return t

    def test_evicts_old_terminal_keeps_young_and_running(self):
        import time as _time

        store = InMemoryTaskStore()
        old = self._finish(store, result=b"r1")
        running = store.upsert(make_task())
        store.update_status(running.task_id, "running - inference")
        # Age the finished task's set score artificially.
        path = old.endpoint_path
        store._sets[(path, "completed")][old.task_id] = _time.time() - 1000
        store._tasks[old.task_id].timestamp = _time.time() - 1000
        young = self._finish(store, result=b"r2")

        assert store.evict_terminal_older_than(500) == 1
        with pytest.raises(TaskNotFound):
            store.get(old.task_id)
        assert store.get_result(old.task_id) is None
        assert store.get_original_body(old.task_id) == b""
        assert store.set_len(path, "completed") == 1  # young survives
        assert store.get(young.task_id).canonical_status == "completed"
        assert store.get(running.task_id).canonical_status == "running"

    def test_eviction_deletes_offloaded_blobs(self, tmp_path):
        import os
        import time as _time

        from ai4e_tpu.taskstore import FileResultBackend

        blobs = str(tmp_path / "blobs")
        store = InMemoryTaskStore(result_backend=FileResultBackend(blobs),
                                  result_offload_threshold=0)
        t = self._finish(store, result=b"blob-bytes" * 10)
        assert len(os.listdir(blobs)) == 2
        store._sets[(t.endpoint_path, "completed")][t.task_id] = (
            _time.time() - 1000)
        assert store.evict_terminal_older_than(500) == 1
        assert os.listdir(blobs) == []

    def test_eviction_survives_restart_and_shrinks_journal(self, tmp_path):
        import os
        import time as _time

        journal = str(tmp_path / "e.jsonl")
        store = JournaledTaskStore(journal)
        tasks = [self._finish(store, body=b"x" * 500, result=b"y" * 500)
                 for _ in range(5)]
        for t in tasks[:4]:
            store._sets[(t.endpoint_path, "completed")][t.task_id] = (
                _time.time() - 1000)
        assert store.evict_terminal_older_than(500) == 4
        store.compact()
        compacted = os.path.getsize(journal)
        store.close()

        revived = JournaledTaskStore(journal)
        for t in tasks[:4]:
            with pytest.raises(TaskNotFound):
                revived.get(t.task_id)
        assert revived.get(tasks[4].task_id).canonical_status == "completed"
        assert revived.get_result(tasks[4].task_id) == (
            b"y" * 500, "application/json")
        # The journal holds ~1 task (~3.4 kB with hex-doubled body/orig/
        # result), not 5 (~17 kB).
        assert compacted < 6000, compacted
        revived.close()

    def test_evict_records_replay_without_compaction(self, tmp_path):
        import time as _time

        journal = str(tmp_path / "r.jsonl")
        store = JournaledTaskStore(journal)
        t = self._finish(store)
        store._sets[(t.endpoint_path, "completed")][t.task_id] = (
            _time.time() - 1000)
        assert store.evict_terminal_older_than(500) == 1
        store.close()  # no compaction: journal = upsert + slim + evict

        revived = JournaledTaskStore(journal)
        with pytest.raises(TaskNotFound):
            revived.get(t.task_id)
        revived.close()

    def test_reaper_drives_eviction(self):
        import time as _time

        from ai4e_tpu.taskstore.reaper import TaskReaper

        async def main():
            store = InMemoryTaskStore()
            t = self._finish(store)
            store._sets[(t.endpoint_path, "completed")][t.task_id] = (
                _time.time() - 1000)
            reaper = TaskReaper(store, running_timeout=None,
                                terminal_retention=500)
            acted = await reaper.sweep()
            assert acted == 1
            with pytest.raises(TaskNotFound):
                store.get(t.task_id)

        import asyncio
        asyncio.run(main())

    def test_eviction_is_order_independent(self, tmp_path):
        """Journal compaction rewrites tasks in CREATION order, so terminal
        sets are not score-monotone after a restart — an old task sitting
        behind a young one must still evict (review repro, r3)."""
        import time as _time

        journal = str(tmp_path / "o.jsonl")
        store = JournaledTaskStore(journal)
        a = self._finish(store)  # created first...
        b = self._finish(store)
        path = a.endpoint_path
        # ...but A completed recently while B completed long ago (age both
        # the set score and the record timestamp — compaction persists the
        # latter).
        store._sets[(path, "completed")][b.task_id] = _time.time() - 10000
        store._tasks[b.task_id].timestamp = _time.time() - 10000
        store.compact()  # rewrite in creation order: A (young) before B (old)
        store.close()

        revived = JournaledTaskStore(journal)
        assert revived.evict_terminal_older_than(5000) == 1
        with pytest.raises(TaskNotFound):
            revived.get(b.task_id)
        assert revived.get(a.task_id).canonical_status == "completed"
        revived.close()

    def test_native_store_with_retention_refused(self):
        from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
        with pytest.raises(ValueError, match="eviction"):
            LocalPlatform(PlatformConfig(native_store=True,
                                         reaper_terminal_retention=60.0))


class TestDirectToStorageResults:
    """The reference's containers write batch outputs straight to blob
    storage (assign_storage_auth_to_aks.sh) — here workers write the shared
    result mount and register only a pointer."""

    def test_ref_registers_existing_blob(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        backend = FileResultBackend(str(tmp_path / "blobs"))
        store = InMemoryTaskStore(result_backend=backend,
                                  result_offload_threshold=10**9)
        t = store.upsert(make_task())
        backend.put(t.task_id, b"worker-wrote-this", "application/json")
        store.set_result_ref(t.task_id)
        assert store.get_result(t.task_id) == (b"worker-wrote-this",
                                               "application/json")

    def test_ref_without_blob_refused(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        store = InMemoryTaskStore(
            result_backend=FileResultBackend(str(tmp_path / "b")))
        t = store.upsert(make_task())
        with pytest.raises(FileNotFoundError):
            store.set_result_ref(t.task_id)

    def test_ref_without_backend_refused(self):
        store = InMemoryTaskStore()
        t = store.upsert(make_task())
        with pytest.raises(RuntimeError, match="backend"):
            store.set_result_ref(t.task_id)

    def test_journaled_ref_survives_restart(self, tmp_path):
        from ai4e_tpu.taskstore import FileResultBackend

        journal = str(tmp_path / "j.jsonl")
        blobs = str(tmp_path / "blobs")
        backend = FileResultBackend(blobs)
        store = JournaledTaskStore(journal, result_backend=backend)
        t = store.upsert(make_task())
        backend.put(t.task_id, b"direct" * 100, "application/octet-stream")
        store.set_result_ref(t.task_id,
                             content_type="application/octet-stream")
        store.close()

        revived = JournaledTaskStore(journal,
                                     result_backend=FileResultBackend(blobs))
        assert revived.get_result(t.task_id) == (
            b"direct" * 100, "application/octet-stream")
        revived.close()


class TestEvictionScales:
    def test_bulk_eviction_is_linear_in_victims(self):
        """Eviction must be O(victims' results), not O(victims × all
        results): the 40-min soak wedged the control plane for minutes when
        ~6k victims each scanned ~190k result keys under the store lock
        (scripts/soak.sh). 20k tasks-with-results evicted here in
        well under the old quadratic path's ~40 s."""
        import time as _time

        from ai4e_tpu.taskstore import InMemoryTaskStore
        from ai4e_tpu.taskstore.task import APITask

        store = InMemoryTaskStore()
        for i in range(20000):
            t = store.upsert(APITask(task_id=f"t{i}", endpoint="http://h/v1/x",
                                     body=b"b", status="completed",
                                     backend_status="completed"))
            store.set_result(t.task_id, b'{"ok":1}')
        t0 = _time.perf_counter()
        evicted = store.evict_terminal_older_than(0.0)
        elapsed = _time.perf_counter() - t0
        assert evicted == 20000
        assert not store._results and not store._result_keys
        assert elapsed < 10.0, f"bulk eviction took {elapsed:.1f}s"

    def test_colon_task_ids_rejected(self):
        """':' is the result-key stage separator — a client-supplied id
        carrying one would alias another task's result namespace (the
        eviction index derives the owner by splitting on ':'), so it is
        refused at every write boundary."""
        import pytest

        from ai4e_tpu.taskstore import InMemoryTaskStore
        from ai4e_tpu.taskstore.task import APITask

        store = InMemoryTaskStore()
        with pytest.raises(ValueError, match="must not contain"):
            store.upsert(APITask(task_id="job:7", endpoint="http://h/v1/x",
                                 body=b"b"))
