"""Task state store — the platform's core state machine.

Equivalent of the reference's CacheManager over Azure Redis
(``ProcessManager/CacheManager/CacheConnectorUpsert.cs:40-213`` /
``CacheConnectorGet.cs:26-74``), re-designed as a library with pluggable
backends instead of an Azure Function over a remote Redis:

- ``upsert`` creates a task (new GUID) or transitions an existing one, updating
  per-endpoint, per-status ordered sets scored by epoch seconds and removing the
  task from its prior status set (mirrors the Redis MULTI transaction at
  ``CacheConnectorUpsert.cs:125-170``). All of that happens under one lock here —
  the transactionality the reference got from Redis MULTI.
- the original request body is stored per task and replayed when a pipeline
  stage re-publishes the task with an empty body
  (``CacheConnectorUpsert.cs:144-176`` reads ``{taskId}_ORIG``).
- when a task is upserted with ``publish=True`` the store hands it to a
  publisher (the broker); a publish failure rolls the task to ``failed`` in the
  same breath (``CacheConnectorUpsert.cs:183-199``).
- ``JournaledTaskStore`` adds crash-durability via an append-only JSONL journal
  (replaces Redis persistence): on restart, replaying the journal rebuilds the
  exact store state so queued tasks survive worker crashes.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Iterable

from dataclasses import replace

from .task import APITask, TaskStatus, new_task_id

Publisher = Callable[[APITask], None]


class TaskNotFound(KeyError):
    pass


class NotPrimaryError(RuntimeError):
    """A mutation reached a follower replica — only the primary accepts
    writes (the HTTP surface maps this to 503 so store clients fail over)."""


class StoreClosedError(RuntimeError):
    """A mutation reached a closed store (shutdown, or a shard primary the
    chaos harness SIGKILLed). RuntimeError subclass so pre-existing callers
    that caught the old bare RuntimeError keep working; the sharded facade
    keys its failover-promotion retry on this specific class."""


class NotOwnerError(RuntimeError):
    """A mutation reached a shard store for a TaskId the hash ring no longer
    assigns to it — the caller raced a rebalance handoff and is the stale
    owner (``taskstore/sharding.py``). Checked under the store lock, and the
    ring flip happens under the OLD owner's lock, so a stale write can never
    slip through the handoff window; the sharded facade re-routes via a
    fresh ring lookup, direct holders of the old shard fail loudly."""


class StaleEpochError(ValueError):
    """A demotion was attempted with an epoch no newer than the store's own
    — the caller is the stale side of the split, not this store (the HTTP
    surface maps this to 409)."""


class JournalDegradedError(RuntimeError):
    """The journal hit a disk fault (ENOSPC/EIO on append or fsync) and the
    store flipped to fenced read-only DEGRADED mode: reads keep serving,
    every mutation refuses with this error until ``recover()`` clears it —
    never an exception mid-mutation that leaves memory ahead of disk. The
    HTTP surfaces map it to a typed 503 with ``X-Shed-Reason:
    journal-degraded`` so breakers/orchestration see the node like a dark
    backend; the sharded facade treats it as a failover trigger
    (docs/durability.md#degraded-mode).

    ``rollback`` tells the raising append's caller whether the in-memory
    mutation must be unwound: True for write/flush failures (the record's
    bytes may be torn or absent on disk), False for fsync failures (the
    bytes ARE in the file — refusing the ack while keeping memory equal to
    the file is the honest state; the refused-but-durable record is the
    documented at-least-once residual)."""

    def __init__(self, message: str, rollback: bool = True):
        super().__init__(message)
        self.rollback = rollback


class StoreSideEffects:
    """Listener + publish side-effect plumbing shared by every store
    implementation (Python and native): transitions notify observers (e.g.
    the gateway's long-poll waiters) outside any lock, and a publish failure
    rolls the task to failed (``CacheConnectorUpsert.cs:183-199``)."""

    _publisher: Publisher | None
    _listeners: list

    def set_publisher(self, publisher: Publisher | None) -> None:
        self._publisher = publisher

    def add_listener(self, listener: Callable[["APITask"], None]) -> None:
        self._listeners.append(listener)

    def _notify(self, task: "APITask") -> None:
        for listener in self._listeners:
            try:
                listener(task)
            except Exception:  # noqa: BLE001 — observers must not break the store
                import logging
                logging.getLogger("ai4e_tpu.taskstore").exception(
                    "task listener failed for %s", task.task_id)

    def _publish_after(self, task: "APITask",
                      publisher: Publisher | None) -> None:
        if publisher is None:
            return
        try:
            publisher(task)
        except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the failure is recorded ON the task itself (failed - could not publish)
            self.update_status(
                task.task_id,
                f"failed - could not publish task: {exc}",
                backend_status=TaskStatus.FAILED,
            )

    def update_status(self, task_id, status, backend_status=None):
        raise NotImplementedError


class InMemoryTaskStore(StoreSideEffects):
    """Thread-safe in-process task store.

    Used directly by tests and single-process deployments; the HTTP task-store
    service (``taskstore.http``) wraps one of these, and multi-host deployments
    talk to that service the way reference services talk to the CacheConnector
    functions.
    """

    # True while applying already-accepted history verbatim (journal replay,
    # follower absorb, rebalance import): input validation AND the shard
    # write fence are both off — history must apply as-is.
    _absorbing = False
    # Closed stores refuse mutations (StoreClosedError); reads stay served.
    # The journaled subclass additionally closes its journal handle; the
    # base flag exists so journal-less shard primaries get SIGKILL
    # semantics too (chaos ``ShardGroup.mark_dead``).
    _closed = False

    def __init__(self, publisher: Publisher | None = None,
                 result_backend=None,
                 result_offload_threshold: int | None = None):
        self._lock = threading.RLock()
        self._tasks: dict[str, APITask] = {}
        # task_id -> (body, content_type): the replay record. Content type
        # rides along because republishes (pipeline handoff, saturation
        # requeue, reaper rescue) must redeliver the original payload with
        # its original type — a JPEG replayed as application/json would be
        # undecodable downstream.
        self._orig_bodies: dict[str, tuple[bytes, str]] = {}
        # key -> (payload, content_type); payload None means the bytes live
        # in the result backend (the blob-storage slot,
        # assign_storage_auth_to_aks.sh:9-17) — only the pointer is held here,
        # so completed-task memory doesn't grow with large batch outputs.
        self._results: dict[str, tuple[bytes | None, str]] = {}
        # task_id -> result keys owned by it ("{tid}" / "{tid}:{stage}"):
        # eviction must be O(victim's results), not O(all results) — the
        # 40-min soak wedged the store for minutes when each of ~6k
        # victims scanned ~190k result keys under the lock
        # (scripts/soak.sh; tests/test_taskstore.py TestEvictionScales).
        self._result_keys: dict[str, set[str]] = {}
        self._result_backend = result_backend
        self._result_offload_threshold = result_offload_threshold
        # (endpoint_path, canonical_status) -> {task_id: score}; insertion
        # ordered + scored like the reference's Redis sorted sets.
        self._sets: dict[tuple[str, str], dict[str, float]] = {}
        self._publisher = publisher
        # Shard ownership fence (``taskstore/sharding.py``): when set, every
        # task/result mutation verifies — under this store's lock — that the
        # hash ring still assigns the TaskId here; a stale owner raises
        # NotOwnerError instead of applying an orphan write. None (the
        # default, every unsharded deployment) is a no-op.
        self._write_fence: Callable[[str], bool] | None = None
        # Change listeners (e.g. the gateway's long-poll waiters). Called
        # outside the lock, after every state transition, possibly from any
        # thread — listeners must be cheap and thread-safe
        # (StoreSideEffects._notify).
        self._listeners: list[Callable[[APITask], None]] = []
        # Hop-ledger timelines (observability/ledger.py): task_id ->
        # [event dicts], appended by every hop when the observability
        # layer is on. Observability state, NOT durable truth — never
        # journaled, dropped with the record at eviction; a restart
        # loses timelines, never tasks (docs/observability.md).
        self._ledgers: dict[str, list[dict]] = {}

    # -- core state machine ------------------------------------------------

    def upsert(self, task: APITask) -> APITask:
        """Create or transition a task; returns the stored record.

        Semantics of ``CacheConnectorUpsert.TaskRun``:
        - no existing record → create (fresh GUID unless one was supplied);
          non-empty body is remembered as the original body for pipeline replay;
        - existing record → status transition; an empty body on a *publishing*
          upsert is a subsequent pipeline call and replays the original body;
        - status-set bookkeeping: remove from prior set, add to new set scored
          by now;
        - ``publish=True`` → hand to the broker; on broker failure the task is
          marked failed instead of raising to the caller.

        Client-supplied TaskIds must not contain ``:`` — it is the result
        namespace's stage separator (``{taskId}:{stage}`` keys), and an id
        carrying one would alias another task's result keys (eviction
        could then leak this task's results or destroy a neighbor's).
        The guard runs on EXTERNAL write paths only (``_validates_task_ids``):
        journal replay and follower absorb apply history as-is — a legacy
        pre-guard journal must never crash-loop ``__init__._replay`` or
        wedge a follower's absorb/retry loop at a fixed offset (ADVICE r5).
        """
        with self._lock:
            # Validation decision UNDER the lock: ``_absorbing`` flips under
            # it (rebalance import), and a pre-lock read could skip the
            # guard for an unrelated external upsert racing an import.
            if ":" in task.task_id and self._validates_task_ids():
                raise ValueError(
                    f"TaskId must not contain ':' (reserved as the result "
                    f"stage separator): {task.task_id!r}")
            # NOTE: '~' (task.SUB_TASK_SEP, pipeline stage sub-tasks) is
            # deliberately NOT rejected here — the coordinator mints
            # "{root}~{stage}" ids through this very path. The HTTP
            # surface refuses CREATES of unknown '~' ids instead
            # (taskstore/http.py), which is where a forged alias could
            # enter; in-process callers are platform code.
            task = self._apply_upsert(task)
            publisher = self._publisher if task.publish else None

        self._notify(task)
        self._publish_after(task, publisher)
        return task

    def _validates_task_ids(self) -> bool:
        """Whether upsert enforces input validation — True on every external
        write path; off while absorbing history (rebalance import here; the
        journaled subclass additionally turns it off while replaying —
        records that were already accepted once must apply verbatim, or a
        restart/follower can never catch up)."""
        return not self._absorbing

    def set_write_fence(self, fence: Callable[[str], bool] | None) -> None:
        """Install (or clear) the shard ownership fence — ``fence(task_id)``
        must answer True iff this store currently owns the id. Called under
        the store lock on every mutation, so it must be cheap and must not
        take other locks (the ring lookup is arithmetic + a list read)."""
        self._write_fence = fence

    def _check_owner(self, task_id: str) -> None:
        """Shard-fence gate for task/result mutations. Skipped while
        absorbing (history applies verbatim — the rebalance import IS the
        new owner receiving the range) and for empty ids (the id is minted
        below, by a store that trivially owns a fresh GUID). Eviction is
        deliberately NOT fenced: it is garbage collection — it can neither
        resurrect nor clobber a task — and the migration's own post-flip
        cleanup of the moved range runs as the (by then) non-owner."""
        fence = self._write_fence
        if fence is None or self._absorbing or not task_id:
            return
        if not fence(task_id):
            raise NotOwnerError(
                f"task {task_id} is no longer owned by this shard "
                "(rebalance moved its hash slot); route via the ring")

    def _apply_upsert(self, task: APITask) -> APITask:
        """State mutation for upsert. Caller holds ``self._lock``; subclasses
        extend this to journal atomically with the mutation."""
        self._check_open()
        self._check_owner(task.task_id)
        prev = self._tasks.get(task.task_id)
        if prev is None:
            if not task.task_id:
                task.task_id = new_task_id()
            if task.body:
                self._orig_bodies[task.task_id] = (task.body, task.content_type)
        else:
            if not task.cache_key:
                # Cache provenance survives pipeline handoffs and requeues:
                # the terminal result of the LAST stage is what the original
                # request's cache key should resolve to (rescache/wiring.py).
                task.cache_key = prev.cache_key
            if not task.deadline_at:
                # Admission state survives handoffs/requeues the same way:
                # a pipeline's second stage runs under the ORIGINAL
                # request's deadline (the caller's budget covers the whole
                # composite), and a requeue must not shed its class label.
                task.deadline_at = prev.deadline_at
            if task.priority == 1 and prev.priority != 1:
                task.priority = prev.priority
            if not prev.durable:
                # Memory-only stays memory-only: an external full upsert
                # (facade records default durable=True) must not promote a
                # cache-hit record into the journal — its create was never
                # journaled, so replay would drop the slim transitions
                # silently and compaction would write the very payload-sized
                # records durable=False exists to prevent.
                task.durable = False
            if not task.body and task.publish:
                # Subsequent pipeline call: replay the original body + its
                # content type (CacheConnectorUpsert.cs:144-176).
                task.body, task.content_type = self._orig_bodies.get(
                    task.task_id, (b"", task.content_type))
            elif task.body and task.publish:
                # Pipeline handoff with a fresh payload (e.g. detector crops
                # for the classifier): that payload is now the task's replay
                # body — a later empty-body requeue of the new stage must get
                # the stage's own input, not stage 1's.
                self._orig_bodies[task.task_id] = (task.body, task.content_type)
            self._remove_from_set(prev)
        if not (self._absorbing and task.timestamp):
            # Live mutations stamp "now"; absorbed history (follower
            # absorb, rebalance import) keeps the record's own timestamp so
            # set scores and the reaper's age clock survive the handoff —
            # and the journaled subclass's append then serializes the TRUE
            # timestamp, so a restart of the absorbing store replays it.
            task.timestamp = time.time()
        self._tasks[task.task_id] = task
        self._add_to_set(task)
        return task

    def update_status(
        self, task_id: str, status: str, backend_status: str | None = None
    ) -> APITask:
        """Atomic status transition by id — no read-modify-write race (the
        reference's ``_UpdateTaskStatus`` GET-then-POST at
        ``distributed_api_task.py:29-56`` is racy; SURVEY.md §5 flags it)."""
        with self._lock:
            task = self._apply_update(task_id, status, backend_status)
        self._notify(task)
        return task

    def _apply_update(
        self, task_id: str, status: str, backend_status: str | None
    ) -> APITask:
        """State mutation for update. Caller holds ``self._lock``."""
        self._check_open()
        self._check_owner(task_id)
        prev = self._tasks.get(task_id)
        if prev is None:
            raise TaskNotFound(task_id)
        task = prev.with_status(status, backend_status)
        task.publish = False
        self._remove_from_set(prev)
        self._tasks[task_id] = task
        self._add_to_set(task)
        return task

    # -- atomic conditional transitions (the reaper's rescue path: a sweep
    # decision taken from a snapshot must not clobber a task that reached a
    # terminal state in the meantime) ---------------------------------------

    def requeue_if(self, task_id: str, expected_status: str) -> APITask | None:
        """Republish the task (empty body → original replay) iff its
        canonical status is still ``expected_status``; None otherwise."""
        with self._lock:
            current = self._tasks.get(task_id)
            if current is None or current.canonical_status != expected_status:
                return None
            task = self._apply_upsert(APITask(
                task_id=task_id, endpoint=current.endpoint, body=b"",
                status=TaskStatus.CREATED, backend_status=TaskStatus.CREATED,
                content_type=current.content_type, publish=True))
            publisher = self._publisher if task.publish else None
        self._notify(task)
        self._publish_after(task, publisher)
        return task

    def update_status_if(self, task_id: str, expected_status: str,
                         status: str,
                         backend_status: str | None = None) -> APITask | None:
        """Status transition iff the canonical status is still
        ``expected_status``; None otherwise."""
        with self._lock:
            current = self._tasks.get(task_id)
            if current is None or current.canonical_status != expected_status:
                return None
            task = self._apply_update(task_id, status, backend_status)
        self._notify(task)
        return task

    def get(self, task_id: str) -> APITask:
        with self._lock:
            task = self._tasks.get(task_id)
            if task is None:
                raise TaskNotFound(task_id)
            return task

    # -- hop ledger (observability/ledger.py) -------------------------------

    def append_ledger(self, task_id: str, events: list[dict]) -> int:
        """Append hop-ledger events to a known task's timeline; returns
        the events actually kept (the per-task cap —
        ``observability.ledger.MAX_EVENTS``, the same bound the worker's
        HopLedger buffers to — drops overflow with a single
        ``truncated`` marker). Raises TaskNotFound for unknown ids and
        refuses on closed/follower/non-owner stores like every other
        mutation — callers (the observability hub, the HTTP surface)
        treat all of those as droppable: the ledger is fail-open
        telemetry, not task state."""
        from ..observability.ledger import (MAX_EVENTS, TRUNCATED,
                                            ledger_event)
        check_writable = getattr(self, "_check_writable", None)
        with self._lock:
            self._check_open()
            if check_writable is not None:
                check_writable()
            self._check_owner(task_id)
            if task_id not in self._tasks:
                raise TaskNotFound(task_id)
            timeline = self._ledgers.setdefault(task_id, [])
            kept = 0
            for ev in events:
                if len(timeline) >= MAX_EVENTS:
                    if (not timeline
                            or timeline[-1].get("e") != TRUNCATED):
                        timeline.append(ledger_event(TRUNCATED, "store"))
                    break
                timeline.append(ev)
                kept += 1
            return kept

    def get_ledger(self, task_id: str) -> list[dict]:
        """The task's timeline (empty for unknown tasks or when the
        observability layer never stamped — reads never raise: the
        ledger query is a debugging surface)."""
        with self._lock:
            return list(self._ledgers.get(task_id, ()))

    def dump_ledgers(self, limit: int = 5000) -> dict[str, list[dict]]:
        """Every resident timeline (bounded) — the rig driver's
        pre-teardown collection surface (``GET /v1/rig/ledgers``): hop
        ledgers are memory-only observability state, so the timeline
        exporter must read them out before the process dies with them
        (docs/observability.md). Newest-stamped last; reads never
        raise."""
        with self._lock:
            items = list(self._ledgers.items())
        if limit >= 0:
            items = items[-limit:] if limit else []
        return {tid: list(evs) for tid, evs in items}

    # -- retention (terminal-history eviction) ------------------------------

    def evict_terminal_older_than(self, age_s: float) -> int:
        """Remove terminal (completed/failed) tasks older than ``age_s``
        seconds — record, status-set entry, original body, results, and any
        offloaded blobs. Without this a long-running store's memory and
        journal grow with every task ever finished (the reference leans on
        Redis eviction/expiry for the same role). Returns tasks evicted.
        Cost is O(terminal history), which this very mechanism keeps
        bounded at ~(completion rate × retention). Set scores are NOT
        assumed monotone — journal compaction rewrites tasks in creation
        order, so a full scan is the only correct victim collection."""
        cutoff = time.time() - age_s
        blob_keys: list[str] = []
        evicted = 0
        try:
            with self._lock:
                victims = []
                for (path, status), members in self._sets.items():
                    if status not in TaskStatus.TERMINAL:
                        continue
                    victims.extend(task_id
                                   for task_id, score in members.items()
                                   if score < cutoff)
                for task_id in victims:
                    blob_keys.extend(self._apply_evict(task_id))
                    evicted += 1
        finally:
            # Backend I/O OUTSIDE the lock (a GCS/PD delete is a network
            # round trip; thousands of victims on a first sweep must not
            # stall every store operation). Crash-ordering: the journaled
            # subclass appends the Evict record inside _apply_evict, i.e.
            # BEFORE these deletes — a crash in between leaks blobs
            # harmlessly instead of replaying a completed task whose
            # offloaded result is gone. Runs in a finally: on a mid-batch
            # journal-degraded abort, earlier victims are already evicted
            # AND journaled, so no record references their blobs — skipping
            # the deletes would orphan them on the mount forever (review
            # finding; the aborted victim itself rolled back and kept its
            # pointers, so its keys never reach blob_keys).
            for key in blob_keys:
                self._delete_blob(key)
        return evicted

    def _apply_evict(self, task_id: str) -> list[str]:
        """Forget one task entirely; returns offloaded-result keys whose
        blobs the CALLER must delete (outside the lock). Caller holds
        ``self._lock``; the journaled subclass extends this."""
        task = self._tasks.pop(task_id, None)
        if task is None:
            return []
        self._remove_from_set(task)
        self._orig_bodies.pop(task_id, None)
        self._ledgers.pop(task_id, None)
        blob_keys = []
        # O(this task's results) via the key index — NEVER a scan of all
        # results (each victim of a bulk eviction would pay O(history)).
        for key in self._result_keys.pop(task_id, ()):
            found = self._results.pop(key, None)
            if found is not None and found[0] is None:
                blob_keys.append(key)
        return blob_keys

    def get_original_body(self, task_id: str) -> bytes:
        with self._lock:
            return self._orig_bodies.get(task_id, (b"", ""))[0]

    # -- results (the reference delegates results to external blob storage;
    # here they're first-class, keyed like {taskId}_RESULT) -----------------

    def set_result(self, task_id: str, result: bytes,
                   content_type: str = "application/json",
                   stage: str | None = None) -> None:
        """Store a task's result payload. ``stage`` stores an intermediate
        pipeline-stage result (keyed ``{taskId}:{stage}``) without touching
        the final result — so each stage of a composite API leaves its output
        retrievable under the shared TaskId, analogous to the reference
        keeping ``{taskId}_ORIG`` alongside the task (``CacheConnectorUpsert.cs:158``)."""
        key = task_id if stage is None else f"{task_id}:{stage}"
        owner = self._tasks.get(task_id)
        offload = (self._result_backend is not None
                   and self._result_offload_threshold is not None
                   and len(result) >= self._result_offload_threshold
                   # Non-durable records (cache hits) are memory-only: their
                   # results stay inline — per-hit blob writes would put
                   # payload-sized I/O back on the exact path the cache
                   # exists to avoid, and a restart would orphan the blobs
                   # on the mount (no journaled record references them, so
                   # no eviction ever deletes them).
                   and (owner is None or owner.durable))
        if offload:
            # Write the blob BEFORE taking the lock (it may be slow storage)
            # and before the pointer becomes visible — a reader that sees the
            # pointer must always find the blob.
            self._result_backend.put(key, result, content_type)
        try:
            with self._lock:
                if task_id not in self._tasks:
                    raise TaskNotFound(task_id)
                self._apply_set_result(key, None if offload else result,
                                       content_type)
        except Exception:
            # Reap the just-written blob UNLESS an offloaded pointer for
            # this key is visible in memory — the one invariant that
            # matters: visible pointer ⇒ its blob must exist. No pointer
            # (unknown/reaped task, closed store, degraded rollback of a
            # fresh result) ⇒ nothing references the blob and it would
            # leak on the mount forever. A visible pointer survives here
            # two ways: the key already held one (put() overwrote that
            # blob in place — deleting would dangle it; the residual is
            # the blob serving the refused write's bytes,
            # docs/durability.md#degraded-mode), or a rollback=False
            # fsync failure applied the mutation to match the file.
            with self._lock:
                now = self._results.get(key)
            if offload and not (now is not None and now[0] is None):
                self._delete_blob(key)
            raise

    def _apply_set_result(self, key: str, result: bytes | None,
                          content_type: str) -> None:
        """Result mutation (``result is None`` = offloaded pointer). Caller
        holds ``self._lock``; the journaled subclass extends this."""
        self._check_open()
        self._check_owner(key.split(":", 1)[0])
        self._set_result_in_memory(key, result, content_type)

    def _set_result_in_memory(self, key: str, result: bytes | None,
                              content_type: str) -> None:
        """The unchecked memory half of a result write. Split out so the
        journaled subclass can apply it AFTER a failed-but-durable append
        (rollback=False), when the open/degraded re-check would refuse a
        mutation whose record is already in the file."""
        prev = self._results.get(key)
        self._results[key] = (result, content_type)
        self._result_keys.setdefault(key.split(":", 1)[0], set()).add(key)
        if (prev is not None and prev[0] is None and result is not None):
            # An inline value superseded an offloaded pointer — the stale
            # blob is unreachable now; delete it. (Pointer→pointer rewrites
            # overwrite the same blob file in put().)
            self._delete_blob(key)

    def _delete_blob(self, key: str) -> None:
        if self._result_backend is None:
            return
        try:
            self._result_backend.delete(key)
        except Exception:  # noqa: BLE001 — cleanup must not mask the result path
            import logging
            logging.getLogger("ai4e_tpu.taskstore").exception(
                "could not delete result blob %s", key)

    def get_result(self, task_id: str,
                   stage: str | None = None) -> tuple[bytes, str] | None:
        key = task_id if stage is None else f"{task_id}:{stage}"
        with self._lock:
            found = self._results.get(key)
        if found is None:
            return None
        body, content_type = found
        if body is None:  # offloaded — fetch from the backend outside the lock
            if self._result_backend is None:
                return None  # unreachable after replay's fail-fast; be safe
            fetched = self._result_backend.get(key)
            if fetched is None:
                return None
            return fetched
        return body, content_type

    def set_result_ref(self, task_id: str,
                       content_type: str = "application/json",
                       stage: str | None = None) -> None:
        """Register a result the caller ALREADY wrote to the shared backend
        under the canonical key — the direct-to-storage worker path (the
        reference gives its containers blob-storage access so outputs never
        transit the control plane, ``assign_storage_auth_to_aks.sh:9-17``).
        The blob's existence is verified BEFORE the pointer becomes visible:
        a reader that sees the pointer must always find the blob."""
        if self._result_backend is None:
            raise RuntimeError(
                "no result backend configured (set result_dir) — cannot "
                "register a direct-to-storage result")
        key = task_id if stage is None else f"{task_id}:{stage}"
        found = self._result_backend.open(key)
        if found is None:
            raise FileNotFoundError(
                f"result blob {key!r} not present in the backend — write "
                "it before registering the pointer")
        found[0].close()
        with self._lock:
            if task_id not in self._tasks:
                raise TaskNotFound(task_id)
            self._apply_set_result(key, None, content_type)

    def open_result(self, task_id: str, stage: str | None = None):
        """Streaming accessor: ``(file_like, content_type, size)`` or None.
        Offloaded results stream straight from the backend (a multi-MB
        batch output never buffers whole in store/server memory); inline
        results adapt through BytesIO so callers have ONE read path."""
        key = task_id if stage is None else f"{task_id}:{stage}"
        with self._lock:
            found = self._results.get(key)
        if found is None:
            return None
        body, content_type = found
        if body is None:
            if self._result_backend is None:
                return None
            return self._result_backend.open(key)
        import io
        return io.BytesIO(body), content_type, len(body)

    # -- status-set queries (queue-depth metrics, QueueLogger.cs:21-47) ----

    def set_len(self, endpoint_path: str, status: str) -> int:
        with self._lock:
            return len(self._sets.get((endpoint_path, status), {}))

    def set_members(self, endpoint_path: str, status: str) -> list[str]:
        with self._lock:
            members = self._sets.get((endpoint_path, status), {})
            return sorted(members, key=members.__getitem__)

    def endpoints(self) -> list[str]:
        with self._lock:
            return sorted({path for path, _ in self._sets})

    def depths(self) -> dict[str, dict[str, int]]:
        """Per-endpoint per-status depths — the autoscaling signal
        (``TaskQueueLogger.cs:19-27`` logs ``_created`` depth every 30 s)."""
        with self._lock:
            out: dict[str, dict[str, int]] = {}
            for (path, status), members in self._sets.items():
                out.setdefault(path, {s: 0 for s in TaskStatus.ALL})[status] = len(members)
            return out

    # -- internals ---------------------------------------------------------

    def _add_to_set(self, task: APITask) -> None:
        key = (task.endpoint_path, task.canonical_status)
        self._sets.setdefault(key, {})[task.task_id] = task.timestamp

    def _remove_from_set(self, task: APITask) -> None:
        key = (task.endpoint_path, task.canonical_status)
        members = self._sets.get(key)
        if members is not None:
            members.pop(task.task_id, None)

    def snapshot(self) -> Iterable[APITask]:
        with self._lock:
            return list(self._tasks.values())

    def unfinished_tasks(self) -> list[APITask]:
        """Tasks in a non-terminal state (created/awaiting/running) — what a
        restarted platform must re-dispatch. Bodies are restored from the
        original-body record so redelivery carries the real payload."""
        with self._lock:
            out = []
            for task in self._tasks.values():
                if task.canonical_status in TaskStatus.TERMINAL:
                    continue
                if not task.body:
                    body, ctype = self._orig_bodies.get(
                        task.task_id, (b"", task.content_type))
                    task = replace(task, body=body, content_type=ctype)
                out.append(task)
            return out

    # -- record shapes shared by the journal and the rebalance wire --------
    # (defined here, not on the journaled subclass: the migration between
    # shards uses the same full-record format whether or not the shard
    # stores are journaled — docs/sharding.md)

    def _full_record(self, task: APITask) -> dict:
        """The journal's full (non-slim) record shape — one source of truth
        for appends, compaction rewrites, and rebalance exports."""
        rec = task.to_dict()
        rec["BodyHex"] = task.body.hex()
        orig = self._orig_bodies.get(task.task_id)
        if orig is not None:
            rec["OrigHex"] = orig[0].hex()
            rec["OrigContentType"] = orig[1]
        return rec

    def _result_record(self, key: str, body: bytes | None,
                       content_type: str) -> dict:
        rec = {"Result": True, "Key": key, "ContentType": content_type}
        if body is None:
            # Offloaded: the payload is durable in the result backend; the
            # journal carries only the pointer (no hex-doubling of large
            # blobs — offload exists precisely to keep them out of memory
            # and out of the journal).
            rec["Offloaded"] = True
        else:
            rec["ResultHex"] = body.hex()
        return rec

    # -- rebalance handoff (``taskstore/sharding.py`` move_slot) -----------

    def export_task_records(self, task_ids) -> list[dict]:
        """Full journal-shaped records (task + original body + its results)
        for the given ids — the rebalance wire format the new owner
        ``import_task_records``s. Task records come first so import applies
        them before their results, exactly like compaction/replay ordering.
        Non-durable records (memory-only cache hits) are skipped: their
        loss on a handoff has the same contract as their loss on a restart
        (the TaskId 404s; the terminal answer was already served)."""
        with self._lock:
            recs: list[dict] = []
            wanted = []
            for tid in task_ids:
                task = self._tasks.get(tid)
                if task is None or not task.durable:
                    continue
                wanted.append(tid)
                recs.append(self._full_record(task))
            for tid in wanted:
                for key in self._result_keys.get(tid, ()):
                    found = self._results.get(key)
                    if found is not None:
                        recs.append(self._result_record(key, found[0],
                                                        found[1]))
            return recs

    def import_task_records(self, recs: list[dict]) -> int:
        """Absorb migrated history from another shard. Applied verbatim like
        journal replay — no id validation, no publish, no listener
        notification (every transition already notified on the exporting
        shard; re-notifying here would be the duplicate-completion the
        chaos invariants reject) — and, on a journaled store, appended to
        the local journal so the imported range survives a restart of THIS
        shard. Idempotent: re-importing a record overwrites with identical
        state (the delta pass of ``move_slot`` relies on this)."""
        applied = 0
        with self._lock:
            self._check_open()
            prev_absorbing = self._absorbing
            self._absorbing = True
            # Defer auto-compaction past the import (journaled stores): the
            # rebalance delta pass runs this while holding the SOURCE
            # shard's lock, and an O(all tasks) compaction rewrite here
            # would stall the source's entire keyspace for its duration.
            # The next ordinary append — outside any foreign lock — picks
            # the deferred compaction up.
            prev_compact_at = getattr(self, "_next_compact_at", None)
            if prev_compact_at is not None:
                self._next_compact_at = float("inf")
            try:
                for rec in recs:
                    if self._apply_import(rec):
                        applied += 1
            finally:
                self._absorbing = prev_absorbing
                if prev_compact_at is not None:
                    self._next_compact_at = prev_compact_at
        return applied

    def _apply_import(self, rec: dict) -> bool:
        """Apply ONE migrated record. Caller holds ``self._lock`` with
        ``_absorbing`` set. Epoch markers are skipped — a fencing epoch is
        the exporting shard's lineage, never the importer's."""
        if "Epoch" in rec or rec.get("Evict") or rec.get("Slim"):
            return False  # migration exports full state only
        if rec.get("Result"):
            body = (None if rec.get("Offloaded")
                    else bytes.fromhex(rec.get("ResultHex", "")))
            self._apply_set_result(rec["Key"], body,
                                   rec.get("ContentType",
                                           "application/json"))
            return True
        task = APITask.from_dict(rec)
        task.body = bytes.fromhex(rec.get("BodyHex", ""))
        # Never re-publish: the task's broker message (if any) already
        # exists on the transport; the ring routes its status writes here.
        task.publish = False
        self._apply_upsert(task)  # _absorbing → timestamp preserved
        orig = rec.get("OrigHex")
        if orig:
            self._orig_bodies[task.task_id] = (
                bytes.fromhex(orig),
                rec.get("OrigContentType", "application/json"))
        return True

    # True while forget_tasks drops a migrated range: the journaled
    # subclass's Evict records then carry KeepBlobs, so neither this drop
    # NOR a later replay of it deletes result blobs the importing shard's
    # pointers now own (shards share one result backend). Only ever
    # flipped under ``self._lock``.
    _forgetting = False

    def forget_tasks(self, task_ids) -> int:
        """Drop the given tasks from this store entirely — the old owner's
        post-flip cleanup after a rebalance export. Unlike eviction, the
        offloaded result blobs are NOT deleted (see ``_forgetting``)."""
        with self._lock:
            dropped = 0
            self._forgetting = True
            try:
                for tid in list(task_ids):
                    if tid in self._tasks:
                        self._apply_evict(tid)  # blob keys deliberately unused
                        dropped += 1
            finally:
                self._forgetting = False
            return dropped

    def _check_open(self) -> None:
        # Refuse BEFORE mutating (the journaled subclass shares this flag
        # and additionally guards its journal handle).
        if self._closed:
            raise StoreClosedError("task store is closed")

    def close(self) -> None:
        self._closed = True


class JournaledTaskStore(InMemoryTaskStore):
    """InMemoryTaskStore + append-only JSONL journal for crash recovery.

    Plays the durability role Redis plays in the reference: a restarted store
    replays the journal and resumes with identical task state, so a crashed
    worker's tasks are still present for redelivery (SURVEY.md §5
    checkpoint/resume).
    """

    # Class-level default so _validates_task_ids is safe during __init__
    # replay on this class too (FollowerTaskStore overrides per instance
    # while absorbing).
    _absorbing = False

    def __init__(self, journal_path: str, publisher: Publisher | None = None,
                 compact_every: int = 5000, result_backend=None,
                 result_offload_threshold: int | None = None,
                 fsync: str | None = None, metrics=None):
        super().__init__(publisher, result_backend=result_backend,
                         result_offload_threshold=result_offload_threshold)
        from . import journal as journal_format
        from ..metrics import DEFAULT_REGISTRY
        self._journal_format = journal_format
        self._journal_path = journal_path
        self._journal = None  # gate journaling off during replay
        self._closed = False
        # Fsync policy (docs/durability.md): never (default — today's
        # write+flush behavior), always (fsync per append), group:<ms>
        # (batched group commit). None resolves AI4E_TASKSTORE_FSYNC;
        # a malformed value fails HERE, at construction.
        self._fsync_kind, self._fsync_group_s = (
            journal_format.parse_fsync_policy(fsync))
        self._fsync_last = 0.0
        self._fsync_timer = None        # pending group-commit timer
        self._fsync_dirty = False       # bytes flushed but not yet fsynced
        # Disk-fault degraded mode: set by _enter_degraded on EIO/ENOSPC;
        # every mutation refuses with JournalDegradedError until recover().
        self.degraded = False
        self.degraded_reason: str | None = None
        # Hash-chain head over this store's own journal file (journal.py):
        # two stores holding the same bytes hold the same head, so
        # divergence is a string comparison (topology/role endpoints).
        self.chain_head = journal_format.GENESIS
        # Blessed default-resolution idiom (AIL002): the assembly plumbs
        # its registry; standalone construction falls back in one visible
        # expression.
        metrics = metrics or DEFAULT_REGISTRY
        self._m_fsyncs = metrics.counter(
            "ai4e_journal_fsyncs_total",
            "Journal fsync calls, by fsync policy")
        self._m_appended = metrics.counter(
            "ai4e_journal_appended_bytes_total",
            "Bytes appended to task-store journals")
        self._m_salvages = metrics.counter(
            "ai4e_journal_salvages_total",
            "Torn journal tails truncated at open, by reason")
        self._m_verify_fail = metrics.counter(
            "ai4e_journal_verify_failures_total",
            "Journal records that failed checksum/chain verification")
        self._m_degraded = metrics.gauge(
            "ai4e_journal_degraded",
            "1 while the store refuses mutations after a journal disk "
            "fault (read-only degraded mode)")
        self._m_degraded_total = metrics.counter(
            "ai4e_journal_degraded_total",
            "Times a journal disk fault flipped the store to degraded "
            "mode, by errno name")
        self._m_compactions = metrics.counter(
            "ai4e_journal_compactions_total",
            "Journal compaction rewrites")
        self._m_append_s = metrics.histogram(
            "ai4e_journal_append_seconds",
            "Journal append wall time (write+flush+policy fsync)")
        # Instance-level stats for bench's `journal` result block — the
        # registry aggregates across stores; these stay per store.
        self._stat_bytes = 0
        self._stat_fsyncs = 0
        self._stat_compactions = 0
        self._stat_salvages = 0
        self._append_times: list[float] = []
        # Auto-compaction: status transitions append forever, so a
        # long-running store's journal (and restart replay time) would grow
        # without bound. Once ``compact_every`` records accumulate beyond
        # the live-task count, the journal is rewritten as one record per
        # task under the lock (atomic tmp+rename) — Redis AOF-rewrite's
        # role, sized so compaction cost amortizes to ~zero per write.
        self._compact_every = compact_every
        self._records = 0
        self._next_compact_at = compact_every
        # Bumped on every compaction rewrite: replication followers track
        # (generation, byte offset) into the journal file, and a rewrite
        # invalidates their offset — a generation mismatch tells them to
        # resync from offset 0 (the compacted journal IS the full state).
        self.journal_generation = 0
        # Split-brain fencing epoch (VERDICT r4 #3) — the monotonic counter
        # of the primary lineage this store's state belongs to. Minted +1 at
        # every promotion and journaled, so it survives restarts and a
        # re-promotion always exceeds every epoch this node has ever seen.
        # The single-writer property the reference bought from managed Redis
        # (RedisConnection.cs:12-38) made explicit: a primary that learns of
        # a higher epoch (client header, demote call, journal-stream probe)
        # self-demotes and refuses writes.
        self.epoch = 0
        self.replayed_task_ids: set[str] = set()
        if os.path.exists(journal_path):
            # Salvage BEFORE replay and before the append handle opens: a
            # torn final record (mid-write crash) is truncated to the last
            # complete verified record, so (a) replay can never crash-loop
            # on a torn tail and (b) the "a"-mode handle below can never
            # concatenate the next record onto torn bytes — the bug a
            # skip-only replay fix would leave behind. A corrupt INTERIOR
            # record raises loudly here with its offset instead
            # (journal.salvage; docs/durability.md).
            report = journal_format.salvage(journal_path)
            if report is not None:
                import logging
                logging.getLogger("ai4e_tpu.taskstore").warning(
                    "journal %s: salvaged torn tail — dropped %d bytes at "
                    "offset %d (%s); %d records kept, chain head %s "
                    "(report: %s.salvage.json)", journal_path,
                    report.dropped_bytes, report.truncated_at,
                    report.reason, report.records_kept, report.chain_head,
                    journal_path)
                self._m_salvages.inc(reason=report.reason)
                self._stat_salvages += 1
            self._replay()
            self.replayed_task_ids = set(self._tasks)
            # Same heuristic as runtime auto-compaction: only rewrite when
            # the journal is meaningfully bloated — a strictly-greater test
            # would rewrite (and fsync) the whole journal on nearly every
            # restart for a negligible win.
            if self._records > 2 * max(self._live_records(), 1):
                self._compact_locked()
        if self._journal is None:
            self._journal = open(journal_path, "a",  # noqa: SIM115
                                 encoding="utf-8")

    def _replay(self) -> None:
        # Salvage already verified the file end to end; the replay pass
        # re-verifies as it applies (cheap — CRC of control-plane-sized
        # records) so the chain head comes out of one code path.
        chain = self._journal_format.GENESIS
        with open(self._journal_path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec, chain, _legacy = self._journal_format.verify_line(
                    line, chain)
                self._records += 1
                self._apply_replay_record(rec)
        self.chain_head = chain

    def _apply_replay_record(self, rec: dict) -> "APITask | None":
        """Apply ONE journal record to in-memory state — the replay step,
        also the unit a replication follower applies per streamed line
        (``replication.py``). Journaling is gated off in both cases
        (``self._journal is None``), so applying never re-appends.

        Returns the transitioned task for Slim records (the follower must
        ``_notify`` its own long-poll waiters of replicated transitions —
        the full-upsert branch already notifies via ``upsert``); None
        otherwise."""
        if "Epoch" in rec:
            # Fencing-epoch marker (promotion mint or demotion fence): the
            # highest epoch ever seen must survive restarts so a later
            # promotion mints past it.
            self.epoch = max(self.epoch, int(rec["Epoch"]))
            return None
        if rec.get("Result"):
            # Result record: inline payload as hex, or an offloaded
            # pointer whose bytes are durable in the backend itself.
            if rec.get("Offloaded") and self._result_backend is None:
                # Fail FAST: replaying the pointer without a backend
                # would serve "completed, no result" — restore the
                # store's result_dir config instead.
                raise RuntimeError(
                    f"journal references offloaded result "
                    f"{rec['Key']!r} but no result backend is "
                    f"configured (set result_dir to the same mount "
                    f"it was written to)")
            body = (None if rec.get("Offloaded")
                    else bytes.fromhex(rec.get("ResultHex", "")))
            self._results[rec["Key"]] = (
                body, rec.get("ContentType", "application/json"))
            self._result_keys.setdefault(
                rec["Key"].split(":", 1)[0], set()).add(rec["Key"])
            return
        if rec.get("Evict"):
            # Journal is None during replay, so the subclass's
            # append is a no-op — this just forgets the task. Blob
            # deletes re-run too: a crash between the Evict append
            # and the original deletes leaked them; replay cleans up.
            # EXCEPT KeepBlobs records (rebalance forget): those blobs
            # belong to the shard that imported the range — deleting
            # them here would dangle the new owner's pointers.
            keys = self._apply_evict(rec["TaskId"])
            if not rec.get("KeepBlobs"):
                for key in keys:
                    self._delete_blob(key)
            return
        if rec.get("Slim"):
            # Transition record: body/orig state is untouched (they
            # ride only on upserts), exactly like the live mutation;
            # the journaled timestamp is kept so set scores replay
            # faithfully.
            prev = self._tasks.get(rec["TaskId"])
            if prev is None:
                return None  # compacted-away predecessor
            task = prev.with_status(rec["Status"],
                                    rec.get("BackendStatus"))
            task.publish = False
            task.timestamp = float(rec.get("Timestamp")
                                   or task.timestamp)
            self._remove_from_set(prev)
            self._tasks[task.task_id] = task
            self._add_to_set(task)
            return task
        task = APITask.from_dict(rec)
        task.body = bytes.fromhex(rec.get("BodyHex", ""))
        # Don't re-publish during replay — LocalPlatform.start()
        # re-seeds the broker from unfinished_tasks() afterwards.
        task.publish = False
        InMemoryTaskStore.upsert(self, task)
        # Keep the journaled timestamp (upsert stamps "now"):
        # set scores and the reaper's stuck-task age clock must
        # survive restarts, not reset to replay time.
        stored = self._tasks[task.task_id]
        ts = float(rec.get("Timestamp") or stored.timestamp)
        stored.timestamp = ts
        self._sets[(stored.endpoint_path,
                    stored.canonical_status)][stored.task_id] = ts
        orig = rec.get("OrigHex")
        if orig:
            self._orig_bodies[task.task_id] = (
                bytes.fromhex(orig),
                rec.get("OrigContentType", "application/json"))

    def _log(self, task: APITask, slim: bool = False) -> None:
        # Called with self._lock held (from _apply_*): journal order is
        # exactly mutation order, so replay reconstructs the true final state.
        if self._journal is None or not task.durable:
            return
        rec = task.to_dict()
        if slim:
            # Status transitions never change body/orig — journaling them
            # again would append the (hex-doubled) payload on EVERY
            # transition, ~8x the necessary bytes for a 4-transition task.
            rec["Slim"] = True
        else:
            rec = self._full_record(task)
        self._append(rec)

    def _append(self, rec: dict) -> None:
        # Called with self._lock held; shared by task and result records.
        if self._journal is None:
            return
        self._check_degraded()
        start = time.monotonic()
        line, chain = self._journal_format.encode_record(
            rec, self.chain_head)
        data = line + "\n"
        try:
            self._journal.write(data)
            self._journal.flush()
        except OSError as exc:
            # The record's bytes may be torn or absent on disk: flip to
            # degraded mode and tell the caller to unwind its in-memory
            # mutation (rollback=True) — the store must never acknowledge,
            # or remember, state the journal does not hold.
            raise self._enter_degraded(exc, "append") from exc
        self.chain_head = chain
        nbytes = len(data.encode("utf-8"))
        self._stat_bytes += nbytes
        self._m_appended.inc(nbytes)
        self._fsync_dirty = True
        if self._fsync_kind == "always":
            # Bytes reached the file before the fsync attempt: on failure
            # memory EQUALS the file, so the mutation stays (rollback=False)
            # — only the acknowledgment is refused (at-least-once residual,
            # docs/durability.md#fsync-policies).
            self._fsync_journal()
        elif self._fsync_kind == "group":
            self._group_commit()
        self._record_append_time(time.monotonic() - start)
        self._records += 1
        if (self._records >= self._next_compact_at
                and self._records > 2 * self._live_records()):
            # The append above flushed this mutation to the journal FILE
            # (durable against process death; durable against machine
            # crash only per the fsync policy — docs/durability.md); a
            # failed rewrite (disk full) must not surface as an error for
            # — or skip the notify/publish of — a transition that
            # succeeded. And it must not retry on the very next write (a
            # full O(tasks) rewrite per transition while the disk is
            # already under pressure): back off a full compaction interval
            # either way.
            import logging
            before = self._records
            try:
                self._compact_locked()
                logging.getLogger("ai4e_tpu.taskstore").info(
                    "journal compacted: %d -> %d records (generation %d)",
                    before, self._records, self.journal_generation)
            except OSError:
                logging.getLogger("ai4e_tpu.taskstore").exception(
                    "journal auto-compaction failed; continuing on the "
                    "append-only journal")
            self._next_compact_at = self._records + self._compact_every

    # -- disk-fault degraded mode + fsync policy (docs/durability.md) ------

    def _check_degraded(self) -> None:
        if self.degraded:
            raise JournalDegradedError(
                f"task store is journal-degraded ({self.degraded_reason}); "
                "mutations refused until recover()", rollback=False)

    def _enter_degraded(self, exc: OSError,
                        where: str) -> JournalDegradedError:
        """Flip to fenced read-only degraded mode on a journal disk fault.
        Returns the typed error for the caller to raise; idempotent for
        repeat faults. Reads keep serving; the HTTP surfaces answer
        mutations 503 + ``X-Shed-Reason: journal-degraded``."""
        import errno as errno_mod
        import logging
        name = errno_mod.errorcode.get(exc.errno or 0, "OSError")
        if not self.degraded:
            self.degraded = True
            self.degraded_reason = f"{name} on journal {where}: {exc}"
            self._m_degraded.set(1.0)
            self._m_degraded_total.inc(errno=name)
            logging.getLogger("ai4e_tpu.taskstore").error(
                "journal %s hit %s on %s; store is now DEGRADED "
                "(read-only) — mutations refuse with 503 "
                "journal-degraded until recover() "
                "(docs/durability.md#degraded-mode)",
                self._journal_path, name, where)
        return JournalDegradedError(
            self.degraded_reason or f"{name} on journal {where}",
            rollback=(where == "append"))

    def _fsync_journal(self) -> None:
        """Push flushed journal bytes to stable storage. Caller holds
        ``self._lock``. Raises JournalDegradedError(rollback=False) on
        EIO — the bytes are in the FILE, so memory stays; only the
        acknowledgment is refused."""
        fh = self._journal
        if fh is None or not self._fsync_dirty:
            return
        try:
            # FaultyFile (chaos/disk.py) exposes fsync(); real handles go
            # through os.fsync on the descriptor.
            sync = getattr(fh, "fsync", None)
            if sync is not None:
                sync()
            else:
                os.fsync(fh.fileno())
        except OSError as exc:
            raise self._enter_degraded(exc, "fsync") from exc
        self._fsync_dirty = False
        self._fsync_last = time.monotonic()
        self._stat_fsyncs += 1
        self._m_fsyncs.inc(policy=self._fsync_kind)

    def _group_commit(self) -> None:
        """group:<ms> policy: at most one fsync per window. An append that
        lands with the window already elapsed pays the fsync inline (the
        amortization point — the store lock serializes appends, so one
        fsync covers every record flushed since the last); otherwise a
        timer completes the window so an idle tail is synced within <ms>
        even when no further append arrives. Caller holds ``self._lock``."""
        now = time.monotonic()
        if now - self._fsync_last >= self._fsync_group_s:
            self._fsync_journal()
            return
        if self._fsync_timer is None:
            delay = max(self._fsync_group_s - (now - self._fsync_last),
                        0.001)
            t = threading.Timer(delay, self._timer_fsync)
            t.daemon = True
            self._fsync_timer = t
            t.start()

    def _timer_fsync(self) -> None:
        """Group-commit window completion (timer thread). A fault here
        flips degraded without raising — there is no caller to refuse;
        the appends inside the broken window are the policy's documented
        acknowledged-but-unsynced residual."""
        with self._lock:
            self._fsync_timer = None
            if self._closed or self.degraded or self._journal is None:
                return
            try:
                self._fsync_journal()
            except JournalDegradedError:
                pass  # _enter_degraded already logged + metered

    def _record_append_time(self, seconds: float) -> None:
        self._m_append_s.observe(seconds)
        self._append_times.append(seconds)
        if len(self._append_times) > 4096:
            # Keep the bench-window reservoir bounded; p99 over the most
            # recent half is plenty for the result block.
            del self._append_times[:2048]

    def recover(self) -> bool:
        """Operator/cycle hook: leave degraded mode once the disk is
        healthy again. Re-salvages the journal (the failed append may have
        left a torn tail on disk — exactly the shape boot-salvage
        repairs), reopens the append handle, probes an fsync, and
        re-admits mutations. Returns True when the store is writable on
        exit; False (still degraded) when the disk still faults."""
        with self._lock:
            if self._closed:
                return False
            if not self.degraded:
                return True
            # Discard the broken handle FIRST — before the scan, and
            # without flushing: its write buffer holds exactly the
            # rolled-back record's bytes, and an ordinary close() would
            # re-flush them onto the now-healthy file, resurrecting a
            # mutation the caller was told was refused and unwound
            # (review finding, regression-tested). Whatever partial bytes
            # the failed flush DID land are a torn tail the salvage scan
            # below truncates.
            # A FOLLOWER keeps its append handle in ``_raw`` with
            # ``_journal`` gated off (e.g. a promote() whose epoch mint
            # hit the disk fault and unwound): discard and reopen THAT
            # slot, or the broken buffered handle would survive recovery
            # while a fresh one lands in the wrong attribute.
            follower = getattr(self, "role", "primary") == "follower"
            if follower:
                old, self._raw = self._raw, None
            else:
                old, self._journal = self._journal, None
            if old is not None:
                self._close_discarding(old)
            try:
                scan = self._journal_format.scan_journal(self._journal_path)
                report = self._journal_format.salvage(
                    self._journal_path, scan)
                fh = open(self._journal_path, "a",  # noqa: SIM115
                          encoding="utf-8")
                os.fsync(fh.fileno())
            except (OSError, self._journal_format.JournalCorruptError):
                import logging
                logging.getLogger("ai4e_tpu.taskstore").exception(
                    "journal %s: recovery attempt failed; store stays "
                    "degraded", self._journal_path)
                return False
            if follower:
                self._raw = fh
            else:
                self._journal = fh
            if report is not None:
                # The salvage truncated bytes that were VISIBLE to
                # replication readers (a torn fragment streams like any
                # other bytes): a reader whose offset passed the verified
                # prefix would otherwise be served the middle of a fresh
                # record spliced onto its stale buffer — or report zero
                # lag while missing every post-recover write. The
                # generation bump is the system's one "file bytes
                # changed" signal (compaction's contract); readers
                # full-resync from offset 0 (review finding).
                self.journal_generation += 1
            self.chain_head = scan.chain_head
            self._records = scan.records
            self._fsync_dirty = False
            self.degraded = False
            self.degraded_reason = None
            self._m_degraded.set(0.0)
            import logging
            logging.getLogger("ai4e_tpu.taskstore").warning(
                "journal %s: recovered from degraded mode; mutations "
                "re-admitted at chain head %s", self._journal_path,
                self.chain_head)
            return True

    def journal_stats(self) -> dict:
        """The bench/ops summary block: append volume, fsync/compaction
        counts, and append p99 — docs/durability.md#observability."""
        with self._lock:
            times = sorted(self._append_times)
            p99 = times[int(len(times) * 0.99)] if times else 0.0
            return {
                "bytes_appended": self._stat_bytes,
                "fsyncs": self._stat_fsyncs,
                "compactions": self._stat_compactions,
                "salvages": self._stat_salvages,
                "fsync_policy": (self._fsync_kind
                                 if self._fsync_kind != "group" else
                                 f"group:{self._fsync_group_s * 1000:g}"),
                "append_p99_ms": round(p99 * 1000, 3),
                "degraded": self.degraded,
                "chain_head": self.chain_head,
            }

    def _compact_locked(self) -> None:
        """Rewrite the journal as one full record per live task (+ one per
        result). Caller holds ``self._lock`` (or is still single-threaded in
        __init__). Failure at ANY point leaves the store on a valid journal:
        the replacement file is fully written and its handle opened before
        the atomic rename, and the old handle is closed only after the swap
        succeeds."""
        tmp = self._journal_path + ".compact"
        new_journal = None
        # The rewrite restarts the hash chain from genesis: the compacted
        # file is a new byte lineage (followers already resync on the
        # generation bump; the chain head is per (generation, file)).
        chain = self._journal_format.GENESIS

        def emit(f, rec: dict) -> None:
            nonlocal chain
            line, chain = self._journal_format.encode_record(rec, chain)
            f.write(line + "\n")

        try:
            with open(tmp, "w", encoding="utf-8") as f:
                if self.epoch:
                    # The fencing epoch must survive the rewrite — it is
                    # state, not history.
                    emit(f, {"Epoch": self.epoch})
                for task in self._tasks.values():
                    if not task.durable:
                        # In-memory-only records (cache hits) must not be
                        # promoted to durability by a rewrite.
                        continue
                    emit(f, self._full_record(task))
                # Tasks first, then results — replay applies them in file
                # order and a result's task record must already exist.
                for key, (body, ctype) in self._results.items():
                    owner = self._tasks.get(key.split(":", 1)[0])
                    if owner is not None and not owner.durable:
                        continue
                    emit(f, self._result_record(key, body, ctype))
                f.flush()
                os.fsync(f.fileno())
            # Open the append handle on the tmp file BEFORE the rename: the
            # handle follows the inode, so after os.replace it IS the live
            # journal — no window where a failed reopen leaves a handle
            # pointing at an unlinked file.
            new_journal = open(tmp, "a", encoding="utf-8")  # noqa: SIM115
            os.replace(tmp, self._journal_path)  # atomic swap
        except OSError:
            if new_journal is not None:
                new_journal.close()
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        old = self._journal
        self._journal = new_journal
        self._records = (len(self._tasks) + len(self._results)
                         + (1 if self.epoch else 0))
        self.journal_generation += 1
        self.chain_head = chain
        # The rewrite was fsynced before the rename; nothing unsynced
        # survives from the old file's lineage.
        self._fsync_dirty = False
        self._stat_compactions += 1
        self._m_compactions.inc()
        if old is not None:
            old.close()

    def compact(self) -> None:
        """Force a journal rewrite (operational hook; auto-compaction covers
        normal operation)."""
        with self._lock:
            self._check_open()
            self._compact_locked()

    def _live_records(self) -> int:
        """Journal records a fully-compacted journal would hold — the
        bloat denominator for the compaction heuristics."""
        return len(self._tasks) + len(self._results)

    def _check_open(self) -> None:
        # Degraded refuses BEFORE any memory mutation, with the typed
        # error the HTTP surfaces map to 503 journal-degraded — reads
        # never come through here, so they keep serving.
        super()._check_open()
        if self.degraded:
            self._check_degraded()

    def _apply_set_result(self, key: str, result: bytes | None,
                          content_type: str) -> None:
        # Journal the result so a completed task survives restart WITH its
        # payload — without this a replayed task would report completed
        # while its result is gone (a worse lie than losing the task).
        # Append FIRST, mutate memory second: the base apply deletes a
        # superseded offload blob, which must never happen before the
        # record is known journaled — a degraded append after that delete
        # would roll back to a pointer whose blob is gone, making an
        # acknowledged result unreadable (review finding). Append-first
        # means a failed append leaves memory untouched: nothing to
        # unwind. Pre-validate what the apply would refuse so the journal
        # never holds a record memory rejected.
        self._check_open()
        tid = key.split(":", 1)[0]
        self._check_owner(tid)
        owner = self._tasks.get(tid)
        if owner is None or owner.durable:
            try:
                self._append(
                    self._result_record(key, result, content_type))
            except JournalDegradedError as exc:
                if not exc.rollback:
                    # Fsync-failure shape: the record's bytes ARE in the
                    # file (and on any replica that absorbs the stream).
                    # Apply the memory mutation so memory == file — the
                    # refused-but-possibly-durable at-least-once
                    # residual, the same contract upsert/update keep on
                    # rollback=False (review finding: append-first must
                    # not invert it). The unchecked core: the store is
                    # degraded NOW, so the checked apply would refuse a
                    # mutation whose record is already durable.
                    self._set_result_in_memory(key, result, content_type)
                raise
        # else: the owning record never reached the journal; its result
        # must not either (replay would otherwise restore an orphan
        # result).
        self._set_result_in_memory(key, result, content_type)

    def _apply_evict(self, task_id: str) -> list[str]:
        if task_id not in self._tasks:
            return []
        self._check_open()
        # Capture before the pop: a non-durable record was never journaled,
        # so journaling its eviction would only bloat the file. The rest of
        # the snapshot is the degraded-rollback undo — an eviction whose
        # Evict append fails with possibly-torn bytes must restore the
        # task wholesale, or memory forgets a task the journal still holds
        # (restart/replicas resurrect it) and a recovered retry no-ops
        # before ever journaling the eviction (review finding).
        task = self._tasks[task_id]
        durable = task.durable
        orig = self._orig_bodies.get(task_id)
        ledger = self._ledgers.get(task_id)
        keys = set(self._result_keys.get(task_id, ()))
        results = {key: self._results[key] for key in keys
                   if key in self._results}
        blob_keys = super()._apply_evict(task_id)
        if durable:
            rec = {"Evict": True, "TaskId": task_id}
            if self._forgetting:
                # Rebalance forget: the blobs moved WITH the range — a
                # replay of this record must not delete the new owner's
                # payloads out of the shared backend.
                rec["KeepBlobs"] = True
            try:
                self._append(rec)
            except JournalDegradedError as exc:
                if exc.rollback:
                    self._tasks[task_id] = task
                    self._add_to_set(task)
                    if orig is not None:
                        self._orig_bodies[task_id] = orig
                    if ledger is not None:
                        self._ledgers[task_id] = ledger
                    if keys:
                        self._result_keys[task_id] = keys
                        self._results.update(results)
                    raise
                # Fsync-failure shape: the Evict record IS in the file
                # and memory already forgot the task — the eviction is
                # complete, so fall through and surrender the blob keys.
                # Raising here would leak them forever: nothing
                # references the blobs anymore and the caller's delete
                # loop would never receive the keys (review finding).
                # The sweep's NEXT mutation refuses typed before
                # touching memory, so degradation still surfaces.
        return blob_keys

    def _apply_upsert(self, task: APITask) -> APITask:
        self._check_open()
        prev = self._tasks.get(task.task_id) if task.task_id else None
        had_orig = (task.task_id in self._orig_bodies
                    if task.task_id else False)
        prev_orig = (self._orig_bodies.get(task.task_id)
                     if had_orig else None)
        stored = super()._apply_upsert(task)
        try:
            self._log(stored)
        except JournalDegradedError as exc:
            if exc.rollback:
                self._rollback_upsert(stored, prev, had_orig, prev_orig)
            raise
        return stored

    def _rollback_upsert(self, stored: APITask, prev: APITask | None,
                         had_orig: bool,
                         prev_orig: tuple[bytes, str] | None) -> None:
        """Unwind ONE in-memory upsert whose journal append failed with
        possibly-torn bytes (degraded write path). Caller holds the lock."""
        self._remove_from_set(stored)
        if prev is None:
            self._tasks.pop(stored.task_id, None)
        else:
            self._tasks[prev.task_id] = prev
            self._add_to_set(prev)
        if had_orig:
            self._orig_bodies[stored.task_id] = prev_orig
        else:
            self._orig_bodies.pop(stored.task_id, None)

    def _apply_update(
        self, task_id: str, status: str, backend_status: str | None
    ) -> APITask:
        self._check_open()
        prev = self._tasks.get(task_id)
        task = super()._apply_update(task_id, status, backend_status)
        try:
            self._log(task, slim=True)
        except JournalDegradedError as exc:
            if exc.rollback and prev is not None:
                self._remove_from_set(task)
                self._tasks[task_id] = prev
                self._add_to_set(prev)
            raise
        return task

    def _validates_task_ids(self) -> bool:
        # Journal replay runs before the append handle opens
        # (``self._journal is None``) and follower absorb sets
        # ``_absorbing`` — both apply already-accepted history and must
        # never re-validate it (ADVICE r5: a legacy ':' TaskId would
        # crash-loop replay / wedge absorb forever).
        return self._journal is not None and not self._absorbing

    def _drain_fsync_on_close(self) -> None:
        """Cancel any pending group-commit timer and push the dirty tail
        down on a CLEAN close (a graceful shutdown should not owe the
        disk anything, whatever the policy). Caller holds ``self._lock``;
        best-effort — close must succeed on a faulting disk too."""
        timer, self._fsync_timer = self._fsync_timer, None
        if timer is not None:
            timer.cancel()
        if (self._fsync_kind != "never" and self._fsync_dirty
                and not self.degraded and self._journal is not None):
            try:
                self._fsync_journal()
            except JournalDegradedError:
                pass  # _enter_degraded logged it; close proceeds

    @staticmethod
    def _close_discarding(fh) -> None:
        """Close a DEGRADED journal handle WITHOUT flushing its buffer.

        After a rollback=True append failure the handle's write buffer
        holds exactly the refused record's unflushed bytes — an ordinary
        ``close()`` re-flushes them onto the (possibly healed) file,
        landing a mutation the caller was told was refused and unwound:
        a restart, a replica drain, or ``recover()`` would then resurrect
        it (review finding; regression-tested). The descriptor is
        atomically redirected onto ``os.devnull`` (dup2) BEFORE the
        close, so the close-time flush drains harmlessly there. NOT
        os.close()-then-close(): between those two calls another thread
        (a blob write, a sibling shard's open) can open a file that
        REUSES the freed descriptor number, and the close-time flush
        would splice the refused bytes into that unrelated file (review
        finding). Acknowledged records are never at risk — every
        successful append flushed."""
        try:
            fd = fh.fileno()
        except (OSError, ValueError):
            fd = None
        if fd is not None:
            try:
                devnull = os.open(os.devnull, os.O_WRONLY)
            except OSError:
                devnull = None
            if devnull is not None:
                try:
                    os.dup2(devnull, fd)
                except OSError:
                    pass
                finally:
                    os.close(devnull)
        try:
            fh.close()
        except (OSError, ValueError):
            pass

    def close(self) -> None:
        with self._lock:
            if not self._closed and self._journal is not None:
                self._drain_fsync_on_close()
                if self.degraded:
                    self._close_discarding(self._journal)
                else:
                    self._journal.close()
            self._closed = True


class FollowerTaskStore(JournaledTaskStore):
    """Replication follower — the control plane's availability story.

    The reference's task state lives in managed network Redis that any
    component reaches and Azure keeps available (``RedisConnection.cs:12-38``,
    ``deploy_cache_prerequisites.sh:15-31``). This store gives a second
    control-plane replica the same role: it tails the primary's journal
    stream (``replication.py`` pulls ``GET /v1/taskstore/journal``), applies
    each record to its own in-memory state, and appends the raw line to its
    own journal file — byte-compatible with the primary's, so a follower
    restart replays it with the ordinary ``JournaledTaskStore`` machinery.

    While ``role == "follower"`` every external mutation raises
    ``NotPrimaryError`` (the HTTP surface maps it to 503 so store clients
    fail over to the primary); reads — task polls, results, depths — are
    served locally, which also offloads read traffic from the primary.
    ``promote()`` flips it to a live primary: the raw-append handle becomes
    the journal and writes flow.
    """

    # Class-level defaults so the write fence is a no-op while
    # super().__init__ replays the local journal (instance attrs land after).
    role = "primary"
    _absorbing = False
    # The PRIMARY's chain head as verified off the absorbed stream — the
    # value divergence checks compare against the primary's own
    # ``chain_head``. None = unanchored (fresh boot / legacy stream):
    # checksums still verify, the first enveloped line's chain is adopted.
    # Distinct from ``chain_head``, which tracks this replica's OWN file
    # (whose leading epoch line from ``reset`` makes its byte lineage —
    # legitimately — different from the primary's).
    _absorb_chain: str | None = None

    def __init__(self, journal_path: str, start_as_primary: bool = False,
                 **kwargs):
        super().__init__(journal_path, **kwargs)
        self._absorbing = False
        if start_as_primary:
            # Born primary (an HA deployment's active node): behaves exactly
            # like a JournaledTaskStore, plus the demote()/note_epoch()
            # fence so a promoted standby can depose it (VERDICT r4 #3).
            # No epoch is minted — boot is not a failover.
            self._raw = None
            self.role = "primary"
        else:
            # Demote: keep the append handle for raw absorbed lines, but
            # gate self-journaling off (absorbed records are appended
            # verbatim; the _log path must not double-write them).
            self._raw = self._journal
            self._journal = None
            self.role = "follower"

    # -- replication feed ---------------------------------------------------

    def _write_own_line(self, fh, rec: dict) -> None:
        """Append one record to this replica's OWN journal, enveloped
        against its own chain — so the local file is self-consistent for
        its own restart salvage/replay (its byte lineage legitimately
        differs from the primary's by the ``reset`` epoch line). Caller
        holds ``self._lock``; caller flushes."""
        line, self.chain_head = self._journal_format.encode_record(
            rec, self.chain_head)
        fh.write(line + "\n")

    @property
    def replica_chain_head(self) -> str | None:
        """The primary-stream chain head this replica has verified up to —
        compare with the primary's ``chain_head`` for divergence (None
        until the first enveloped line anchors it)."""
        return self._absorb_chain

    def absorb_lines(self, lines: list[str]) -> None:
        """Apply journal lines streamed from the primary and append them
        to the local journal (one flush per call, not per line).
        Replicated Slim transitions notify this replica's own listeners
        (gateway long-poll waiters on the standby must wake when a task
        completes on the primary); full upserts already notify inside
        ``upsert``.

        Every line is checksum- and chain-verified BEFORE anything
        applies: a corrupt streamed line must never absorb silently (it
        would poison this replica with bytes the primary never wrote, or
        ratify the primary's own bit-rot). The verified prefix is applied
        and kept; the bad line and everything after it raise
        ``JournalCorruptError`` — the HTTP replicator answers with a full
        generation-style resync, the in-process shard link parks loudly
        at the offset (``sharding.ShardReplicaLink``). Legacy
        checksum-less lines absorb verbatim for migration."""
        transitions: list[APITask] = []
        error = None
        with self._lock:
            if self.role != "follower":
                raise RuntimeError("absorb after promote — replication "
                                   "must stop when the follower becomes "
                                   "primary")
            self._check_open()
            verified: list[dict] = []
            chain = self._absorb_chain
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec, chain, _legacy = (
                        self._journal_format.verify_line(line, chain))
                except self._journal_format.JournalCorruptError as exc:
                    self._m_verify_fail.inc()
                    error = exc
                    break
                verified.append(rec)
            self._absorbing = True
            try:
                for rec in verified:
                    task = self._apply_replay_record(rec)
                    if task is not None:
                        transitions.append(task)
                    self._write_own_line(self._raw, rec)
                    self._records += 1
            finally:
                self._absorbing = False
            self._raw.flush()
            self._absorb_chain = chain
        for task in transitions:
            self._notify(task)
        if error is not None:
            raise error

    def reset(self) -> None:
        """Discard all replicated state — the primary compacted (journal
        generation changed), so the follower resyncs from offset 0 of the
        rewritten file, which is a full state snapshot."""
        with self._lock:
            if self.role != "follower":
                # Same fence as absorb_lines: a replicator that kept running
                # past a promotion (e.g. the HTTP /promote path racing a
                # poll) must never wipe the newly-promoted primary.
                raise RuntimeError("reset after promote — replication must "
                                   "stop when the follower becomes primary")
            self._check_open()
            self._tasks.clear()
            self._orig_bodies.clear()
            self._results.clear()
            self._result_keys.clear()
            self._sets.clear()
            self._records = 0
            self._raw.close()
            self._raw = open(self._journal_path, "w",  # noqa: SIM115
                             encoding="utf-8")
            # Fresh file, fresh lineages: our own chain restarts at
            # genesis, and the absorbed stream restarts at the primary's
            # genesis (the resync re-reads its file from offset 0).
            self.chain_head = self._journal_format.GENESIS
            self._absorb_chain = self._journal_format.GENESIS
            if self.epoch:
                # The fencing epoch survives the truncation: a crash before
                # the absorbed stream re-delivers the primary's epoch record
                # must not replay this node back to an unfenced epoch 0.
                self._write_own_line(self._raw, {"Epoch": self.epoch})
                self._raw.flush()
                self._records = 1

    def promote(self) -> None:
        """Become the primary: accept writes, journal them normally. The
        caller must stop the replication feed first (``absorb_lines``
        refuses afterwards) and re-seed its transport from
        ``unfinished_tasks()`` — exactly what a restarted platform does.

        Mints the next fencing epoch and journals it: this store's writes
        now belong to a lineage strictly newer than anything the deposed
        primary can claim, and the mint survives restarts (so no two
        promotions ever share an epoch)."""
        with self._lock:
            if self.role == "primary":
                return
            self.role = "primary"
            self._journal = self._raw
            self.epoch += 1
            try:
                self._append({"Epoch": self.epoch})
            except JournalDegradedError as exc:
                if exc.rollback:
                    # The mint never reached the file: unwind WHOLESALE.
                    # A half-promoted store would hold a memory-only
                    # epoch a restart replays away — a later promotion
                    # could then re-mint an epoch this lineage already
                    # claimed, breaking the no-two-promotions-share-an-
                    # epoch fencing guarantee (review finding). Unwound,
                    # the store is an intact (degraded) follower; after
                    # recover() a retried promote() re-mints cleanly.
                    self.epoch -= 1
                    self._journal = None
                    self.role = "follower"
                    raise
                # Fsync-failure shape: the Epoch record IS in the file —
                # the promotion is durable and complete. Swallow: the
                # store is primary and degraded; every subsequent
                # mutation refuses with the typed error anyway.

    def demote(self, epoch: int) -> None:
        """Fence this node out of the primary role: a peer presented
        evidence of a strictly newer primary lineage (``epoch`` greater
        than ours). Writes refuse with ``NotPrimaryError`` from the moment
        this returns; reads stay served. Raises ``StaleEpochError`` when
        the presented epoch is not newer — the CALLER is the stale side
        and must not depose us. Idempotent for an already-demoted node."""
        with self._lock:
            self._check_open()
            if self.role == "follower":
                self.epoch = max(self.epoch, epoch)
                return
            if epoch <= self.epoch:
                raise StaleEpochError(
                    f"demotion epoch {epoch} is not newer than ours "
                    f"({self.epoch}); refusing")
            self.epoch = epoch
            self.role = "follower"
            self._raw = self._journal
            self._journal = None
            # Record the fence so a restart replays epoch >= this value: a
            # rebooted deposed primary can never re-mint an epoch the new
            # primary already holds.
            self._write_own_line(self._raw, {"Epoch": epoch})
            self._raw.flush()
            self._records += 1

    # Whether PASSIVE fencing evidence (X-Store-Epoch request headers, a
    # journal-stream probe's epoch param) may demote this node. True by
    # default — a FollowerTaskStore exists for HA; the platform sets it
    # False on a born-primary with NO configured HA peer, so a solo
    # deployment can never be written out of service by a forged or stale
    # epoch header (there is no standby to take over). The explicit
    # /demote endpoint is unaffected — it is an operator/prober action.
    passive_fencing = True

    # Plausibility bound on PASSIVE fencing evidence (ADVICE r5 #2): an
    # unauthenticated X-Store-Epoch header may only demote us when it is
    # within this many epochs of our own. Epochs advance by 1 per promotion,
    # so a legitimate peer can realistically be at most a few ahead; a
    # forged huge epoch would otherwise be ADOPTED as our own, propagate via
    # honest clients' echoes, and depose the newly-promoted standby too — a
    # one-request total write outage. Evidence beyond the bound is ignored
    # (logged); genuinely large jumps go through the authenticated /demote
    # path, which stays unbounded.
    PASSIVE_EPOCH_BOUND = 8

    def note_epoch(self, epoch: int) -> None:
        """Ingest fencing evidence carried by ordinary traffic (the
        ``X-Store-Epoch`` request header, a journal-stream probe's epoch
        param): a higher epoch means a newer primary exists somewhere —
        self-demote before touching state. Cheap no-op on every request
        where the epoch is not newer (the steady state). Evidence more than
        ``PASSIVE_EPOCH_BOUND`` ahead of our own epoch is implausible from
        an honest peer and is ignored (see the bound's comment)."""
        if not self.passive_fencing:
            return
        if epoch > self.epoch + self.PASSIVE_EPOCH_BOUND:
            import logging
            logging.getLogger("ai4e_tpu.taskstore").warning(
                "ignoring implausible passive fencing epoch %d (ours is %d, "
                "bound +%d); use the authenticated /demote path if this is "
                "a real failover", epoch, self.epoch,
                self.PASSIVE_EPOCH_BOUND)
            return
        if epoch > self.epoch and self.role == "primary":
            try:
                self.demote(epoch)
            except StaleEpochError:
                pass  # raced with a concurrent demotion to a higher epoch

    # -- follower write fence ----------------------------------------------

    def _check_writable(self) -> None:
        if self.role == "follower" and not self._absorbing:
            raise NotPrimaryError(
                "store replica is a follower; writes go to the primary")

    def _apply_upsert(self, task: APITask) -> APITask:
        self._check_writable()
        return super()._apply_upsert(task)

    def _apply_update(self, task_id: str, status: str,
                      backend_status: str | None) -> APITask:
        self._check_writable()
        return super()._apply_update(task_id, status, backend_status)

    def _apply_set_result(self, key: str, result: bytes | None,
                          content_type: str) -> None:
        self._check_writable()
        super()._apply_set_result(key, result, content_type)

    def _apply_evict(self, task_id: str) -> list[str]:
        self._check_writable()
        return super()._apply_evict(task_id)

    def close(self) -> None:
        with self._lock:
            if not self._closed:
                if self.role == "follower" and self._raw is not None:
                    timer, self._fsync_timer = self._fsync_timer, None
                    if timer is not None:
                        timer.cancel()
                    self._raw.close()
                elif self._journal is not None:
                    self._drain_fsync_on_close()
                    if self.degraded:
                        self._close_discarding(self._journal)
                    else:
                        self._journal.close()
            self._closed = True
