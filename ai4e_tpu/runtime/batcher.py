"""Micro-batcher — packs queued requests into dense fixed-shape TPU batches.

THE architectural divergence from the reference (SURVEY.md §7 hard parts #1):
the reference dispatches one task per HTTP POST to a GPU container; a TPU mesh
wants large dense batches. The batcher sits between the request path and the
device:

- requests arrive one at a time (``submit`` returns a future);
- a flusher drains the pending queue whenever the device is free, taking up to
  ``max_bucket`` examples — under load the batch grows toward the biggest
  bucket (adaptive batching), idle requests leave at batch 1 with
  ``max_wait_ms`` bounding added latency;
- the batch is padded to the smallest compiled bucket (no recompiles, static
  shapes) and run on the mesh via a single executor thread (one TPU program
  at a time — the device is the serial resource);
- outputs fan back out to per-request futures; per-example postprocess errors
  fail only that request (failure isolation: one bad image fails one task,
  never the batch).

Backpressure: ``pending_count`` over ``max_pending`` → ``submit`` raises
``BatcherSaturated`` and the service returns 503, which the dispatcher already
treats as backpressure — the queue-depth-vs-device-utilisation translation of
the reference's per-replica thread cap (SURVEY.md §7 hard part #2).
"""

from __future__ import annotations

import asyncio
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from ..metrics import DEFAULT_REGISTRY, MetricsRegistry
from ..observability.tracing import device_trace
from ..rollout.drain import DrainingError, retire_pending
from .ladder import EXPOSITION_BUCKETS, exposition_buckets
from .registry import ModelRuntime

log = logging.getLogger("ai4e_tpu.batcher")


class BatcherSaturated(RuntimeError):
    pass


@dataclass
class _Pending:
    example: np.ndarray
    future: asyncio.Future
    enqueued: float = field(default_factory=time.perf_counter)
    priority: int = 0  # 0 = interactive, higher = background
    # Absolute wall-clock deadline (unix seconds; 0.0 = none): an entry
    # still pending when it passes is dropped at batch-cut time with
    # DeadlineExceeded instead of being padded onto the device
    # (admission/ — dead work never reaches the TPU).
    deadline_at: float = 0.0
    # Hop-ledger buffer (observability/ledger.HopLedger) the worker
    # passed with the request; the batcher stamps batch-cut and device
    # phases into it. None = no stamping (the default).
    ledger: object = None


class MicroBatcher:
    def __init__(
        self,
        runtime: ModelRuntime,
        max_wait_ms: float = 5.0,
        max_pending: int = 256,
        metrics: MetricsRegistry | None = None,
        pipeline_depth: int = 2,
        interactive_reserve: float = 0.25,
        priority_aging_s: float = 2.0,
        measure_phases: bool = False,
        ladder_manager=None,
        double_buffer: bool = False,
    ):
        self.runtime = runtime
        self.max_wait = max_wait_ms / 1000.0
        self.max_pending = max_pending
        # Priority isolation is enforced at BOTH gates:
        # - admission: background submits saturate at (1 - reserve) of the
        #   queue, so stacks can never eat the whole cap and 503 interactive
        #   traffic out of the batcher;
        # - batch cut: interactive-first, but a background item's effective
        #   priority decays by 1 class per ``priority_aging_s`` waited, so
        #   sustained interactive load delays stacks boundedly instead of
        #   starving them (0 disables aging → strict priority).
        self._background_cap = max(1, int(max_pending
                                          * (1.0 - interactive_reserve)))
        self.priority_aging_s = priority_aging_s
        self.metrics = metrics or DEFAULT_REGISTRY
        self._pending: dict[str, list[_Pending]] = {}
        self._wakeup: asyncio.Event = asyncio.Event()
        self._stop = False
        # Rollout drain (rollout/drain.py, docs/deployment.md#drain):
        # while draining, submits raise DrainingError (the worker answers
        # 503 + Retry-After + X-Draining and async tasks redeliver through
        # the broker), the flusher stops cutting new batches, and batches
        # already on the device finish normally.
        self._draining = False
        self._flusher: asyncio.Task | None = None
        # ``pipeline_depth`` device-feeding threads + an equal-slot window:
        # the device still serialises compute, but batch N+1's host work
        # (padding, dispatch, result transfer) overlaps batch N's device time
        # instead of waiting on its device_get. Depth 2 is double
        # buffering; deeper windows are not re-measured on a local chip.
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        self.pipeline_depth = pipeline_depth
        self._executor = ThreadPoolExecutor(max_workers=pipeline_depth,
                                            thread_name_prefix="tpu-batcher")
        self._window = asyncio.Semaphore(pipeline_depth)
        self._inflight_execs: set[asyncio.Task] = set()
        # Traffic-tuned ladders (runtime/ladder.py, AI4E_RUNTIME_LADDER_
        # DERIVE): the manager sees every batch cut and re-derives each
        # servable's bucket ladder in the background. None (default) =
        # static factory ladders, no observation overhead.
        self._ladders = ladder_manager
        # With derivation on, the ai4e_batch_size exposition buckets are
        # built from the servables' OWN ladders at construction (the
        # static copy would drift the moment ladders are derived); with
        # it off they stay the static exposition ladder so the default
        # /metrics content is byte-identical to the pre-derivation
        # platform. Register AFTER all models so the union is complete.
        expo = (exposition_buckets(runtime.models.values())
                if ladder_manager is not None else EXPOSITION_BUCKETS)
        self._batch_size_hist = self.metrics.histogram(
            "ai4e_batch_size", "Executed batch sizes",
            buckets=(*expo, float("inf")))
        self._batch_latency = self.metrics.histogram(
            "ai4e_batch_exec_seconds", "Device execution time per batch")
        self._queue_wait = self.metrics.histogram(
            "ai4e_batch_queue_wait_seconds", "Request wait before batching")
        self._pending_gauge = self.metrics.gauge(
            "ai4e_batcher_pending", "Requests waiting for a batch slot")
        self._inflight_gauge = self.metrics.gauge(
            "ai4e_batcher_inflight_batches",
            "Device batches currently in the pipeline window")
        # Link accounting: actual bytes shipped host→device per executed
        # batch (bucket-padded input) and device→host (fetched outputs),
        # reported per-request by the bench.
        self._h2d_bytes = self.metrics.counter(
            "ai4e_batch_h2d_bytes_total",
            "Host-to-device bytes shipped (padded batches)")
        self._d2h_bytes = self.metrics.counter(
            "ai4e_batch_d2h_bytes_total",
            "Device-to-host bytes fetched (batch outputs)")
        # Deadline drops at the batch cut (admission/): same series every
        # other hop reports into, labeled with THIS hop.
        self._expired_total = self.metrics.counter(
            "ai4e_admission_expired_total",
            "Requests dropped on deadline expiry, by hop/priority")
        # Device-phase decomposition (observability/, ROADMAP item 2's
        # overlap metric): off by default — the batch path and /metrics
        # content are byte-identical until AI4E_OBSERVABILITY_HOP_LEDGER
        # turns it on. When on, batches run through the runtime's
        # run_batch_phases (measured h2d / compile-or-execute / d2h),
        # each phase lands in its histogram, and the h2d seconds spent
        # while ANOTHER batch was executing accumulate into the overlap
        # counter — overlap ratio ≈ how well transfers hide under
        # compute (1.0 = fully hidden, the double-buffering goal).
        self.measure_phases = measure_phases
        if measure_phases:
            import threading
            self._phase_hist = self.metrics.histogram(
                "ai4e_device_phase_seconds",
                "Device-boundary phase durations (h2d/compile/execute/"
                "d2h) per batch")
            self._overlap_total = self.metrics.counter(
                "ai4e_batch_h2d_overlap_seconds_total",
                "H2D transfer seconds that overlapped another batch's "
                "execute phase")
            self._overlap_ratio = self.metrics.gauge(
                "ai4e_batch_overlap_ratio",
                "Cumulative h2d/execute overlap ratio (overlapped h2d "
                "seconds / total h2d seconds)")
            self._phase_lock = threading.Lock()
            # Completed execute windows (start, end) + in-flight batch
            # starts — the overlap denominator's counterparty. In-flight
            # windows are approximated from the batch's call start (the
            # exact execute start is known only at completion), which
            # slightly over-counts overlap; documented in
            # docs/observability.md.
            from collections import deque as _deque
            self._exec_windows = _deque(maxlen=64)
            self._exec_pending: dict[int, float] = {}
            self._h2d_seconds = 0.0
            self._h2d_overlap_seconds = 0.0
        # Pad-waste accounting (ai4e_batch_pad_ratio / _pad_bytes_total):
        # the measurement that justifies — and regression-guards — ladder
        # derivation (docs/METRICS.md). Gated with the device-phase /
        # ladder instruments so the default batcher's /metrics stays
        # byte-identical to the pre-ladder platform.
        self._pad_enabled = measure_phases or ladder_manager is not None
        if self._pad_enabled:
            self._pad_state: dict[str, list[int]] = {}
            self._pad_ratio = self.metrics.gauge(
                "ai4e_batch_pad_ratio",
                "Cumulative padded-slots / occupied-slots per model "
                "(0 = every executed batch exactly filled its bucket)")
            self._pad_bytes = self.metrics.counter(
                "ai4e_batch_pad_bytes_total",
                "Host-to-device bytes spent on bucket padding, per model")
        # Double-buffered transfer pipeline (docs/device_path.md#double-
        # buffered-transfers, AI4E_RUNTIME_BATCH_DOUBLE_BUFFER): h2d,
        # execute, and d2h run on separate single-thread executors with
        # an alternating host staging-buffer ring, so batch N+1's
        # device_put overlaps batch N's execute and batch N's device_get
        # overlaps batch N+1's execute — the PR 8 overlap ratio's reason
        # to be > 0. Requires a runtime exposing the split-phase surface
        # (single-host ModelRuntime); otherwise the fused path serves.
        self._double = bool(
            double_buffer
            and getattr(runtime, "supports_split_phases", None) is not None
            and runtime.supports_split_phases())
        if self._double:
            self._h2d_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-h2d")
            self._exec_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-exec")
            self._d2h_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-d2h")
            # Host staging ring per (model, bucket): pipeline_depth
            # buffers cycling, so batch N+1 pads into a fresh buffer
            # while batch N's is still device-bound; the window
            # semaphore bounds in-flight batches at pipeline_depth, so
            # a buffer is never reused before its h2d completed.
            self._staging: dict[tuple[str, int], list] = {}
            self._staging_idx: dict[tuple[str, int], int] = {}

    # -- request side ------------------------------------------------------

    @property
    def pending_count(self) -> int:
        return sum(len(v) for v in self._pending.values())

    async def submit(self, model_name: str, example: np.ndarray,
                     priority: int = 0, deadline_at: float = 0.0,
                     ledger=None):
        """Queue one example; resolves to that example's postprocessed result.

        ``priority`` 0 is interactive (default); higher values are
        background classes (the batch API submits at 1). Every device batch
        is filled interactive-first, so a long background stack shares the
        device without queueing ahead of interactive latency — the
        isolation the reference gets only from separate container pools.

        ``deadline_at`` (absolute unix seconds; 0.0 = none): if the entry
        is still pending when the deadline passes, the await raises
        ``DeadlineExceeded`` at the next batch cut and the example never
        ships to the device (admission/).

        ``ledger`` (optional ``observability.ledger.HopLedger``): the
        batch cut and the device phases this example rides are stamped
        into it (``batched``/``h2d``/``execute``/``d2h``) — the worker
        flushes the buffer to the task store when the request finishes.
        """
        if self._stop:
            raise RuntimeError("batcher stopped")
        if self._draining:
            raise DrainingError("batcher draining; submit refused")
        cap = self.max_pending if priority <= 0 else self._background_cap
        if self.pending_count >= cap:
            raise BatcherSaturated(
                f"batcher at {self.pending_count}/{cap} pending "
                f"(priority {priority})")
        servable = self.runtime.models[model_name]
        expected = tuple(servable.input_shape)
        if tuple(example.shape) != expected:
            raise ValueError(
                f"bad input shape {example.shape}, expected {expected}")
        fut = asyncio.get_running_loop().create_future()
        self._pending.setdefault(model_name, []).append(
            _Pending(example, fut, priority=priority,
                     deadline_at=deadline_at, ledger=ledger))
        self._pending_gauge.set(self.pending_count)
        self._wakeup.set()
        return await fut

    # -- drain (rollout/drain.py drives these; docs/deployment.md) ---------

    def begin_drain(self) -> int:
        """Stop cutting new batches and retire every UNCUT pending entry
        with ``DrainingError`` (each redelivers through the broker per
        task). The take-and-clear is one synchronous step with the
        draining flip — no await — so a concurrently scheduled batch cut
        can never deliver into a future this sweep already failed
        (tests/test_race_regressions.py). Batches already in the pipeline
        window finish normally; ``drain_complete`` turns true when they
        have."""
        self._draining = True
        retired = retire_pending(self._pending)
        self._pending_gauge.set(self.pending_count)
        self._wakeup.set()
        return retired

    @property
    def drain_complete(self) -> bool:
        """Draining AND quiesced: nothing pending, nothing on the device."""
        return (self._draining and not self._inflight_execs
                and self.pending_count == 0)

    def resume_from_drain(self) -> None:
        """Re-arm after an aborted drain (the rollback path re-weights a
        worker back into service without a process restart)."""
        self._draining = False
        self._wakeup.set()

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        self._stop = False
        self._flusher = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        self._stop = True
        self._wakeup.set()
        if self._flusher is not None:
            await self._flusher
        if self._inflight_execs:
            await asyncio.gather(*self._inflight_execs,
                                 return_exceptions=True)
        self._executor.shutdown(wait=True)
        if self._double:
            for pool in (self._h2d_pool, self._exec_pool, self._d2h_pool):
                pool.shutdown(wait=True)

    # -- flusher -----------------------------------------------------------

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while not self._stop:
            if self.pending_count == 0:
                self._wakeup.clear()
                try:
                    await asyncio.wait_for(self._wakeup.wait(), timeout=0.5)
                except asyncio.TimeoutError:
                    continue
            # Brief PER-MODEL accumulation window: a model is cut when
            # ITS OWN largest bucket is full or ITS OWN oldest entry has
            # waited max_wait; until some model is ready, sleep to the
            # nearest per-model deadline. (The old global gate anchored
            # one shared window on the oldest pending anywhere and
            # compared the longest queue against the GLOBALLY largest
            # bucket — one model's ladder deciding another's cut, the
            # cross-model coupling per-model derived ladders cannot
            # tolerate.)
            if self.max_wait > 0:
                sleep_for = self._nearest_cut_deadline(time.perf_counter())
                if sleep_for is not None and sleep_for > 0:
                    await asyncio.sleep(sleep_for)
            now = time.perf_counter()
            if self._draining:
                # Drained pending queues are already empty; anything that
                # raced in between the retire sweep and the submit-side
                # refusal is retired here rather than cut to the device.
                retire_pending(self._pending)
                self._pending_gauge.set(self.pending_count)
                continue
            for model_name in list(self._pending):
                if not self._pending.get(model_name):
                    continue
                if not self._cut_ready(model_name, now):
                    continue  # still accumulating its own window
                # Acquire the window slot BEFORE carving the batch: while all
                # slots are busy, arriving requests keep joining the pending
                # queue, so the batch cut the moment a slot frees is as full
                # as possible (cutting first would freeze the batch at
                # whatever had arrived, then let it stale-wait).
                await self._window.acquire()
                with device_trace("ai4e.batch.cut", model=model_name):
                    batch, bucket = self._take_batch(model_name)
                if not batch:
                    self._window.release()
                    continue
                # Bounded pipelining: admit the batch and keep draining —
                # don't wait for its results.
                task = loop.create_task(
                    self._execute(loop, model_name, batch, bucket))
                self._inflight_execs.add(task)
                self._inflight_gauge.set(len(self._inflight_execs))

                def _done(t: asyncio.Task) -> None:
                    self._inflight_execs.discard(t)
                    self._inflight_gauge.set(len(self._inflight_execs))
                    self._window.release()

                task.add_done_callback(_done)

    def _cut_ready(self, model_name: str, now: float) -> bool:
        """This model's cut decision, against ITS OWN ladder only: full
        largest bucket, or its oldest pending entry has waited out the
        accumulation window (max_wait == 0 is always ready)."""
        queue = self._pending.get(model_name)
        if not queue:
            return False
        servable = self.runtime.models.get(model_name)
        if servable is not None and len(queue) >= servable.max_bucket:
            return True
        return (self.max_wait <= 0
                or now - queue[0].enqueued >= self.max_wait)

    def _nearest_cut_deadline(self, now: float) -> float | None:
        """Seconds until the FIRST model becomes cut-ready: 0.0 when one
        already is (full bucket or expired window), the smallest
        remaining per-model window otherwise, None with nothing
        pending."""
        nearest: float | None = None
        for name, queue in self._pending.items():
            if not queue:
                continue
            if self._cut_ready(name, now):
                return 0.0
            remaining = self.max_wait - (now - queue[0].enqueued)
            nearest = (remaining if nearest is None
                       else min(nearest, remaining))
        return nearest

    def _take_batch(self, model_name: str
                    ) -> tuple[list[_Pending], int]:
        """Cut one batch and choose its bucket from ONE snapshot of the
        servable's ladder. Returns ``(batch, bucket)`` — the bucket is
        decided HERE, not in ``_execute``: a deriver-thread ladder swap
        between the cut and the execute would otherwise let
        ``bucket_for(n)`` clamp to a new, smaller top bucket than the
        cut itself (IndexError mid-padding, every future in the batch
        stranded). A bucket chosen from the pre-swap tuple stays safe on
        either side of a swap — old-ladder programs are never evicted
        (``_executed_shapes`` is append-only)."""
        queue = self._pending.get(model_name, [])
        if not queue:
            return [], 0
        queue = self._sweep_expired(model_name, queue)
        if not queue:
            return [], 0
        servable = self.runtime.models[model_name]
        ladder = tuple(servable.batch_buckets)  # single read vs the swap
        if self._ladders is not None:
            # Feed the PRE-clamp demand to the ladder deriver — O(1)
            # histogram update; derivation/compile runs on its own
            # thread. Observing the post-clamp cut size would let the
            # ladder only ever ratchet DOWN: once a swap shrinks the top
            # bucket, every cut is capped at it and the histogram could
            # never witness the larger demand that should grow the
            # ladder back (the manager clamps to the FACTORY ladder's
            # max — the operator's memory bound).
            self._ladders.observe_cut(model_name, len(queue))
        take = min(len(queue), ladder[-1])
        if take < len(queue):
            # Cut interactive-first: a background stack never queues ahead
            # of fresh interactive requests when the batch can't hold
            # everyone — but waiting decays a class per priority_aging_s so
            # nothing starves. Within a class the aged key preserves
            # oldest-first. Full drains skip the sort.
            now = time.perf_counter()
            aging = self.priority_aging_s

            def effective(p: _Pending) -> float:
                if aging <= 0:
                    return float(p.priority)
                return p.priority - (now - p.enqueued) / aging

            queue = sorted(queue, key=effective)
        batch, rest = queue[:take], queue[take:]
        self._pending[model_name] = rest
        self._pending_gauge.set(self.pending_count)
        bucket = next((b for b in ladder if b >= take), ladder[-1])
        return batch, bucket

    def _sweep_expired(self, model_name: str,
                       queue: list[_Pending]) -> list[_Pending]:
        """Drop pending entries whose deadline passed while they queued —
        at the batch cut, the last gate before the device (admission/: zero
        expired examples ever reach ``_execute``). Their futures resolve to
        ``DeadlineExceeded`` so the worker can move the task to the
        terminal ``expired`` status. Deadline-free entries pass untouched;
        the all-deadline-free fast path allocates nothing."""
        now = time.time()
        if not any(p.deadline_at and p.deadline_at <= now for p in queue):
            return queue
        from ..admission.deadline import DeadlineExceeded, priority_name
        live: list[_Pending] = []
        for p in queue:
            if (p.deadline_at and p.deadline_at <= now
                    and not p.future.done()):
                p.future.set_exception(
                    DeadlineExceeded("batcher", p.deadline_at))
                self._expired_total.inc(hop="batcher",
                                        priority=priority_name(p.priority))
            else:
                live.append(p)
        self._pending[model_name] = live
        self._pending_gauge.set(self.pending_count)
        return live

    def _note_phases(self, model_name: str, t_call: float,
                     phases: dict, batch: list[_Pending]) -> None:
        """Account one FUSED-path phased batch (``run_batch_phases``
        measures durations, not wall windows): reconstruct back-to-back
        windows from the call start and delegate. The double-buffered
        path calls ``_note_phase_windows`` directly with the real,
        possibly gapped, per-stage windows."""
        windows: dict[str, tuple[float, float]] = {}
        cursor = t_call
        for phase in ("h2d", "compile", "execute", "d2h"):
            dur = phases.get(phase)
            if dur is None:
                continue
            windows[phase] = (cursor, cursor + dur)
            cursor += dur
        self._note_phase_windows(model_name, windows, batch,
                                 token=id(batch))

    def _note_phase_windows(self, model_name: str,
                            windows: dict[str, tuple[float, float]],
                            batch: list[_Pending],
                            token: int | None = None) -> None:
        """Account one batch's measured phase wall windows (perf-counter
        space): phase histograms, h2d/execute overlap against OTHER
        batches' execute windows, and per-request ledger stamps.
        ``token`` identifies this batch in ``_exec_pending`` so its own
        in-flight execute never counts as overlap."""
        now = time.perf_counter()
        for phase, (w0, w1) in windows.items():
            self._phase_hist.observe(w1 - w0, phase=phase, model=model_name)
        h2d_w = windows.get("h2d")
        exec_w = windows.get("execute", windows.get("compile"))
        if h2d_w is not None and h2d_w[1] > h2d_w[0]:
            h2d = h2d_w[1] - h2d_w[0]
            with self._phase_lock:
                overlap = 0.0
                for w0, w1 in self._exec_windows:
                    overlap += max(0.0, min(h2d_w[1], w1) - max(h2d_w[0], w0))
                for tok, start in self._exec_pending.items():
                    if tok != token:
                        # In-flight batch: execute window approximated
                        # from its call start to now (over-counts by its
                        # own h2d time on the fused path; exact on the
                        # double-buffered path, whose pending entries
                        # are stamped at execute-stage entry — see
                        # __init__ comment / docs/observability.md).
                        overlap += max(0.0, min(h2d_w[1], now)
                                       - max(h2d_w[0], start))
                overlap = min(overlap, h2d)
                if exec_w is not None:
                    self._exec_windows.append(exec_w)
                self._h2d_seconds += h2d
                self._h2d_overlap_seconds += overlap
                ratio = (self._h2d_overlap_seconds / self._h2d_seconds
                         if self._h2d_seconds > 0 else 0.0)
            self._overlap_total.inc(overlap, model=model_name)
            self._overlap_ratio.set(ratio)
        elif exec_w is not None:
            with self._phase_lock:
                self._exec_windows.append(exec_w)
        # Ledger stamps ride wall-clock time like every other hop:
        # convert the perf-counter anchors through "now".
        stamped = [p for p in batch if p.ledger is not None]
        if stamped:
            epoch_off = time.time() - now
            for phase in ("h2d", "compile", "execute", "d2h"):
                w = windows.get(phase)
                if w is None:
                    continue
                for p in stamped:
                    p.ledger.stamp(phase, "device", t=epoch_off + w[0],
                                   ms=(w[1] - w[0]) * 1e3)

    def _note_pad(self, model_name: str, n: int, bucket: int,
                  example_nbytes: int) -> None:
        """Pad-waste accounting at the cut: cumulative padded/occupied
        slot ratio and padding bytes shipped to the device — the series
        that justifies (and regression-guards) ladder derivation."""
        if not self._pad_enabled:
            return
        state = self._pad_state.setdefault(model_name, [0, 0])
        state[0] += bucket - n
        state[1] += n
        self._pad_ratio.set(state[0] / state[1], model=model_name)
        if bucket > n:
            self._pad_bytes.inc((bucket - n) * example_nbytes,
                                model=model_name)

    def _staging_buffer(self, model_name: str, bucket: int,
                        servable) -> np.ndarray:
        """Next host staging buffer from the (model, bucket) ring — the
        alternating buffer pair (``pipeline_depth`` deep) that lets
        batch N+1 pad while batch N's buffer is still transfer-bound.
        The window semaphore admits at most ``pipeline_depth`` in-flight
        batches in FIFO order, so a buffer is never handed out again
        before its previous batch fully completed."""
        key = (model_name, bucket)
        # A ladder swap retired buckets: drop their rings, or shifting
        # traffic accumulates pipeline_depth full-size host buffers per
        # stale bucket forever (a 512px detector ring is ~200 MB each).
        # Swept on EVERY call — a shrink-only swap never allocates a new
        # key, so allocation-time-only eviction would keep the retired
        # larger ring for the process lifetime. In-flight batches hold
        # their own references to the arrays, so eviction only releases
        # this cache; a cut still riding the pre-swap ladder (this
        # call's ``bucket`` is exempt from the sweep) re-allocates.
        live = set(servable.batch_buckets)
        for stale in [k for k in self._staging
                      if k[0] == model_name and k[1] not in live
                      and k[1] != bucket]:
            del self._staging[stale]
            self._staging_idx.pop(stale, None)
        ring = self._staging.get(key)
        if ring is None:
            ring = [np.zeros((bucket, *servable.input_shape),
                             servable.input_dtype)
                    for _ in range(self.pipeline_depth)]
            self._staging[key] = ring
            self._staging_idx[key] = 0
        idx = self._staging_idx[key]
        self._staging_idx[key] = (idx + 1) % len(ring)
        return ring[idx]

    async def _execute(self, loop, model_name: str, batch: list[_Pending],
                       bucket: int) -> None:
        """Run one cut batch padded to ``bucket`` — chosen at cut time
        from the same ladder snapshot as the cut itself (see
        ``_take_batch``); never re-derived here."""
        servable = self.runtime.models[model_name]
        n = len(batch)
        now = time.perf_counter()
        for p in batch:
            self._queue_wait.observe(now - p.enqueued, model=model_name)

        if self._double:
            await self._execute_pipelined(loop, model_name, servable,
                                          batch, n, bucket)
            return

        padded = np.zeros((bucket, *servable.input_shape),
                          servable.input_dtype)
        for i, p in enumerate(batch):
            padded[i] = p.example
            if p.ledger is not None:
                p.ledger.stamp("batched", "batcher",
                               reason=f"size {n} bucket {bucket}")
        self._note_pad(model_name, n, bucket, padded.nbytes // bucket)

        t0 = time.perf_counter()
        # Phase-decomposed path (observability): measured h2d / execute /
        # d2h plus transfer/execute overlap accounting. Falls back to
        # run_batch_report — which surfaces rows a degraded follower
        # invalidated (multihost zeros-shard path) — and plain run_batch
        # for duck-typed runtimes without either.
        phased = (self.measure_phases
                  and getattr(self.runtime, "run_batch_phases", None)
                  is not None)
        runner = getattr(self.runtime, "run_batch_report", None)
        phases: dict = {}
        if phased:
            with self._phase_lock:
                self._exec_pending[id(batch)] = t0
        try:
            if phased:
                outputs, poisoned, phases = await loop.run_in_executor(
                    self._executor, self.runtime.run_batch_phases,
                    model_name, padded)
            elif runner is not None:
                outputs, poisoned = await loop.run_in_executor(
                    self._executor, runner, model_name, padded)
            else:
                outputs = await loop.run_in_executor(
                    self._executor, self.runtime.run_batch, model_name, padded)
                poisoned = frozenset()
        except Exception as exc:  # noqa: BLE001 — device failure fails the batch
            log.exception("batch execution failed for %s", model_name)
            for p in batch:
                if not p.future.done():
                    p.future.set_exception(exc)
            return
        finally:
            if phased:
                with self._phase_lock:
                    self._exec_pending.pop(id(batch), None)
        if phases:
            self._note_phases(model_name, t0, phases, batch)
        # Mesh serving plane: per-mesh-process device phases (primary's
        # per-follower shard staging + the SPMD execute) stamped into each
        # request's ledger keyed by process index — existing h2d/execute
        # vocabulary, reason carries the key (docs/mesh_serving.md).
        drain = getattr(self.runtime, "drain_process_phases", None)
        if drain is not None:
            for label, proc, dur in drain():
                for p in batch:
                    if p.ledger is not None:
                        p.ledger.stamp(label, "device",
                                       reason=f"proc={proc}", ms=dur * 1e3)
        self._batch_latency.observe(time.perf_counter() - t0, model=model_name)
        self._batch_size_hist.observe(n, model=model_name)
        self._h2d_bytes.inc(padded.nbytes, model=model_name)
        self._d2h_bytes.inc(_tree_nbytes(outputs), model=model_name)
        await self._deliver(loop, model_name, servable, batch, outputs,
                            n, poisoned)

    async def _execute_pipelined(self, loop, model_name: str, servable,
                                 batch: list[_Pending], n: int,
                                 bucket: int) -> None:
        """The double-buffered execute path: padding into an alternating
        staging buffer, then h2d → execute → d2h on three dedicated
        single-thread executors. The device still serialises compute
        (one execute thread), but batch N+1's ``device_put`` runs while
        batch N executes and batch N's ``device_get`` runs while batch
        N+1 executes — transfer hidden under compute, measured by the
        phase windows this path hands ``_note_phase_windows`` verbatim
        (real wall windows, not back-to-back reconstructions)."""
        buf = self._staging_buffer(model_name, bucket, servable)
        for i, p in enumerate(batch):
            buf[i] = p.example
            if p.ledger is not None:
                p.ledger.stamp("batched", "batcher",
                               reason=f"size {n} bucket {bucket}")
        if n < bucket:
            buf[n:] = 0  # previous batch's rows must not ride as padding
        self._note_pad(model_name, n, bucket, buf.nbytes // bucket)
        token = id(batch)
        t0 = time.perf_counter()
        try:
            device_batch, h2d_w = await loop.run_in_executor(
                self._h2d_pool, self.runtime.h2d_resident, model_name, buf)
            if self.measure_phases:
                # Visible to concurrent batches' overlap accounting from
                # the moment this batch enters the execute stage.
                with self._phase_lock:
                    self._exec_pending[token] = time.perf_counter()
            try:
                out, label, exec_w = await loop.run_in_executor(
                    self._exec_pool, self.runtime.execute_resident,
                    model_name, device_batch)
            finally:
                if self.measure_phases:
                    with self._phase_lock:
                        self._exec_pending.pop(token, None)
            outputs, d2h_w = await loop.run_in_executor(
                self._d2h_pool, self.runtime.fetch_resident, out)
        except Exception as exc:  # noqa: BLE001 — device failure fails the batch
            log.exception("batch execution failed for %s", model_name)
            for p in batch:
                if not p.future.done():
                    p.future.set_exception(exc)
            return
        if self.measure_phases:
            self._note_phase_windows(
                model_name, {"h2d": h2d_w, label: exec_w, "d2h": d2h_w},
                batch, token=token)
        self._batch_latency.observe(d2h_w[1] - t0, model=model_name)
        self._batch_size_hist.observe(n, model=model_name)
        self._h2d_bytes.inc(buf.nbytes, model=model_name)
        self._d2h_bytes.inc(_tree_nbytes(outputs), model=model_name)
        # Split-phase execution is single-runtime only (the multi-host
        # mirror loop keeps the fused path): no partial-degrade mode.
        await self._deliver(loop, model_name, servable, batch, outputs,
                            n, frozenset())

    async def _deliver(self, loop, model_name: str, servable,
                       batch: list[_Pending], outputs, n: int,
                       poisoned: frozenset) -> None:
        if poisoned:
            # Fail exactly the affected tasks — their rows ran on a zeros
            # shard (or a failed follower) and any "result" would be a
            # confidently wrong answer; the batch's other rows are good.
            # The typed RowPoisoned lets the worker redeliver exactly these
            # tasks through resilience instead of terminally failing them
            # (runtime/mesh/redelivery.py, docs/mesh_serving.md).
            from .mesh.redelivery import RowPoisoned
            log.error("batch for %s: %d of %d rows poisoned by a degraded "
                      "host; failing those tasks", model_name,
                      sum(1 for i in range(n) if i in poisoned), n)
            for i, p in enumerate(batch):
                if i in poisoned and not p.future.done():
                    p.future.set_exception(RowPoisoned())

        # Per-example postprocess runs on the executor, not the event loop:
        # a heavy postprocess (e.g. PNG-encoding 64 class maps) would
        # otherwise stall the flusher and every other request for the whole
        # fan-out. Each in-flight batch uses at most one executor task at a
        # time (device run XOR fan-out), so this never starves run_batch.
        # Snapshot the still-wanted indices first — don't postprocess
        # examples whose futures are already done (cancelled/timed out).
        wanted = [i for i, p in enumerate(batch) if not p.future.done()]

        def _fan_out() -> list:
            results: list = []
            for i in wanted:
                try:
                    results.append(
                        (True, servable.postprocess(_tree_index(outputs, i))))
                except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the exception is delivered to the example's future below, not dropped
                    results.append((False, exc))
            return results

        for i, (ok, value) in zip(
                wanted, await loop.run_in_executor(self._executor, _fan_out)):
            fut = batch[i].future
            if fut.done():  # cancelled while the fan-out ran
                continue
            if ok:
                fut.set_result(value)
            else:
                fut.set_exception(value)


def _tree_index(outputs, i: int):
    """Slice example ``i`` out of a pytree of batched arrays."""
    import jax
    return jax.tree_util.tree_map(lambda a: a[i], outputs)


def _tree_nbytes(outputs) -> int:
    """Total bytes across a pytree of fetched arrays."""
    import jax
    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(outputs))
