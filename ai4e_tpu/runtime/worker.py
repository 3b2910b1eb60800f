"""Inference worker — binds servable models to APIService endpoints.

The per-model GPU container of the reference (``Containers/base-py`` + user
model code) becomes: one APIService with a sync and an async endpoint per
servable, both feeding the shared micro-batcher. The task semantics are
identical to the reference's (``ai4e_service.py:158-213``): sync returns the
result inline; async drives the task created→running→completed/failed and
stores the result on the task store.
"""

from __future__ import annotations

import asyncio
import inspect
import json
import logging
import time

import numpy as np

from ..admission.deadline import (SHED_REASON_HEADER, DeadlineExceeded,
                                  expired, expired_status, priority_name,
                                  shed_reason, worker_admission_kwargs)
from ..metrics import MetricsRegistry
from ..rescache.keys import cache_bypass_requested, request_key
from ..rollout.drain import (DRAINING_HEADER, DrainingError, DrainState,
                             drain_worker)
from ..rollout.canary import generation_label
from ..service import APIService
from ..service.task_manager import TaskManagerBase
from ..taskstore import TaskStatus
from .batcher import BatcherSaturated, MicroBatcher
from .mesh.redelivery import RowPoisoned, redeliver_poisoned
from .registry import ModelRuntime, ServableModel, device_report

log = logging.getLogger("ai4e_tpu.worker")


class InferenceWorker:
    """Hosts one or more servables behind one service shell."""

    def __init__(self, name: str, runtime: ModelRuntime, batcher: MicroBatcher,
                 task_manager: TaskManagerBase | None = None,
                 prefix: str = "v1", metrics: MetricsRegistry | None = None,
                 store=None, reporter=None, result_cache=None,
                 checkpoint_root: str | None = None,
                 admin_api_keys=None, cache_sync_path: bool = True,
                 hop_ledger: bool = False,
                 drain_timeout_s: float = 30.0):
        import os

        self.runtime = runtime
        self.batcher = batcher
        self.store = store
        # Hop-ledger participation (observability/ledger.py,
        # AI4E_OBSERVABILITY_HOP_LEDGER): each async request carries a
        # HopLedger buffer through the batcher (batch cut + device
        # phases) and flushes it to the task store in ONE call before
        # the terminal transition — so the control plane's per-task
        # timeline is complete across the process boundary. Off (the
        # default) allocates nothing and makes no extra store calls.
        self._hop_ledger = hop_ledger
        # Inference result cache (rescache/): the sync path answers repeat
        # requests from it (keyed on model + params_version + wire + body,
        # so a reload's version bump alone already misses), and a checkpoint
        # hot reload invalidates every family this worker serves — a stale
        # result can never outlive a weight swap.
        self.result_cache = result_cache
        # False when a CACHING GATEWAY fronts this worker with the same
        # ResultCache (combined-process assembly, bench): the proxy already
        # answers hits and fills on response — a second worker-keyed entry
        # per request would hold identical bytes twice against the byte
        # budget and could never be hit by gateway traffic (the gateway
        # answers from its own key first). The reload invalidation hook is
        # unaffected — it needs only the cache reference.
        self._cache_sync_path = cache_sync_path
        # Hot-reload confinement (ADVICE r5): when set, reload checkpoints
        # must resolve (realpath, symlinks included) under this directory —
        # anything else answers 403. None preserves the open single-host
        # behavior for dev/tests.
        self._checkpoint_root = (os.path.realpath(checkpoint_root)
                                 if checkpoint_root else None)
        # API-key gate for the admin surface (reload): the same subscription
        # keys the gateway's middleware checks; None → open.
        self._admin_keys = set(admin_api_keys) if admin_api_keys else None
        self.service = APIService(name, prefix=prefix,
                                  task_manager=task_manager, metrics=metrics,
                                  reporter=reporter)
        # Deadline drops at the worker's submit hop (admission/): the same
        # series the gateway/dispatcher/batcher report into.
        self._expired_total = self.service.metrics.counter(
            "ai4e_admission_expired_total",
            "Requests dropped on deadline expiry, by hop/priority")
        self._served: dict[str, dict] = {}  # model -> endpoint listing
        # Streaming decode engines served via serve_stream — the reload
        # endpoint resolves LM names here (they never enter
        # runtime.models) and the launcher starts/stops them.
        self.decode_engines: list = []
        # Serializes hot reloads: concurrent swaps would otherwise leave
        # checkpoint_path/params_version reporting a different rollout
        # than the params actually serving.
        self._reload_lock = asyncio.Lock()
        # Rollout drain (rollout/drain.py, AI4E_ROLLOUT_DRAIN_TIMEOUT_MS):
        # one state machine shared by every surface of this process — the
        # batcher, the decode engines, the reload verb and the admission
        # checks all consult it.
        self.drain_state = DrainState()
        self._drain_timeout_s = drain_timeout_s
        # Per-generation serving outcomes/latency (docs/METRICS.md): the
        # rollout controller's burn guard compares these series between
        # the canary and the incumbent generation. The label is bounded
        # by generation_label (AIL013 — top-N+other).
        self._rollout_outcomes = self.service.metrics.counter(
            "ai4e_rollout_outcomes_total",
            "Worker inference outcomes by rollout generation")
        self._rollout_latency = self.service.metrics.histogram(
            "ai4e_rollout_request_seconds",
            "Worker inference latency by rollout generation")
        self._drain_gauge = self.service.metrics.gauge(
            "ai4e_rollout_drain_state",
            "Worker drain state (0 active, 1 draining, 2 drained)")
        self.service.app.router.add_get(self.service.prefix + "/models",
                                        self._list_models)
        self.service.app.router.add_post(
            self.service.prefix + "/models/{name}/reload",
            self._reload_model)
        self.service.app.router.add_post(
            self.service.prefix + "/worker/drain", self._drain_worker)
        self.service.app.router.add_get(
            self.service.prefix + "/worker/drain", self._drain_status)
        self.service.app.router.add_post(
            self.service.prefix + "/worker/resume", self._resume_worker)

    def _admin_denied(self, request):
        """The admin surface's API-key gate (reload/drain/resume): same
        header contract as the gateway's middleware; None passes."""
        if self._admin_keys is None:
            return None
        from aiohttp import web
        key = (request.headers.get("Ocp-Apim-Subscription-Key")
               or request.headers.get("X-Api-Key"))
        if key not in self._admin_keys:
            return web.json_response(
                {"error": "missing or invalid subscription key"},
                status=401)
        return None

    async def _drain_worker(self, request):
        """POST {prefix}/worker/drain — graceful drain: stop admitting,
        retire uncut work (each async task redelivers through the broker),
        finish in-flight device batches / active decode sequences bounded
        by the drain budget, then force-retire stragglers. Idempotent —
        a second POST reports the current state. Body (optional):
        ``{"timeout_ms": N}`` overrides the configured budget."""
        from aiohttp import web
        denied = self._admin_denied(request)
        if denied is not None:
            return denied
        timeout_s = self._drain_timeout_s
        try:
            payload = json.loads(await request.read() or b"{}")
            if isinstance(payload, dict) and "timeout_ms" in payload:
                timeout_s = max(0.0, float(payload["timeout_ms"])) / 1000.0
        except (json.JSONDecodeError, TypeError, ValueError):
            return web.json_response({"error": "invalid JSON"}, status=400)
        summary = await drain_worker(
            self.drain_state, batchers=[self.batcher],
            engines=self.decode_engines, timeout_s=timeout_s)
        self._drain_gauge.set(self.drain_state.state_code)
        log.warning("worker drained: %s", summary)
        return web.json_response(summary)

    async def _drain_status(self, _request):
        from aiohttp import web
        return web.json_response({
            "state": self.drain_state.state,
            "reloads_in_flight": self.drain_state.reloads_in_flight,
            "batcher_pending": self.batcher.pending_count,
            "decode_active": sum(e.active_count
                                 for e in self.decode_engines)})

    async def _resume_worker(self, request):
        """POST {prefix}/worker/resume — re-arm after an aborted drain:
        the rollback path re-weights this replica back into service
        without a process restart."""
        from aiohttp import web
        denied = self._admin_denied(request)
        if denied is not None:
            return denied
        self.drain_state.resume()
        self.batcher.resume_from_drain()
        for engine in self.decode_engines:
            engine.resume_from_drain()
        self._drain_gauge.set(self.drain_state.state_code)
        log.warning("worker resumed from drain")
        return web.json_response({"state": self.drain_state.state})

    async def _list_models(self, _request):
        """Model-registry introspection — what the reference delegates to its
        container registry + values files, queryable live here. ``device``
        is what JAX says this process executes on (platform, device_kind,
        device_count, mesh axes, jax/jaxlib/libtpu versions): a caller
        reads it before believing any number the worker produced."""
        from aiohttp import web
        out = []
        # Mesh serving plane: the validated layout + live health, one per
        # endpoint (worker-level, every model on it) — how clients and
        # the orchestrator discover the shape/cost tier a worker serves
        # (docs/mesh_serving.md#introspection).
        mesh_desc = (self.runtime.describe()
                     if hasattr(self.runtime, "layout")
                     and hasattr(self.runtime, "describe") else None)
        for name, s in self.runtime.models.items():
            entry = {
                "name": name, "version": s.version,
                "params_version": s.params_version,
                "generation": s.generation,
                "checkpoint": s.checkpoint_path,
                "input_shape": list(s.input_shape),
                "input_dtype": str(np.dtype(s.input_dtype)),
                "batch_buckets": list(s.batch_buckets),
                "endpoints": self._served.get(name, {}),
            }
            # How a batch lands on the devices: the number the input
            # sharding spans and one device's slice of the largest bucket.
            entry["batch_sharding"] = {
                "devices": len(s._batch_sharding.device_set),
                "largest_bucket_shard": list(s._batch_sharding.shard_shape(
                    (s.max_bucket, *s.input_shape)))}
            if mesh_desc is not None:
                entry["mesh"] = mesh_desc
            if s.stack_item_shape is not None:
                # The batch-STACK contract when it differs from the device
                # input shape (wire-encoded servables): clients discover the
                # shape stacks must ship in, not the on-device layout.
                entry["stack_item_shape"] = list(s.stack_item_shape)
                entry["stack_item_dtype"] = str(np.dtype(
                    s.stack_item_dtype if s.stack_item_dtype is not None
                    else s.input_dtype))
            out.append(entry)
        return web.json_response(
            {"models": out, "device": device_report(self.runtime.mesh)})

    async def _reload_model(self, request):
        """POST {prefix}/models/{name}/reload — hot-swap the model's weights
        from its checkpoint (or a new one in the JSON body), no restart, no
        recompile (``ModelRuntime.reload_params``). The reference updates a
        model by building + rolling a new container image; here a retrained
        checkpoint lands on the shared mount and this endpoint flips serving
        to it between batches.

        Body (optional): ``{"checkpoint": "/abs/or/relative/path"}`` —
        relative paths resolve against the model's current checkpoint
        directory. Errors: 404 unknown model, 400 no checkpoint known,
        409 checkpoint tree mismatch, 501 on a multi-host slice (every
        process would need the swap; roll replicas there instead)."""
        import os

        from aiohttp import web

        import jax

        denied = self._admin_denied(request)
        if denied is not None:
            return denied
        name = request.match_info["name"]
        servable = self.runtime.models.get(name)
        lm_backend = None
        if servable is None:
            # Streaming LMs live on decode engines, not runtime.models;
            # their reload additionally invalidates the pooled KV cache
            # (params_version bump → the engine re-prefills actives,
            # docs/streaming.md).
            lm_backend = next(
                (e.backend for e in self.decode_engines
                 if getattr(e.backend, "name", None) == name), None)
            if lm_backend is None:
                return web.json_response({"error": "unknown model"},
                                         status=404)
            servable = lm_backend.servable
        if jax.process_count() > 1:
            return web.json_response(
                {"error": "hot reload is single-host; drain each replica "
                          "(POST /v1/worker/drain) and roll the multi-host "
                          "slice through the rollout controller instead "
                          "(docs/deployment.md#rollouts)"}, status=501)
        try:
            payload = json.loads(await request.read() or b"{}")
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        if not isinstance(payload, dict):
            return web.json_response(
                {"error": "body must be a JSON object"}, status=400)
        path = payload.get("checkpoint") or servable.checkpoint_path
        if not path:
            return web.json_response(
                {"error": "model has no checkpoint to reload; pass "
                          '{"checkpoint": ...}'}, status=400)
        if not isinstance(path, str):
            return web.json_response(
                {"error": "checkpoint must be a string path"}, status=400)
        if not os.path.isabs(path):
            if not servable.checkpoint_path:
                # No directory to resolve against — orbax would resolve
                # it against the server CWD, a silent wrong place.
                return web.json_response(
                    {"error": "relative checkpoint path but the model has "
                              "no recorded checkpoint directory; pass an "
                              "absolute path"}, status=400)
            path = os.path.abspath(os.path.join(
                os.path.dirname(servable.checkpoint_path), path))
        if self._checkpoint_root is not None:
            # Realpath-prefix confinement (ADVICE r5): the request body names
            # a filesystem path — without this check anyone who can reach
            # the worker port could swap the served weights to ANY readable
            # checkpoint on disk ("../" traversal, absolute paths, symlink
            # hops included).
            real = os.path.realpath(path)
            if not (real == self._checkpoint_root
                    or real.startswith(self._checkpoint_root + os.sep)):
                return web.json_response(
                    {"error": "checkpoint path escapes the configured "
                              "checkpoint directory"}, status=403)
            path = real

        generation = payload.get("generation")
        if generation is not None and not isinstance(generation, int):
            return web.json_response(
                {"error": "generation must be an integer"}, status=400)

        def load_and_swap():
            from ..checkpoint import load_params
            new_params = load_params(path, like=servable.params)
            if lm_backend is not None:
                lm_backend.reload_params(new_params)
                return servable
            return self.runtime.reload_params(name, new_params)

        # Drain interlock (rollout/drain.py): check + register are one
        # synchronous step, so a reload racing a drain either lands fully
        # before the drain (which then waits on reloads_in_flight) or is
        # refused here — a weight swap can never complete on a worker
        # that already reported itself drained
        # (tests/test_race_regressions.py).
        if not self.drain_state.try_begin_reload():
            return web.json_response(
                {"error": "worker is draining; reload refused — the "
                          "rollout path owns this replica now"},
                status=409, headers={DRAINING_HEADER: "1"})
        try:
            async with self._reload_lock:
                try:
                    # Off the event loop: orbax reads disk and device_puts.
                    await asyncio.to_thread(load_and_swap)
                except ValueError as exc:
                    return web.json_response({"error": str(exc)}, status=409)
                except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the error is returned to the caller as the 400 body
                    return web.json_response(
                        {"error": f"reload failed: {type(exc).__name__}: "
                                  f"{exc}"}, status=400)
                servable.checkpoint_path = path
                if generation is not None:
                    # The rollout coordinate: the controller's reload
                    # carries the target generation; the canary split
                    # routes on it (rollout/canary.py).
                    servable.generation = generation
                if self.result_cache is not None:
                    # Invalidation-on-reload (rescache/): drop every cached
                    # result this model could have produced — the worker's
                    # own family (sync path) AND each endpoint path it
                    # serves (the gateway/dispatcher key namespace) — so a
                    # result computed on the old weights is unreachable
                    # from the moment the swap lands.
                    for family in (name,
                                   *self._served.get(name, {}).values()):
                        self.result_cache.invalidate_family(family)
                return web.json_response(
                    {"model": name, "checkpoint": path,
                     "params_version": servable.params_version,
                     "generation": servable.generation})
        finally:
            self.drain_state.end_reload()

    def serve_model(self, servable: ServableModel,
                    sync_path: str | None = None,
                    async_path: str | None = None,
                    maximum_concurrent_requests: int = 64,
                    pipeline_to=None) -> None:
        """Expose a servable on sync + async endpoints.

        ``pipeline_to`` makes this servable a *pipeline stage* (the composite
        ensembles of ``distributed_api_task.py:67-100``): a callable
        ``(result) -> (next_endpoint, body_bytes) | None`` evaluated after
        inference on the async path. A two-argument callable additionally
        receives the stage's decoded input example — payload-shaping
        handoffs (``handoffs.crops_handoff`` shipping detector crops to the
        classifier) need the image, not just the JSON result. A tuple hands
        the task — same TaskId — to the next API via AddPipelineTask;
        ``None`` means "nothing to hand off" and the stage completes the
        task itself (e.g. a detector that found no animals skips the
        classifier).
        """
        if pipeline_to is not None:
            params = [
                p for p in inspect.signature(pipeline_to).parameters.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
            handoff_wants_example = len(params) >= 2
        else:
            handoff_wants_example = False
        name = servable.name
        sync_path = sync_path or f"/{name}"
        async_path = async_path or f"/{name}-async"
        self._served.setdefault(name, {}).update({
            "sync": self.service.prefix + sync_path,
            "async": self.service.prefix + async_path})

        def _saturation_check():
            # Drain gate first (rollout/drain.py): a draining worker
            # refuses BEFORE adopting a task — the broker redelivers it to
            # a peer, and the X-Draining marker ejects this backend from
            # placement for a TTL instead of tripping a breaker.
            if self.drain_state.is_draining:
                return (503, "Worker draining; retry a peer.",
                        {"Retry-After": "1", DRAINING_HEADER: "1",
                         SHED_REASON_HEADER:
                             shed_reason("worker", "draining")})
            # Mesh-endpoint health gate (docs/mesh_serving.md): a dead
            # follower means THIS endpoint cannot answer correctly — 500,
            # a breaker FAILURE, so dispatchers eject it and route to
            # healthy replicas. Deliberately not 503: observe_status
            # treats 503 as saturation-neutral ("peers are melting too"),
            # which must not apply to a half-dead mesh.
            health = getattr(self.runtime, "health", None)
            if health is not None and not health.healthy:
                return 500, f"Mesh endpoint unhealthy: {health.reason}"
            # Admission-time backpressure: refuse BEFORE adopting a task so
            # the dispatcher's 503 handling (delay + redeliver) engages —
            # queue-depth-vs-device-occupancy replacing the reference's
            # per-replica thread cap (SURVEY.md §7 hard part #2).
            if self.batcher.pending_count >= self.batcher.max_pending:
                return 503, "Inference queue saturated; retry later.", {
                    "Retry-After": "1"}
            return None

        async def _sync_request_kwargs(request):
            # Default body/content_type extraction plus the cache opt-out:
            # the handler signature has no request object, and the
            # documented X-Cache-Bypass / Cache-Control: no-cache contract
            # ("this request must execute; no cache read, no store") must
            # hold at the worker's own cache too — the gateway's sync proxy
            # forwards these headers verbatim. Admission state rides the
            # same extraction: X-Deadline-At (stamped by the proxy) or
            # X-Deadline-Ms (a direct caller), X-Priority.
            return {"body": await request.read(),
                    "content_type": request.content_type,
                    "cache_bypass": cache_bypass_requested(request.headers),
                    **worker_admission_kwargs(request.headers)}

        async def _async_request_kwargs(request):
            # The dispatcher forwards X-Deadline-At / X-Priority on its
            # backend POST (broker/dispatcher.py); the worker is the LAST
            # shed point before the device, so the handler needs them.
            return {"body": await request.read(),
                    "content_type": request.content_type,
                    **worker_admission_kwargs(request.headers)}

        @self.service.api_sync_func(
            sync_path, maximum_concurrent_requests=maximum_concurrent_requests,
            admission_check=_saturation_check,
            request_processing_function=_sync_request_kwargs)
        async def _sync(body, content_type, cache_bypass=False,
                        deadline_at=0.0, priority=0, _name=name,
                        _servable=servable):
            if expired(deadline_at):
                # Submit-hop shed (admission/): the budget is already gone —
                # answering 504 now is strictly better than computing a
                # result the caller stopped waiting for.
                self._expired_total.inc(hop="worker",
                                        priority=priority_name(priority))
                from aiohttp import web
                return web.Response(
                    status=504, text="Deadline exceeded before execution.",
                    headers={SHED_REASON_HEADER:
                             shed_reason("worker", "deadline")})
            # Worker-level result cache (rescache/): keyed on the model AND
            # its params_version, so a hot reload's version bump alone makes
            # every pre-swap entry unreachable (the reload hook additionally
            # invalidates the family outright).
            cache = (self.result_cache
                     if self._cache_sync_path and not cache_bypass else None)
            key = None
            if cache is not None:
                key = request_key(_name, body, content_type,
                                  checkpoint=str(_servable.params_version))
                # count=False: hit/miss outcomes are counted once, at the
                # gateway edge — this inner lookup must not double-count a
                # request the sync proxy already recorded.
                found = cache.get(key, count=False)
                if found is not None:
                    return json.loads(found[0])
            example = _servable.preprocess(body, content_type)
            gen_label = generation_label(_servable.generation)
            t0 = time.perf_counter()
            try:
                result = await self.batcher.submit(_name, np.asarray(example),
                                                   priority=priority,
                                                   deadline_at=deadline_at)
            except BatcherSaturated:
                from aiohttp import web
                self._rollout_outcomes.inc(generation=gen_label,
                                           outcome="saturated")
                return web.Response(status=503,
                                    text="Inference queue saturated; retry.",
                                    headers={"Retry-After": "1"})
            except DrainingError:
                # Raced the drain flip between admission and submit: the
                # refusal is retryable at a peer, never a failure of this
                # request (docs/deployment.md#drain).
                from aiohttp import web
                self._rollout_outcomes.inc(generation=gen_label,
                                           outcome="draining")
                return web.Response(
                    status=503, text="Worker draining; retry a peer.",
                    headers={"Retry-After": "1", DRAINING_HEADER: "1"})
            except RowPoisoned:
                # Sync path has no task to redeliver — answer an honest
                # retryable error (503: the caller/proxy retries; other
                # rows of the batch were unaffected), never the zeros-
                # shard "result".
                from aiohttp import web
                return web.Response(
                    status=503,
                    text="Result invalidated by a degraded mesh host; retry.",
                    headers={"Retry-After": "1"})
            except DeadlineExceeded as exc:
                from aiohttp import web
                self._rollout_outcomes.inc(generation=gen_label, outcome="expired")
                return web.Response(
                    status=504, text="Deadline exceeded while queued.",
                    headers={SHED_REASON_HEADER:
                             shed_reason(exc.hop, "deadline")})
            except Exception:
                self._rollout_outcomes.inc(generation=gen_label, outcome="error")
                raise
            self._rollout_outcomes.inc(generation=gen_label, outcome="ok")
            self._rollout_latency.observe(time.perf_counter() - t0,
                                          generation=gen_label)
            out = _jsonable(result)
            if key is not None:
                cache.put(key, json.dumps(out).encode(), "application/json")
            return out

        @self.service.api_async_func(
            async_path, maximum_concurrent_requests=maximum_concurrent_requests,
            admission_check=_saturation_check,
            request_processing_function=_async_request_kwargs)
        async def _async(taskId, body, content_type, deadline_at=0.0,
                         priority=0, _name=name, _servable=servable):
            tm = self.service.task_manager
            buf = None
            if self._hop_ledger:
                from ..observability.ledger import HopLedger
                buf = HopLedger()
            if expired(deadline_at):
                # Submit-hop shed (admission/): terminal `expired`, never
                # adopted into the batcher — the dispatcher treats the 200
                # as delivered and the store transition carries provenance.
                self._expired_total.inc(hop="worker",
                                        priority=priority_name(priority))
                await tm.update_task_status(
                    taskId, expired_status("worker"), TaskStatus.EXPIRED)
                return
            await tm.update_task_status(taskId, f"running - {_name} inference")
            try:
                example = _servable.preprocess(body, content_type)
            except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the error is recorded on the task record (failed - bad input)
                await tm.fail_task(taskId, f"failed - bad input: {exc}")
                return
            gen_label = generation_label(_servable.generation)
            t0 = time.perf_counter()
            try:
                result = await self.batcher.submit(_name, np.asarray(example),
                                                   priority=priority,
                                                   deadline_at=deadline_at,
                                                   ledger=buf)
            except BatcherSaturated:
                # Saturated between admission and submit: hand the task back
                # to the broker (same-endpoint republish with empty body →
                # original-body replay → redelivery) instead of failing it.
                self._rollout_outcomes.inc(generation=gen_label,
                                           outcome="saturated")
                current = await tm.get_task_status(taskId)
                endpoint = (current or {}).get("Endpoint", async_path)
                await tm.add_pipeline_task(taskId, endpoint)
                return
            except DrainingError:
                # The drain retired this entry before it was cut to the
                # device (or the flip raced submit): redeliver the task
                # through the broker — per task, exactly the poisoned-row
                # path — so a peer serves it and no client sees a loss
                # (docs/deployment.md#drain).
                self._rollout_outcomes.inc(generation=gen_label,
                                           outcome="draining")
                if buf is not None:
                    from ..observability.ledger import RETRY
                    buf.stamp(RETRY, "worker", reason="draining")
                await self._flush_ledger(tm, taskId, buf)
                await redeliver_poisoned(tm, taskId, async_path)
                return
            except RowPoisoned:
                # A degraded mesh host invalidated THIS row (the batch's
                # other rows completed): redeliver the task through the
                # broker — per-task retry, never a terminal failure and
                # never a silent wrong answer. The redelivery helper
                # probes terminality first, so a concurrently completed
                # duplicate is suppressed, not re-executed
                # (docs/mesh_serving.md#poisoned-rows).
                if buf is not None:
                    from ..observability.ledger import RETRY
                    buf.stamp(RETRY, "worker", reason="poisoned-row")
                await self._flush_ledger(tm, taskId, buf)
                await redeliver_poisoned(tm, taskId, async_path)
                return
            except DeadlineExceeded as exc:
                # Expired while pending in the batcher (which already
                # counted the hop metric): terminal transition only.
                self._rollout_outcomes.inc(generation=gen_label, outcome="expired")
                await self._flush_ledger(tm, taskId, buf)
                await tm.update_task_status(
                    taskId, expired_status(exc.hop), TaskStatus.EXPIRED)
                return
            except Exception:
                # Execution failure (device error surfacing through the
                # batch future): the service shell fails the task AFTER
                # this re-raise — flush the batched/phase stamps FIRST,
                # while the task is still non-terminal, so exactly the
                # failed requests the flight recorder keeps at 100 %
                # carry their worker-side timeline.
                self._rollout_outcomes.inc(generation=gen_label, outcome="error")
                await self._flush_ledger(tm, taskId, buf)
                raise
            self._rollout_outcomes.inc(generation=gen_label, outcome="ok")
            self._rollout_latency.observe(time.perf_counter() - t0,
                                          generation=gen_label)
            if pipeline_to is not None:
                if handoff_wants_example:
                    # Handoffs consume the natural image; wire-encoded
                    # servables (yuv420 flat planes) decode it back first.
                    img = (_servable.example_decoder(example)
                           if _servable.example_decoder is not None
                           else example)
                    handoff = pipeline_to(result, img)
                else:
                    handoff = pipeline_to(result)
                if handoff is not None:
                    next_endpoint, next_body = handoff
                    # Stage 1's device phases flush now — the next
                    # stage's worker opens its own buffer under the
                    # same TaskId, so the timeline spans the pipeline.
                    await self._flush_ledger(tm, taskId, buf)
                    # Keep the stage's intermediate output retrievable
                    # under the same TaskId while the task moves on.
                    await self._store_result(
                        taskId, json.dumps(_jsonable(result)).encode(),
                        stage=_name)
                    await tm.update_task_status(
                        taskId, f"running - {_name} handing off to "
                                f"{next_endpoint}")
                    await tm.add_pipeline_task(taskId, next_endpoint,
                                               body=next_body)
                    return
            # Flush BEFORE the result write and the terminal transition:
            # the task is still live (retention cannot have evicted it),
            # and a failing result hop then still leaves the timeline on
            # the record for the shell's failure path.
            await self._flush_ledger(tm, taskId, buf)
            await self._store_result(
                taskId, json.dumps(_jsonable(result)).encode())
            await tm.complete_task(
                taskId, f"completed - {_summarise(result)}")

    async def _flush_ledger(self, tm, task_id: str, buf) -> None:
        """Ship a request's buffered hop-ledger events to the store in
        one call; DRAINS the buffer, so the finally backstop after an
        already-flushed path is a no-op. Failures are dropped with a
        debug log — fail-open telemetry, never a serving error
        (docs/observability.md)."""
        if buf is None:
            return
        events = buf.drain()
        if not events:
            return
        try:
            await tm.append_ledger(task_id, events)
        except Exception:  # noqa: BLE001 — observability is fail-open: a dropped flush loses a timeline, not a task
            log.debug("hop-ledger flush dropped for task %s", task_id,
                      exc_info=True)

    def serve_stream(self, engine, async_path: str | None = None,
                     maximum_concurrent_requests: int = 64,
                     event_hub=None) -> None:
        """Expose a streaming autoregressive endpoint over a
        ``DecodeEngine`` (``runtime/decode.py``) — the continuous-
        batching serving path. The request joins the running decode
        batch between steps; every generated token is published as a
        ``chunk`` event through ``event_hub`` (the PR 9 ``TaskEventHub``)
        under the request's TaskId, so ``GET /v1/taskmanagement/task/
        {id}/events`` streams tokens live while the task runs.

        Request body (JSON): ``{"prompt": [token ids],
        "max_new_tokens": N}``. The stored result is
        ``{"tokens": [...], "count": N}``. ``event_hub=None`` (a worker
        process with no in-process hub) still serves — tokens just
        aren't fanned out as SSE chunks from THIS process.

        Backpressure rides the existing admission path: a saturated
        engine answers 503 at admission (the dispatcher's delay +
        redeliver contract), and a mid-handler saturation republishes
        the task exactly like the batch path.
        """
        from ..pipeline.events import CHUNK
        from .decode import DecodeSaturated

        name = engine.backend.name
        async_path = async_path or f"/{name}-stream-async"
        self._served.setdefault(name, {}).update(
            stream_async=self.service.prefix + async_path)
        self.decode_engines.append(engine)
        vocab = getattr(engine.backend, "servable", None)
        vocab = getattr(vocab, "vocab_size", None)

        def _saturation_check():
            if self.drain_state.is_draining:
                return (503, "Worker draining; retry a peer.",
                        {"Retry-After": "1", DRAINING_HEADER: "1",
                         SHED_REASON_HEADER:
                             shed_reason("worker", "draining")})
            if engine.pending_count >= engine.max_pending:
                return 503, "Decode queue saturated; retry later.", {
                    "Retry-After": "1"}
            return None

        async def _request_kwargs(request):
            return {"body": await request.read(),
                    "content_type": request.content_type,
                    **worker_admission_kwargs(request.headers)}

        def _parse(body: bytes) -> tuple[list[int], int]:
            payload = json.loads(body)
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
            # "prompt" is the client wire; "tokens" lets an upstream
            # stage's stored result ({"tokens": [...]}) feed this stage
            # directly — the chained ASR→summarize pipeline shape
            # (docs/streaming.md).
            prompt = payload.get("prompt", payload.get("tokens"))
            if (not isinstance(prompt, list) or not prompt
                    or not all(isinstance(t, int) for t in prompt)):
                raise ValueError('"prompt" must be a non-empty list of '
                                 'token ids')
            if vocab is not None and any(
                    not 0 <= t < vocab for t in prompt):
                raise ValueError(f"token ids must be in [0, {vocab})")
            if len(prompt) >= engine.backend.max_len:
                # Client-input error, failed HERE so it lands as
                # "failed - bad input" like every other bad payload —
                # engine.submit's own guard would otherwise surface
                # through the shell's crash path.
                raise ValueError(
                    f"prompt of {len(prompt)} tokens leaves no room to "
                    f"generate under the KV-cache length "
                    f"{engine.backend.max_len}")
            max_new = payload.get("max_new_tokens", 64)
            if not isinstance(max_new, int) or max_new < 1:
                raise ValueError('"max_new_tokens" must be a positive int')
            return prompt, max_new

        @self.service.api_async_func(
            async_path,
            maximum_concurrent_requests=maximum_concurrent_requests,
            admission_check=_saturation_check,
            request_processing_function=_request_kwargs)
        async def _stream(taskId, body, content_type, deadline_at=0.0,
                          priority=0, _name=name):
            tm = self.service.task_manager
            buf = None
            if self._hop_ledger:
                from ..observability.ledger import HopLedger
                buf = HopLedger()
            if expired(deadline_at):
                self._expired_total.inc(hop="worker",
                                        priority=priority_name(priority))
                await tm.update_task_status(
                    taskId, expired_status("worker"), TaskStatus.EXPIRED)
                return
            try:
                prompt, max_new = _parse(body)
            except (ValueError, json.JSONDecodeError) as exc:
                await tm.fail_task(taskId, f"failed - bad input: {exc}")
                return
            # Pipeline-stage chunk layering (docs/pipelines.md): a stage
            # sub-task's tokens publish under the ROOT TaskId — the one
            # stream a client watches — with the stage name labeling
            # which node is talking, exactly like the coordinator's
            # `stage` events.
            publish_id = taskId
            if event_hub is not None:
                from ..pipeline.spec import split_sub_task_id
                root = split_sub_task_id(taskId)
                if root is not None:
                    publish_id = root[0]
                # Buffer chunks even before any SSE subscriber attaches —
                # a client connecting mid-stream replays the (bounded)
                # token history (docs/streaming.md).
                event_hub.track(publish_id)
            await tm.update_task_status(taskId, f"running - {_name} decode")

            def on_token(index: int, token: int) -> None:
                if event_hub is not None:
                    event_hub.publish(publish_id, CHUNK,
                                      {"stage": _name, "index": index,
                                       "data": {"token": token}})

            try:
                tokens = await engine.submit(prompt, max_new,
                                             on_token=on_token,
                                             priority=priority,
                                             deadline_at=deadline_at,
                                             ledger=buf)
            except DecodeSaturated:
                # Saturated between admission and submit: hand the task
                # back to the broker, same as the batch path.
                current = await tm.get_task_status(taskId)
                endpoint = (current or {}).get("Endpoint", async_path)
                await tm.add_pipeline_task(taskId, endpoint)
                return
            except DrainingError:
                # Drained mid-decode (queued entry retired, or an active
                # straggler force-retired past the budget): redeliver
                # through the broker per task — a peer re-decodes from
                # the prompt, the client never sees the drain.
                if buf is not None:
                    from ..observability.ledger import RETRY
                    buf.stamp(RETRY, "worker", reason="draining")
                await self._flush_ledger(tm, taskId, buf)
                await redeliver_poisoned(tm, taskId, async_path)
                return
            except DeadlineExceeded as exc:
                await self._flush_ledger(tm, taskId, buf)
                await tm.update_task_status(
                    taskId, expired_status(exc.hop), TaskStatus.EXPIRED)
                return
            except Exception:
                await self._flush_ledger(tm, taskId, buf)
                raise
            await self._flush_ledger(tm, taskId, buf)
            await self._store_result(taskId, json.dumps(
                {"tokens": tokens, "count": len(tokens)}).encode())
            await tm.complete_task(
                taskId, f"completed - {len(tokens)} tokens")

    def serve_batch(self, servable: ServableModel,
                    sync_path: str | None = None,
                    async_path: str | None = None,
                    max_items: int = 1024,
                    submit_concurrency: int = 64,
                    progress_every: float = 2.0,
                    maximum_concurrent_requests: int = 8) -> None:
        """Expose a *batch* API for a servable: one request carries a stack of
        N examples (npy array of shape ``(N, *stack_item_shape)`` — which is
        ``input_shape`` unless the servable declares a wire adapter, e.g.
        yuv420 servables take ``(N, H, W, 3)`` stacks and convert each item
        at ingestion), the platform fans them into the micro-batcher and
        aggregates the results.

        The reference's batch APIs (``APIs/Projects/camera-trap/
        batch-detection-async.dockerfile``) are long-running tasks over many
        images inside one container; here the stack rides the same device
        batching as everything else — a 1000-image batch task and single-image
        requests interleave on the mesh. Per-image failure isolation: a bad
        image yields an ``error`` entry at its index, never failing the stack
        (SURVEY.md §7 hard part #1). The async path reports incremental
        progress ("running - k/N"), the reference's long-task status contract
        (``ai4e_service.py:180-213``).
        """
        import asyncio
        import io

        name = servable.name
        sync_path = sync_path or f"/{name}-batch"
        async_path = async_path or f"/{name}-batch-async"
        self._served.setdefault(name, {}).update(
            batch_sync=self.service.prefix + sync_path,
            batch_async=self.service.prefix + async_path)
        # Stacks arrive in the servable's natural payload shape; servables
        # whose device input differs (yuv420's flat planes) declare the
        # stack shape + a per-item adapter, so batch clients and the crops
        # handoff keep shipping plain (N, H, W, 3) arrays on every wire.
        item_shape = tuple(servable.stack_item_shape
                           or servable.input_shape)
        item_dtype = (servable.stack_item_dtype
                      if servable.stack_item_dtype is not None
                      else servable.input_dtype)

        def _decode_stack(body: bytes) -> np.ndarray:
            arr = np.load(io.BytesIO(body))
            if arr.ndim != len(item_shape) + 1 or tuple(arr.shape[1:]) != item_shape:
                raise ValueError(
                    f"expected stack (N, {', '.join(map(str, item_shape))}), "
                    f"got {arr.shape}")
            if len(arr) == 0:
                raise ValueError("empty batch")
            if len(arr) > max_items:
                raise ValueError(f"batch of {len(arr)} exceeds max {max_items}")
            if servable.stack_validator is not None:
                # Raw-value validation BEFORE the cast (see ServableModel).
                servable.stack_validator(arr)
            from .families import cast_image_payload
            arr = cast_image_payload(arr, item_dtype)
            if servable.stack_adapter is not None:
                arr = np.stack([servable.stack_adapter(x) for x in arr])
            return arr

        async def _run_stack(stack: np.ndarray, on_progress=None) -> list:
            results: list = [None] * len(stack)
            done = 0
            queue: asyncio.Queue[int] = asyncio.Queue()
            for i in range(len(stack)):
                queue.put_nowait(i)

            async def _puller():
                nonlocal done
                while True:
                    try:
                        i = queue.get_nowait()
                    except asyncio.QueueEmpty:
                        return
                    while True:
                        try:
                            # Background priority: the stack shares device
                            # batches with interactive traffic but never
                            # queues ahead of it.
                            out = await self.batcher.submit(
                                name, np.asarray(stack[i]), priority=1)
                            results[i] = {"index": i, "result": _jsonable(out)}
                            break
                        except BatcherSaturated:
                            # Throttle, don't fail: the stack shares the
                            # device with interactive traffic.
                            await asyncio.sleep(0.05)
                        except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the error is reported in the batch result payload for this index
                            results[i] = {"index": i, "error": str(exc)}
                            break
                    done += 1
                    if on_progress is not None:
                        await on_progress(done, len(stack))

            pullers = min(submit_concurrency, len(stack))
            await asyncio.gather(*(_puller() for _ in range(pullers)))
            return results

        @self.service.api_sync_func(
            sync_path, maximum_concurrent_requests=maximum_concurrent_requests)
        async def _sync_batch(body, content_type):
            # Off the event loop: decoding + per-item wire conversion of a
            # 1000-image stack is seconds of numpy work that must not stall
            # the interactive traffic the priority classes protect.
            stack = await asyncio.to_thread(_decode_stack, body)
            results = await _run_stack(stack)
            failed = sum(1 for r in results if "error" in r)
            return {"count": len(results), "failed": failed, "items": results}

        @self.service.api_async_func(
            async_path, maximum_concurrent_requests=maximum_concurrent_requests)
        async def _async_batch(taskId, body, content_type):
            tm = self.service.task_manager
            try:
                stack = await asyncio.to_thread(_decode_stack, body)
            except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — the error is recorded on the task record (failed - bad input)
                await tm.fail_task(taskId, f"failed - bad input: {exc}")
                return
            total = len(stack)
            await tm.update_task_status(
                taskId, f"running - {name} batch 0/{total}")
            last = {"t": 0.0}

            async def on_progress(k, n):
                import time as _t
                now = _t.monotonic()
                if now - last["t"] >= progress_every or k == n:
                    last["t"] = now
                    await tm.update_task_status(
                        taskId, f"running - {name} batch {k}/{n}")

            results = await _run_stack(stack, on_progress)
            failed = sum(1 for r in results if "error" in r)
            await self._store_result(taskId, json.dumps(
                {"count": total, "failed": failed, "items": results}).encode())
            # Never put the word "failed" in this terminal status: canonical
            # bucketing (TaskStatus.canonical) and SDK wait() test "failed"
            # first, so "completed - N images, 0 failed" would land every
            # successful batch task in the failed set.
            await tm.complete_task(
                taskId, f"completed - {total} images, {failed} errors")

    async def _store_result(self, task_id: str, payload: bytes,
                            stage: str | None = None) -> None:
        """Works with both the in-process store (sync ``set_result``) and
        ``HttpResultStore`` (coroutine) — a remote worker stores results on
        the control plane's task store."""
        if self.store is None:
            return
        res = self.store.set_result(task_id, payload, stage=stage)
        if inspect.isawaitable(res):
            await res


def _jsonable(obj):
    import jax
    if isinstance(obj, (np.ndarray, jax.Array)):
        return np.asarray(obj).tolist()
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return obj


def _summarise(result) -> str:
    if isinstance(result, dict):
        return ", ".join(f"{k}" for k in result)
    if isinstance(result, list):
        return f"{len(result)} items"
    return str(result)[:64]
