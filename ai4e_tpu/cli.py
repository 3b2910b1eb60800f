"""Component launchers — ``python -m ai4e_tpu <component>``.

The reference deploys its components as separately-provisioned Azure
resources wired by 15 bash scripts (``InfrastructureDeployment/
deploy_infrastructure.sh:5-38``); here each node of a multi-host deployment
runs one launcher, configured by ``AI4E_*`` env vars (the typed sections in
``config.py``) plus a JSON spec file:

- ``control-plane --routes routes.json`` — gateway + task store (HTTP
  surface included) + broker + dispatchers + autoscalers in one process:
  the APIM + CacheManager + Service Bus + function-app tier.
- ``worker --models models.json`` — a TPU inference node: model runtime +
  micro-batcher + service shell, task state via HttpTaskManager against
  the control plane (the AKS model-container tier).
- ``reporter`` — cross-replica in-flight request counter (the reference's
  RequestReporter function app, ``deploy_request_reporter_function.sh``).

Spec formats (JSON):

routes.json::

    {"apis": [{"prefix": "/v1/landcover/classify-async",
               "backend": "http://worker:8081/v1/landcover/classify-async",
               // or a weighted canary set (same path, hosts differ):
               // "backends": [{"uri": "http://fleet:8081/v1/...", "weight": 95},
               //              {"uri": "http://canary:8081/v1/...", "weight": 5}],
               "mode": "async",             // or "sync"
               "autoscale": {"max_replicas": 8},   // optional
               "max_body_bytes": 67108864,  // optional edge payload cap
               "concurrency": 4}]}          // optional

models.json::

    {"models": [{"family": "unet", "name": "landcover", "tile": 256,
                 "buckets": [1, 16, 64],
                 "sync_path": "/classify",
                 "async_path": "/classify-async",
                 "batch": {"max_items": 512},     // optional batch API
                 "checkpoint": "/ckpts/landcover"}],  // optional weights
     "prefix": "v1/landcover"}
"""

from __future__ import annotations

import argparse
import asyncio
import inspect
import json
import logging
import os
import signal

from .config import ConfigError, FrameworkConfig

log = logging.getLogger("ai4e_tpu.cli")


def load_spec(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def build_control_plane(config: FrameworkConfig, routes: dict):
    """Assemble the control-plane process; returns the wired platform (its
    gateway app also carries the task-store HTTP surface)."""
    from .platform_assembly import LocalPlatform
    from .scaling import AutoscalePolicy
    from .taskstore.http import make_app as make_taskstore_app

    platform = LocalPlatform(config.to_platform_config())
    if config.gateway.api_keys is not None:
        # APIM front-door parity: published APIs require a subscription key.
        keys = {k.strip() for k in config.gateway.api_keys.split(",")
                if k.strip()}
        if not keys:
            # Fail CLOSED: a set-but-empty keys value means the operator
            # wanted auth; silently running open would invert that intent.
            raise ConfigError(
                "AI4E_GATEWAY_API_KEYS is set but contains no keys")
        platform.gateway.set_api_keys(keys)
    platform.gateway.max_body_bytes = config.gateway.max_body_bytes
    if config.gateway.rate_limit_rps or config.gateway.rate_limits:
        from .gateway.ratelimit import (RateLimit, RateLimiter,
                                        parse_rate_limits)
        per_key = parse_rate_limits(config.gateway.rate_limits or "")
        if config.gateway.rate_limit_rps:
            default = RateLimit(rps=config.gateway.rate_limit_rps,
                                burst=config.gateway.rate_limit_burst)
        else:
            # Only per-key limits were given: keys without one stay
            # unlimited (a very high default bucket).
            default = RateLimit(rps=1e9)
        platform.gateway.set_rate_limiter(RateLimiter(default,
                                                      per_key=per_key))
    if config.gateway.quota or config.gateway.quotas:
        from .gateway.ratelimit import (QuotaTracker, parse_quota,
                                        parse_quotas)
        per_key_q = parse_quotas(config.gateway.quotas or "")
        # default None: keys without a per-key quota are unlimited AND
        # untracked (no per-identity window bookkeeping).
        default_q = (parse_quota(config.gateway.quota)
                     if config.gateway.quota else None)
        platform.gateway.set_quota_tracker(QuotaTracker(default_q,
                                                        per_key=per_key_q))
    # The task-store HTTP surface rides on the gateway app — one
    # control-plane port serves the CACHE_CONNECTOR_*_URI endpoints remote
    # workers use (distributed_api_task.py:14-15 pattern). It enforces the
    # gateway's edge cap itself: the app's aiohttp cap is disabled.
    make_taskstore_app(platform.store, app=platform.gateway.app,
                       max_body_bytes=config.gateway.max_body_bytes,
                       max_result_bytes=config.gateway.max_result_bytes,
                       # Role flips over HTTP (promote/demote) must run the
                       # platform's full sequence — replication torn down
                       # before the store flip, transport started/stopped
                       # around it — not a bare store flip.
                       lifecycle=platform)
    # Typed API definitions ({org, api, backend_host, ...}) publish through
    # the registration customizer (gateway/registration.py) — one publish
    # code path; both spec styles can coexist in one routes.json.
    if routes.get("definitions"):
        from .gateway.registration import ApiDefinition, register_definitions
        register_definitions(platform, [ApiDefinition.from_dict(r)
                                        for r in routes["definitions"]])
    for api in routes.get("apis", []):
        mode = api.get("mode", "async")
        # "backend": one URI; "backends": weighted canary set
        # ([{"uri": ..., "weight": N}, ...] — utils/backends.py). Presence
        # check, not truthiness: an explicitly-empty "backends" must hit
        # normalize_backends' clear error, not silently fall back.
        backend = api["backends"] if "backends" in api else api["backend"]
        if mode == "sync":
            platform.publish_sync_api(api["prefix"], backend,
                                      max_body_bytes=api.get("max_body_bytes"))
            continue
        autoscale = api.get("autoscale")
        if api.get("internal"):
            # Pipeline-stage backend: transport consumer only, no public
            # gateway route (tasks arrive via handoff republish).
            platform.register_internal_route(
                backend,
                retry_delay=api.get("retry_delay"),
                concurrency=api.get("concurrency"),
                autoscale=AutoscalePolicy(**autoscale) if autoscale else None)
            continue
        platform.publish_async_api(
            api["prefix"], backend,
            retry_delay=api.get("retry_delay"),
            concurrency=api.get("concurrency"),
            autoscale=AutoscalePolicy(**autoscale) if autoscale else None,
            max_body_bytes=api.get("max_body_bytes"))
    return platform


def _declarative_handoff(spec: dict | None):
    """Translate a model spec's ``pipeline_to`` into a handoff callable —
    composite APIs as deployment data (the reference composes ensembles in
    code via AddPipelineTask, ``distributed_api_task.py:67-100``).

    ``{"endpoint": "/v1/models/classify-async",   # next stage's backend route
       "when_nonempty": "detections"}             # optional gate on the result

    An empty handoff body makes the store replay the task's ORIGINAL payload
    to the next stage (``CacheConnectorUpsert.cs:144-176`` semantics), so a
    detector can gate a classifier on the same image. When the gate field is
    empty/absent the stage completes the task itself.

    ``"payload": "crops"`` instead ships the detector's CROPS to the next
    stage's batch endpoint (``runtime/handoffs.crops_handoff``) — tune with
    ``crop_size`` / ``max_crops`` / ``min_score``:

    ``{"endpoint": "/v1/models/classify-species-batch-async",
       "payload": "crops", "crop_size": 224, "max_crops": 16}``
    """
    if not spec:
        return None
    endpoint = spec["endpoint"]
    if spec.get("payload") == "crops":
        from .runtime.handoffs import crops_handoff
        return crops_handoff(endpoint,
                             crop_size=spec.get("crop_size", 224),
                             max_crops=spec.get("max_crops", 16),
                             min_score=spec.get("min_score"))
    gate = spec.get("when_nonempty")

    def pipeline_to(result):
        if gate is not None:
            value = result.get(gate) if isinstance(result, dict) else None
            if not value:
                return None  # nothing to hand off — stage completes the task
        return endpoint, b""  # empty body → original-body replay downstream

    return pipeline_to


def _mesh_from_config(rt):
    """Build the serving mesh from the runtime section. Two sources,
    mutually exclusive:

    - ``AI4E_RUNTIME_MESH_SPEC`` — the declarative serving-mesh grammar
      ("dp=8", "dp=2,tp=2"; runtime/mesh/spec.py), validated against the
      visible device/process topology and served as a mesh endpoint
      (docs/mesh_serving.md);
    - the low-level AI4E_RUNTIME_DP/FSDP/TP/SP/EP axis sizes.

    All defaults (no spec, dp=0, rest=1) → None → ModelRuntime's
    all-devices data-parallel default."""
    from .runtime.mesh.spec import parse_mesh_spec
    layout = parse_mesh_spec(rt.mesh_spec)
    axes = dict(fsdp=rt.fsdp, tp=rt.tp, sp=rt.sp, ep=rt.ep)
    axes_set = rt.dp > 0 or any(v > 1 for v in axes.values())
    if layout is not None:
        if axes_set:
            raise ValueError(
                "AI4E_RUNTIME_MESH_SPEC and the AI4E_RUNTIME_DP/FSDP/TP/"
                "SP/EP axis knobs are mutually exclusive — the spec IS "
                "the serving mesh; unset the axis knobs")
        from .runtime.mesh.placement import mesh_for_layout
        return mesh_for_layout(layout)
    if not axes_set:
        return None
    import jax

    from .parallel import MeshSpec, make_mesh
    denom = max(1, rt.fsdp) * max(1, rt.tp) * max(1, rt.sp) * max(1, rt.ep)
    if rt.dp <= 0:
        if jax.device_count() % denom:
            raise ValueError(
                f"{jax.device_count()} devices not divisible by "
                f"fsdp*tp*sp*ep={denom} (AI4E_RUNTIME_* axis sizes)")
        dp = jax.device_count() // denom
    else:
        dp = rt.dp
    return make_mesh(MeshSpec(dp=dp, **{k: max(1, v)
                                        for k, v in axes.items()}))


def _restore_checkpoint(servable, checkpoint: str,
                        checkpoint_dir: str | None) -> None:
    """Restore a servable's params from a models-spec checkpoint —
    shared by the batch and streaming-LM paths so resolution cannot
    diverge. Relative paths resolve under ``checkpoint_dir``
    (AI4E_RUNTIME_CHECKPOINT_DIR, the chart's volume mount) or the
    working directory — orbax requires absolute paths. The path is
    recorded for the hot-reload endpoint (POST
    {prefix}/models/{name}/reload re-reads it)."""
    from .checkpoint import load_params
    if not os.path.isabs(checkpoint):
        checkpoint = os.path.abspath(os.path.join(
            checkpoint_dir or ".", checkpoint))
    servable.params = load_params(checkpoint, like=servable.params)
    servable.checkpoint_path = checkpoint
    log.info("restored %s params from %s", servable.name, checkpoint)


def _param_bytes(params) -> int:
    """Bytes of a servable's parameters, from their shapes: nothing is
    waited for. A seeded initialisation is dispatched, not done, when its
    call returns, and the device finishes it under the host's next work
    (the pools, the first prefill's trace) — a wait here would take that
    overlap out of every start."""
    import jax
    return sum(leaf.nbytes for leaf in jax.tree.leaves(params))


def build_worker(config: FrameworkConfig, models: dict):
    """Assemble a worker process; returns (worker, batcher, task_manager).

    Under ``run_worker`` every second of it is booked to a phase of the
    boot ledger (``observability/boot.py``): ``enter`` closes the phase
    that is open and opens the next."""
    from .observability import boot
    boot.enter("build")   # the runtime's imports, the mesh, the task client
    from .runtime import (
        LM_FAMILIES,
        InferenceWorker,
        MicroBatcher,
        ModelRuntime,
        build_servable,
        enable_compilation_cache,
    )
    from .service.task_manager import (
        HttpResultStore,
        HttpTaskManager,
        LocalTaskManager,
    )

    rt = config.runtime
    cache_dir = enable_compilation_cache()
    # Multi-host slice: JAX_COORDINATOR_ADDRESS et al. initialise the DCN
    # plane (no-op single-process); the default mesh then spans every host.
    from .parallel import init_distributed
    init_distributed()
    runtime = ModelRuntime(mesh=_mesh_from_config(rt),
                           donate_batch=rt.donate_batch)

    store_base = models.get("taskstore") or config.gateway.taskstore_get_uri
    if store_base:
        # The chart mounts the gateway's comma-separated "keys" secret entry
        # directly; the worker authenticates with the first NON-EMPTY key
        # (same filtering as the gateway's parse — a leading comma must not
        # silently leave the worker keyless against a keyed store).
        key = next(
            (k.strip()
             for k in (config.service.taskstore_api_key or "").split(",")
             if k.strip()), None)
        # A comma-separated value is the control-plane REPLICA SET
        # (primary first, then standby — control-plane-standby.yaml): the
        # store client rotates on connection failure / 503-not-primary so a
        # failover needs no worker restart (_HttpStoreClient._request).
        if isinstance(store_base, str) and "," in store_base:
            store_base = [u.strip() for u in store_base.split(",")
                          if u.strip()]
        task_manager = HttpTaskManager(store_base, api_key=key)
        store = HttpResultStore(store_base, api_key=key)
        if config.service.result_dir:
            # Direct-to-storage results: large outputs write to the shared
            # result mount (same root the control plane serves via
            # AI4E_PLATFORM_RESULT_DIR) and only a pointer crosses the
            # control network — the reference's containers-write-to-blob
            # architecture.
            from .service.task_manager import DirectResultStore
            store = DirectResultStore(
                config.service.result_dir, store,
                threshold=config.service.result_offload_threshold)
    else:
        # Standalone worker (dev): own in-memory store. result_dir becomes
        # the store's OWN offload backend (no control plane to register
        # pointers with — DirectResultStore would be a wrapper around a
        # backend-less store and every large result would be refused).
        from .taskstore import InMemoryTaskStore
        result_backend = None
        threshold = None
        if config.service.result_dir:
            from .taskstore.results import FileResultBackend
            result_backend = FileResultBackend(config.service.result_dir)
            threshold = config.service.result_offload_threshold
        store = InMemoryTaskStore(result_backend=result_backend,
                                  result_offload_threshold=threshold)
        task_manager = LocalTaskManager(store)

    reporter = None
    if config.service.reporter_uri:
        # Cross-replica in-flight reporting (REQUEST_REPORTER_URI pattern,
        # ai4e_service.py:21,135-146).
        from .metrics import ProcessingReporterClient
        reporter = ProcessingReporterClient(config.service.reporter_uri,
                                            cluster=config.service.cluster)

    # Register every servable BEFORE the batcher exists: with ladder
    # derivation on, the ai4e_batch_size exposition buckets are built
    # from the servables' (possibly restored) ladders at batcher
    # construction, and the persisted-ladder restore must land before
    # warmup so a restarted worker AOT-warms the traffic-tuned ladder
    # (docs/device_path.md).
    to_serve: list[tuple] = []
    lm_specs: list[dict] = []
    for spec in models.get("models", []):
        spec = dict(spec)
        if spec["family"] in LM_FAMILIES:
            # Streaming decode servables ride the continuous-batching
            # engine, not the MicroBatcher — collected here, wired after
            # the worker exists (docs/streaming.md).
            lm_specs.append(spec)
            continue
        family = spec.pop("family")
        sync_path = spec.pop("sync_path", None)
        async_path = spec.pop("async_path", None)
        cap = spec.pop("maximum_concurrent_requests", 64)
        batch = spec.pop("batch", None)  # true | {serve_batch kwargs}
        checkpoint = spec.pop("checkpoint", None)
        pipeline_spec = spec.pop("pipeline_to", None)
        # Families that build mesh-aware compute (seqformer's sp attention)
        # receive the serving mesh; the rest ignore it via their **_ sink.
        spec.setdefault("mesh", runtime.mesh)
        boot.enter("build", model=spec.get("name", family))
        servable = build_servable(family, **spec)
        if checkpoint:
            # Restore real weights at pod start (SURVEY.md §5: the slot the
            # reference fills by baking weights into container images;
            # ai4e_tpu.train.make_checkpoints produces them).
            _restore_checkpoint(servable, checkpoint, rt.checkpoint_dir)
        runtime.register(servable)
        boot.note(param_bytes=_param_bytes(servable.params))
        to_serve.append((servable, sync_path, async_path, cap,
                         pipeline_spec, batch))

    boot.enter("serve")   # the ladder, the batcher, the shell's routes
    ladders = None
    import jax
    if rt.ladder_derive and jax.process_count() > 1 and jax.process_index():
        # Only the mesh primary derives: followers mirror the primary's
        # executions in follower_loop and jit-compile new bucket shapes
        # the moment its descriptors carry them, so a follower-local
        # deriver would only desync the broadcast order
        # (docs/mesh_serving.md). This replaces the old blanket
        # multi-process refusal — the primary's deriver now warm-executes
        # through MultihostRuntime.prepare_buckets, which broadcasts the
        # dummies so the whole slice compiles in lockstep.
        log.info("ladder derivation: follower %d defers to the mesh "
                 "primary's derived ladder", jax.process_index())
    elif rt.ladder_derive:
        # Traffic-tuned bucket ladders (AI4E_RUNTIME_LADDER_*, docs/
        # device_path.md): restore any persisted derived ladder now —
        # BEFORE warmup — so the restarted worker compiles the tuned
        # ladder and its first serving call stamps execute, not compile.
        from .runtime.ladder import LadderManager
        ladders = LadderManager(
            runtime, window_s=rt.ladder_window_s,
            max_programs=rt.ladder_max_programs,
            period_s=rt.ladder_period_s, dwell_s=rt.ladder_dwell_s,
            persist_path=(rt.ladder_path or os.path.join(
                cache_dir, "ladders.json")))
        restored = ladders.restore()
        if restored:
            log.info("restored derived ladders for %s",
                     sorted(restored))

    batcher = MicroBatcher(runtime, max_wait_ms=rt.batch_max_wait_ms,
                           max_pending=rt.batch_max_pending,
                           pipeline_depth=rt.batch_pipeline_depth,
                           interactive_reserve=rt.batch_interactive_reserve,
                           priority_aging_s=rt.batch_priority_aging_s,
                           # Device-phase decomposition rides the same
                           # switch as the worker's ledger flushes
                           # (AI4E_OBSERVABILITY_HOP_LEDGER).
                           measure_phases=config.observability.hop_ledger,
                           ladder_manager=ladders,
                           double_buffer=rt.batch_double_buffer)
    admin_keys = None
    if config.gateway.api_keys is not None:
        # The reload surface is an operator action: gate it with the same
        # front-door secret the gateway checks (the reference's APIM keys;
        # the control plane reuses it for the taskstore too).
        admin_keys = {k.strip() for k in config.gateway.api_keys.split(",")
                      if k.strip()}
    worker = InferenceWorker(
        models.get("service_name", "tpu-worker"), runtime, batcher,
        task_manager=task_manager, prefix=models.get("prefix", "v1"),
        store=store, reporter=reporter,
        # Hot-reload confinement (ADVICE r5): checkpoints must resolve
        # under the configured checkpoint mount — without this, anyone who
        # can reach the worker port could swap the served weights to any
        # readable path. None (dev, no AI4E_RUNTIME_CHECKPOINT_DIR) keeps
        # the open single-host behavior.
        checkpoint_root=rt.checkpoint_dir,
        admin_api_keys=admin_keys,
        hop_ledger=config.observability.hop_ledger,
        drain_timeout_s=config.rollout.drain_timeout_ms / 1000.0)
    for servable, sync_path, async_path, cap, pipeline_spec, batch in to_serve:
        if config.rollout.generation:
            # The deploy generation this process serves (rollout/): the
            # rollout controller bumps it per respawn; 0 keeps the
            # registry default.
            servable.generation = config.rollout.generation
        worker.serve_model(servable, sync_path=sync_path,
                           async_path=async_path,
                           maximum_concurrent_requests=cap,
                           pipeline_to=_declarative_handoff(pipeline_spec))
        if batch:
            worker.serve_batch(servable,
                               **(batch if isinstance(batch, dict) else {}))
    boot.enter("batch_warmup")
    boot.note(model_s=runtime.warmup())

    # Continuous-batching decode path (AI4E_RUNTIME_DECODE_ENABLE,
    # docs/streaming.md): one engine per LM-family spec, AOT-warmed
    # (prefill buckets + the step program) so nothing compiles on the
    # serving path. Gated twice: the knob AND a spec — neither alone
    # constructs an engine, keeping the default worker byte-identical.
    # serve_stream registers each engine on worker.decode_engines (the
    # reload endpoint and run_worker's start/stop read it there).
    if lm_specs and not rt.decode_enable:
        log.warning("models spec names %d LM servable(s) but "
                    "AI4E_RUNTIME_DECODE_ENABLE is off — not serving them",
                    len(lm_specs))
    elif lm_specs and jax.process_count() > 1:
        log.warning("streaming decode is single-host only (the engine "
                    "loop owns the device); not serving %d LM "
                    "servable(s)", len(lm_specs))
    elif lm_specs:
        boot.enter("build")   # the decode engine's and the families' imports
        from .runtime.decode import DecodeEngine
        from .runtime.executables import ExecutableStore
        from .runtime.kvcache import PagedDecodeRuntime, build_lm_servable
        # Beside the compile cache: every process of a checkout resolves the
        # same path, and this start loads what the last one built.
        store = ExecutableStore(os.path.join(cache_dir, "executables"))
        for spec in lm_specs:
            async_path = spec.pop("async_path", None)
            cap = spec.pop("maximum_concurrent_requests", 64)
            checkpoint = spec.pop("checkpoint", None)
            spec.setdefault("max_len", rt.kv_max_len)
            boot.enter("build", model=spec.get("name"))
            lm = build_lm_servable(**spec)
            if checkpoint:
                _restore_checkpoint(lm, checkpoint, rt.checkpoint_dir)
            boot.note(model=lm.name, param_bytes=_param_bytes(lm.params))
            backend = PagedDecodeRuntime(
                lm, slots=rt.kv_slots,
                prompt_buckets=rt.decode_prompt_buckets or None, store=store)
            boot.enter("pools", model=lm.name, bytes=backend.cache_nbytes())
            backend.reset_cache()   # the first pool: allocated here
            boot.enter("warm", model=lm.name)
            backend.warm()
            boot.enter("serve")
            engine = DecodeEngine(backend,
                                  max_pending=rt.decode_max_pending,
                                  metrics=worker.service.metrics)
            worker.serve_stream(engine, async_path=async_path,
                                maximum_concurrent_requests=cap)
            log.info("decode engine %s: %d slots, max_len %d, prompt "
                     "buckets %s, cache %.1f MB, stored executables %.1f MB "
                     "under %s", lm.name, backend.slots, backend.max_len,
                     backend.prompt_buckets, backend.cache_nbytes() / 1e6,
                     store.nbytes() / 1e6, store.directory)

    boot.enter("serve")
    if jax.process_count() > 1:
        # Multi-host serving (SURVEY.md §7 hard part #3): the primary's
        # batcher broadcasts each batch so every process enters the same
        # compiled call; followers mirror in follower_loop (run_worker).
        from .parallel.multihost import MultihostRuntime
        mh = MultihostRuntime(runtime)
        worker.runtime = mh
        batcher.runtime = mh
        if ladders is not None:
            # Derivation dummies must enter through the broadcast so
            # followers mirror them (MultihostRuntime.prepare_buckets).
            ladders.runtime = mh

    from .runtime.mesh import parse_mesh_spec
    layout = parse_mesh_spec(rt.mesh_spec)
    if layout is not None:
        # Mesh serving plane (AI4E_RUNTIME_MESH_SPEC, docs/mesh_serving.md):
        # the worker serves through a validated MeshEndpoint — layout
        # checked against the live mesh, poison accounting wired to the
        # coordinator's follower-health state machine, per-process device
        # phases drained into hop ledgers. Outermost wrapper: it must see
        # the multihost runtime's poison gathers, not raw registry calls.
        from .runtime.mesh import EndpointHealth, MeshCoordinator, MeshEndpoint
        health = EndpointHealth()
        coordinator = MeshCoordinator(
            layout, health=health,
            process_count=jax.process_count(),
            process_index=jax.process_index(),
            unhealthy_after=rt.mesh_unhealthy_after)
        inner = worker.runtime
        if hasattr(inner, "poison_listener"):
            coordinator.attach(inner)
        endpoint = MeshEndpoint(inner, layout, health=health,
                                coordinator=coordinator)
        worker.runtime = endpoint
        batcher.runtime = endpoint
        log.info("mesh serving plane ON: %s (tier %s, %d devices, "
                 "process %d/%d)", layout.describe()["spec"],
                 layout.tier_label, layout.size, jax.process_index(),
                 jax.process_count())
    return worker, batcher, task_manager


async def run_control_plane(config: FrameworkConfig, routes: dict) -> None:
    from aiohttp import web

    platform = build_control_plane(config, routes)
    runner = web.AppRunner(platform.gateway.app)
    await runner.setup()
    site = web.TCPSite(runner, config.gateway.host, config.gateway.port)
    await site.start()
    await platform.start()
    vitals = None
    if config.observability.vitals:
        # Runtime vitals into the ASSEMBLY registry: loop lag / GC /
        # RSS land beside the serving metrics on this process's
        # /metrics (AI4E_OBSERVABILITY_VITALS, docs/observability.md).
        from .observability.vitals import VitalsSampler
        vitals = VitalsSampler(platform.metrics,
                               interval_s=config.observability
                               .vitals_interval)
        await vitals.start()
    # Operators grep startup lines for posture; admission changes the
    # public contract (sheds, expiry, computed Retry-After —
    # AI4E_PLATFORM_ADMISSION=1, docs/admission.md) and resilience changes
    # failure semantics (breakers, retries, 5xx-as-transient —
    # AI4E_PLATFORM_RESILIENCE=1, docs/resilience.md).
    journal_stats = (platform.store.journal_stats()
                     if hasattr(platform.store, "journal_stats") else {})
    posture = ("".join([
        ", admission control ON" if platform.admission is not None else "",
        ", resilience ON" if platform.resilience is not None else "",
        # Orchestration changes placement + overload semantics (deadline/
        # cost-aware picks, brownout ladder, predictive scaling —
        # AI4E_PLATFORM_ORCHESTRATION=1, docs/orchestration.md).
        (", orchestration ON"
         if platform.orchestration is not None else ""),
        # Sharding changes the durability/availability topology (per-shard
        # journals + failover — AI4E_PLATFORM_TASK_SHARDS, docs/sharding.md).
        (f", task store sharded x{platform.config.task_shards}"
         if platform.config.task_shards > 1 else ""),
        # Tenancy changes the admission contract per caller (tenant
        # quotas, fair lanes, per-tenant series — AI4E_TENANCY_ENABLED,
        # docs/tenancy.md).
        (f", tenancy ON ({len(platform.tenancy.registry.tenant_ids())}"
         f" tenants)"
         if getattr(platform, "tenancy", None) is not None else ""),
        # Observability adds the hop ledger + flight recorder
        # (AI4E_PLATFORM_OBSERVABILITY, docs/observability.md) and,
        # with objectives, the SLO burn-rate engine.
        (", observability ON"
         if platform.observability is not None else ""),
        (f", SLO engine ON ({len(platform.slo.objectives)} objectives)"
         if platform.slo is not None else ""),
        # Vitals change what /metrics reports about the PROCESS itself
        # (ai4e_process_* — AI4E_OBSERVABILITY_VITALS).
        ", vitals ON" if vitals is not None else "",
        # The fsync policy changes what an acknowledgment MEANS against
        # a machine crash (AI4E_TASKSTORE_FSYNC, docs/durability.md) —
        # logged whenever a journal is in play (single or sharded) so
        # the posture line names the durability contract in force.
        (f", journal fsync={journal_stats['fsync_policy']}"
         if journal_stats else "")]))
    log.info("control plane on %s:%s (%d routes%s)", config.gateway.host,
             config.gateway.port, len(platform.gateway.routes), posture)
    try:
        await _wait_for_termination()
    finally:
        if vitals is not None:
            await vitals.stop()
        await platform.stop()
        await runner.cleanup()


def _claim_devices(rt) -> None:
    """The worker role's device boundary: pin the platform the operator
    named (``AI4E_RUNTIME_PLATFORM`` overrides an inherited
    ``JAX_PLATFORMS``), bring the backend up, and refuse to serve from a
    CPU nobody asked for. With no pin JAX takes the best backend it can
    initialise, and on a host whose chip is missing or held by another
    process that is the CPU after one libtpu warning — a worker that
    carried on would answer requests at CPU speed under a TPU deployment's
    name. A pinned platform that is absent fails inside ``jax.devices()``
    with JAX's own "Unable to initialize backend"."""
    import jax

    from .observability import boot
    from .parallel import init_distributed
    from .runtime.registry import device_report
    if rt.platform:
        jax.config.update("jax_platforms", rt.platform)
    # A serving worker's programs carry no Python tracebacks in their HLO:
    # nobody reads them there, and every loaded program's metadata is what
    # the profiler walks when a traced window is collected (a 4 s trace of
    # a 5.7 ms decode tick: 116 -> 102 s, CHANGES.md PR 30).
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # Before the first backend touch: jax.distributed cannot start after it.
    boot.enter("backend")
    init_distributed()
    report = device_report()
    if report["platform"] == "cpu" and rt.platform != "cpu":
        raise SystemExit(
            "worker: JAX found no accelerator and came back with "
            f"{report['device_count']} x {report['device_kind']!r} on "
            f"platform 'cpu' (jax_platforms={jax.config.jax_platforms!r}); "
            "refusing to serve from it unasked. Set "
            "AI4E_RUNTIME_PLATFORM=cpu for a CPU worker, or free the chip "
            "(one process holds it at a time).")


async def _close(resource) -> None:
    """``close()`` is a coroutine on the HTTP-backed task manager/store
    and a plain method on the in-memory ones."""
    closer = getattr(resource, "close", None)
    if closer is not None:
        result = closer()
        if inspect.isawaitable(result):
            await result


async def run_worker(config: FrameworkConfig, models: dict) -> None:
    from aiohttp import web

    from .observability import boot
    # Open since the process's own start, closed where the server accepts.
    boot.begin(models.get("service_name", "tpu-worker"))
    _claim_devices(config.runtime)
    worker, batcher, task_manager = build_worker(config, models)

    import jax
    if jax.process_count() > 1 and jax.process_index() != 0:
        # Follower host of a pod slice: no HTTP surface — mirror the
        # primary's batch executions until it shuts us down.
        log.info("follower %d/%d: entering mirror loop",
                 jax.process_index(), jax.process_count())
        boot.serving(worker.service.metrics)   # as far as a follower boots
        await asyncio.to_thread(worker.runtime.follower_loop)
        return

    await batcher.start()
    for engine in getattr(worker, "decode_engines", []):
        await engine.start()
    runner = web.AppRunner(worker.service.app)
    await runner.setup()
    site = web.TCPSite(runner, config.service.host, config.service.port)
    await site.start()
    boot.serving(worker.service.metrics)
    vitals = None
    if config.observability.vitals:
        # Same sampler as the control plane, in the worker's service
        # registry — loop lag here is what explains "the batch sat
        # ready while the loop was blocked".
        from .observability.vitals import VitalsSampler
        vitals = VitalsSampler(worker.service.metrics,
                               interval_s=config.observability
                               .vitals_interval)
        await vitals.start()
    from .runtime.registry import device_report
    device = device_report(worker.runtime.mesh)
    log.info("worker on %s:%s, device %s (%s) x%d, mesh %s, serving "
             "%s%s%s%s%s%s", config.service.host, config.service.port,
             # Device posture: what JAX says this process holds — the line
             # to read before believing any number the worker produces.
             device["platform"], device["device_kind"],
             device["device_count"],
             ",".join(f"{axis}={n}" for axis, n in device["mesh"].items()
                      if n > 1) or "dp=1",
             list(worker.runtime.models),
             # Mesh posture (docs/mesh_serving.md): the declared serving
             # layout doubles as the orchestration cost-tier label.
             (", mesh %s ON (tier %s)" % (
                 worker.runtime.layout.describe()["spec"],
                 worker.runtime.layout.tier_label)
              if hasattr(worker.runtime, "layout") else ""),
             ", vitals ON" if vitals is not None else "",
             # Device-path posture (docs/device_path.md): operators grep
             # these to confirm the traffic-tuned/overlapped hot path.
             ", ladder derivation ON" if batcher._ladders is not None
             else "",
             ", double-buffered transfers ON" if batcher._double else "",
             # Streaming posture (docs/streaming.md): the continuous-
             # batching decode engines this worker serves.
             (", streaming decode ON (%s)" % ", ".join(
                 e.backend.name
                 for e in getattr(worker, "decode_engines", []))
              if getattr(worker, "decode_engines", []) else ""))
    try:
        await _wait_for_termination()
    finally:
        if vitals is not None:
            await vitals.stop()
        await worker.service.drain(timeout=config.service.drain_timeout)
        await batcher.stop()
        for engine in getattr(worker, "decode_engines", []):
            await engine.stop()
        if jax.process_count() > 1:
            worker.runtime.shutdown_followers()
        if worker.service.reporter is not None:
            await worker.service.reporter.close()
        await _close(task_manager)
        await _close(worker.store)
        await runner.cleanup()


async def run_reporter(config: FrameworkConfig, port: int | None) -> None:
    """Standalone request-reporter node (the reference deploys it as its own
    function app, ``deploy_request_reporter_function.sh``)."""
    from aiohttp import web

    from .metrics import RequestReporterService

    svc = RequestReporterService()
    runner = web.AppRunner(svc.app)
    await runner.setup()
    site = web.TCPSite(runner, config.service.host, port or 8085)
    await site.start()
    log.info("request reporter on %s:%s", config.service.host, port or 8085)
    try:
        await _wait_for_termination()
    finally:
        await runner.cleanup()


async def _wait_for_termination() -> None:
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    log.info("termination signal; draining")


def main(argv=None) -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    parser = argparse.ArgumentParser(prog="ai4e_tpu")
    sub = parser.add_subparsers(dest="component", required=True)

    cp = sub.add_parser("control-plane",
                        help="gateway + task store + broker + dispatchers")
    cp.add_argument("--routes", required=True, help="routes.json path")
    cp.add_argument("--port", type=int, default=None)

    wk = sub.add_parser("worker", help="TPU inference worker")
    wk.add_argument("--models", required=True, help="models.json path")
    wk.add_argument("--port", type=int, default=None)

    rp = sub.add_parser("reporter",
                        help="cross-replica in-flight request reporter")
    rp.add_argument("--port", type=int, default=None)

    rd = sub.add_parser(
        "redrive",
        help="re-dispatch dead-lettered (or otherwise failed) tasks — the "
             "Service Bus Explorer resubmit workflow, against the store's "
             "ORIG replay")
    rd.add_argument("--store", default="http://127.0.0.1:8080",
                    help="control-plane URL (the task-store surface)")
    rd.add_argument("--task-id", default=None,
                    help="redrive ONE task (any failed state)")
    from .taskstore.task import TaskStatus as _TS
    rd.add_argument("--contains", default=_TS.DEAD_LETTER_PROSE,
                    help="sweep filter on the failed Status prose; '' "
                         "redrives every failed task")
    rd.add_argument("--api-key", default=None,
                    help="subscription key when the control plane runs "
                         "with gateway keys")

    tr = sub.add_parser(
        "trace",
        help="render task/request span trees from the JSONL trace log — "
             "the App Insights end-to-end transaction view, offline — "
             "or, with --url, a task's HOP LEDGER fetched live from the "
             "control plane (docs/observability.md)")
    tr.add_argument("--export", default=None,
                    help="span log path (default: the configured "
                         "AI4E_OBSERVABILITY_TRACE_EXPORT_PATH)")
    tr.add_argument("--url", default=None,
                    help="control-plane base URL: fetch the task's hop "
                         "ledger (GET /v1/taskmanagement/task/{id}"
                         "?ledger=1) instead of reading a span log; "
                         "requires --task-id")
    tr.add_argument("--api-key", default=None,
                    help="subscription key when the control plane runs "
                         "with gateway keys (--url mode)")
    tr_sel = tr.add_mutually_exclusive_group()
    tr_sel.add_argument("--task-id", default=None,
                        help="render every trace this task traversed")
    tr_sel.add_argument("--trace-id", default=None,
                        help="render one trace")
    tr.add_argument("--list", action="store_true", dest="list_traces",
                    help="summarize recent traces instead of rendering")
    tr.add_argument("--limit", type=int, default=20,
                    help="--list: how many recent traces")

    tp = sub.add_parser(
        "top",
        help="live fleet dashboard — per-proc req/s, goodput, SLO "
             "burn, event-loop lag, RSS from the federation snapshot "
             "(docs/observability.md)")
    tp.add_argument("--collector", default=None,
                    help="poll a collector's /v1/debug/fleet (the rig's "
                         "collector role)")
    tp.add_argument("--spec", default=None,
                    help="scrape a rig topology.json's roles directly")
    tp.add_argument("--targets", default=None,
                    help="ad-hoc name=url,name=url target list")
    tp.add_argument("--interval", type=float, default=2.0)
    tp.add_argument("--once", action="store_true",
                    help="print one frame and exit (scriptable)")

    tl = sub.add_parser(
        "timeline",
        help="export a rig run as ONE Chrome-trace/Perfetto JSON — hop "
             "ledgers, device phases, chaos verbs, vitals curves "
             "(load the output at https://ui.perfetto.dev)")
    tl.add_argument("--rig-dir", required=True,
                    help="rig artifact directory (rig.json + the "
                         "ledgers/vitals files the driver wrote)")
    tl.add_argument("--out", default=None,
                    help="output path (default <rig-dir>/timeline.json)")

    args = parser.parse_args(argv)

    if args.component == "top":
        # Pure fleet-snapshot client — no jax, no platform assembly.
        from .observability.top import run_top
        raise SystemExit(asyncio.run(run_top(
            collector=args.collector, spec=args.spec,
            targets=args.targets, interval=args.interval,
            once=args.once)))

    if args.component == "timeline":
        # Pure artifact transform — no jax, no platform assembly.
        import json as _json
        import os as _os

        from .observability.timeline import build_from_rig_dir
        if not _os.path.isdir(args.rig_dir):
            raise SystemExit(f"timeline: {args.rig_dir} is not a "
                             "directory (pass the rig artifact dir "
                             "`--out` wrote)")
        if not any(_os.path.exists(_os.path.join(args.rig_dir, f))
                   for f in ("rig.json", "ledgers.json")):
            raise SystemExit(f"timeline: {args.rig_dir} has neither "
                             "rig.json nor ledgers.json — not a rig "
                             "artifact directory")
        doc = build_from_rig_dir(args.rig_dir)
        out_path = args.out or _os.path.join(args.rig_dir,
                                             "timeline.json")
        with open(out_path, "w", encoding="utf-8") as fh:
            _json.dump(doc, fh)
        meta = doc["otherData"]
        print(f"wrote {out_path}: {len(doc['traceEvents'])} events, "
              f"{meta['tasks']} tasks, hops {meta['hops']}, "
              f"{len(meta['procs'])} procs — load it at "
              "https://ui.perfetto.dev")
        return

    if args.component == "trace":
        if args.url:
            # Live hop-ledger mode — pure HTTP client, no jax, no
            # assembly: one GET answers "where did this task's time go"
            # across every process it traversed.
            if not args.task_id:
                raise SystemExit("--url mode requires --task-id")
            import json as _json
            import urllib.error
            import urllib.request

            from .observability.ledger import render_ledger
            req = urllib.request.Request(
                args.url.rstrip("/")
                + f"/v1/taskmanagement/task/{args.task_id}?ledger=1",
                headers=({"Ocp-Apim-Subscription-Key": args.api_key}
                         if args.api_key else {}))
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    record = _json.loads(resp.read())
            except urllib.error.HTTPError as exc:
                raise SystemExit(
                    f"task fetch failed: HTTP {exc.code} "
                    f"{exc.read().decode(errors='replace')[:200]}")
            except OSError as exc:
                raise SystemExit(f"cannot reach {args.url}: {exc}")
            print(render_ledger(args.task_id, record.get("Ledger") or [],
                                status=record.get("Status")))
            return
        # Pure log reader — no jax, no platform assembly.
        from .observability.traceview import (load_spans, render_list,
                                              render_trace, select_traces)
        path = args.export
        if path is None:
            path = FrameworkConfig.from_env().observability.trace_export_path
        if not path:
            raise SystemExit(
                "no span log: pass --export or set "
                "AI4E_OBSERVABILITY_TRACE_EXPORT_PATH on the services")
        try:
            spans = load_spans(path)
        except OSError as exc:
            raise SystemExit(f"cannot read span log {path}: {exc}")
        selected = select_traces(spans, task_id=args.task_id,
                                 trace_id=args.trace_id)
        if not selected and (args.task_id or args.trace_id):
            # A filter that matches nothing must fail loudly in both
            # modes — an empty --list reading as "zero-span traces" would
            # mislead scripted callers.
            raise SystemExit("no matching spans")
        if args.list_traces:
            # --list composes with the filters: summarize the SELECTED
            # traces (all of them when no filter given).
            print(render_list(selected, limit=args.limit))
            return
        if not selected:
            raise SystemExit("no matching spans")
        print(render_trace(selected))
        return

    if args.component == "redrive":
        # Pure HTTP client — no jax, no platform assembly.
        import json as _json
        import sys
        import urllib.error
        import urllib.request

        if args.task_id:
            payload: dict = {"TaskId": args.task_id}
        else:
            payload = {"Contains": args.contains}
        req = urllib.request.Request(
            args.store.rstrip("/") + "/v1/taskstore/redrive",
            data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json",
                     **({"Ocp-Apim-Subscription-Key": args.api_key}
                        if args.api_key else {})},
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                print(resp.read().decode())
        except urllib.error.HTTPError as exc:
            detail = exc.read().decode()
            if exc.code == 409:
                # The store evaluated the redrive and refused it: the
                # task is not in a redrivable (dead-lettered) status.
                print("redrive refused (409): task is not in a "
                      "redrivable status", file=sys.stderr)
            elif exc.code == 503:
                after = exc.headers.get("Retry-After") if exc.headers else None
                print("store refused the redrive (503"
                      + (f", retry after {after}s" if after else "")
                      + ") — standby or degraded; retry against the "
                      "primary", file=sys.stderr)
            print(detail)
            raise SystemExit(1)
        except OSError as exc:  # URLError/TimeoutError are OSErrors
            raise SystemExit(f"cannot reach {args.store}: {exc}")
        return
    config = FrameworkConfig.from_env()
    config.observability.apply()

    if args.component == "control-plane":
        if args.port is not None:
            config.gateway.port = args.port
        asyncio.run(run_control_plane(config, load_spec(args.routes)))
    elif args.component == "worker":
        if args.port is not None:
            config.service.port = args.port
        asyncio.run(run_worker(config, load_spec(args.models)))
    elif args.component == "reporter":
        asyncio.run(run_reporter(config, args.port))


if __name__ == "__main__":
    main()
