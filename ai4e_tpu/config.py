"""Typed configuration with environment-variable overrides.

The reference configures everything through two untyped tiers — bash variables
in ``InfrastructureDeployment/setup_env.sh:1-82`` at deploy time, and raw
``getenv`` reads scattered through the code at runtime
(``APIs/1.0/base-py/ai4e_service.py:19-22``, ``APIs/1.0/Common/task_management/
distributed_api_task.py:14-15``, ``ProcessManager/Libraries/RedisConnection.cs:24-27``)
— with secrets pasted into Helm values files
(``APIs/Charts/camera-trap/detection-async/prod-values.yaml:41-46``).

Here the same two tiers are typed: dataclass sections with defaults (the
deploy-time tier) and an ``AI4E_<SECTION>_<FIELD>`` environment override for
every field (the runtime tier). Values are parsed per the field's declared
type, so a malformed override fails loudly at startup instead of deep inside a
request. No secret material is ever written by the framework; anything
secret-shaped stays an env var end to end.

Usage::

    cfg = FrameworkConfig.from_env()            # defaults + AI4E_* overrides
    cfg.observability.apply()                   # tracer sampling/export sink
    platform = LocalPlatform(cfg.to_platform_config())
"""

from __future__ import annotations

import dataclasses
import os
import typing
from dataclasses import dataclass, field, fields

_TRUE = frozenset({"1", "true", "yes", "on"})
_FALSE = frozenset({"0", "false", "no", "off", ""})

# Out-of-band AI4E_* namespaces, read directly by the paths that need them
# and never part of the typed config: AI4E_FAULT_* (fault injection, e.g.
# AI4E_FAULT_FETCH_FAIL_NTHS), AI4E_CHAOS_* (chaos-harness seeds,
# tests/test_chaos.py), AI4E_FEED_* (the multihost shard feed's direct
# knobs, e.g. AI4E_FEED_ADVERTISE_IP in parallel/multihost.py — previously
# REJECTED by from_env, so a multihost deployment pinning its feed IP
# could not boot; AIL006 surfaced the drift), AI4E_TASKSTORE_* (the
# journal's durability knobs, e.g. AI4E_TASKSTORE_FSYNC read by
# taskstore/journal.py at store construction — a storage-layer policy any
# journal-bearing process honors, whether or not it builds a typed
# FrameworkConfig), AI4E_RIG_* (the multi-process deployment rig's
# driver-side knobs, e.g. AI4E_RIG_BASE_PORT read by ai4e_tpu/rig/ — rig
# child processes are configured by the resolved topology spec file, not
# env). Single source of truth — FrameworkConfig.from_env exempts these
# from its unknown-variable check and the AIL006 config-drift rule
# imports the same tuple. All five are documented in docs/config.md.
OUT_OF_BAND_ENV_PREFIXES = ("AI4E_FAULT_", "AI4E_CHAOS_", "AI4E_FEED_",
                            "AI4E_TASKSTORE_", "AI4E_RIG_")


class ConfigError(ValueError):
    pass


# Where the compile cache goes when nobody outside the program said: one
# fixed, git-ignored directory at the checkout root — no pid, timestamp or
# temp name, so every process of every run resolves the same path.
_CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """The persistent XLA compile cache directory: ``JAX_COMPILATION_
    CACHE_DIR`` where the environment sets it (JAX reads that variable
    itself — the program then sets no directory in code), else the fixed
    in-checkout path. No JAX import: launch scripts resolve it too."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_COMPILE_CACHE


def _parse(raw: str, typ, name: str):
    """Parse an env string per the declared field type."""
    origin = typing.get_origin(typ)
    if origin is typing.Union:  # Optional[X] — "" means None
        args = [a for a in typing.get_args(typ) if a is not type(None)]
        if raw == "":
            return None
        return _parse(raw, args[0], name)
    if typ is bool:
        low = raw.strip().lower()
        if low in _TRUE:
            return True
        if low in _FALSE:
            return False
        raise ConfigError(f"{name}: {raw!r} is not a boolean")
    if typ is int:
        try:
            return int(raw)
        except ValueError as e:
            raise ConfigError(f"{name}: {raw!r} is not an int") from e
    if typ is float:
        try:
            return float(raw)
        except ValueError as e:
            raise ConfigError(f"{name}: {raw!r} is not a float") from e
    if origin in (tuple, list):
        item_t = (typing.get_args(typ) or (str,))[0]
        if item_t is Ellipsis:
            item_t = str
        items = [s.strip() for s in raw.split(",") if s.strip()]
        parsed = [_parse(s, item_t, name) for s in items]
        return tuple(parsed) if origin is tuple else parsed
    return raw


def section_from_env(cls, env: typing.Mapping[str, str] | None = None,
                     prefix: str = "AI4E_", **overrides):
    """Build a config dataclass from defaults + ``{prefix}{FIELD}`` env vars.

    Explicit ``overrides`` win over env, env wins over defaults — the same
    precedence the reference gets from Helm values overriding chart defaults
    (``APIs/Charts/templates/async-gpu/templates/deployment.yaml:23-63``).
    """
    env = os.environ if env is None else env
    kwargs = {}
    hints = typing.get_type_hints(cls)
    known = {prefix + f.name.upper(): f.name for f in fields(cls)}
    for key, name in known.items():
        if name in overrides:
            kwargs[name] = overrides[name]
        elif key in env:
            kwargs[name] = _parse(env[key], hints[name], key)
    # A prefixed-but-unknown variable is a misspelled field, the most common
    # operator error — fail loudly instead of silently keeping the default.
    unknown = [k for k in env if k.startswith(prefix) and k not in known]
    if unknown:
        raise ConfigError(
            f"unknown config variable(s) {sorted(unknown)}; "
            f"valid: {sorted(known)}")
    return cls(**kwargs)


def _env_section(prefix: str):
    """Class decorator: attach ``from_env`` with the section's prefix."""
    def deco(cls):
        cls = dataclass(cls)
        cls._env_prefix = prefix

        def from_env(inner_cls, env=None, **overrides):
            return section_from_env(inner_cls, env=env, prefix=prefix,
                                    **overrides)

        cls.from_env = classmethod(from_env)
        return cls
    return deco


@_env_section("AI4E_PLATFORM_")
class PlatformSection:
    """Transport/task-fabric knobs (setup_env.sh:65-74 tier)."""
    transport: str = "queue"         # TRANSPORT_TYPE (setup_env.sh:11): queue | push
    retry_delay: float = 60.0        # dispatcher backoff on 429/503 (s)
    max_delivery_count: int = 1440   # broker patience (setup_env.sh:65)
    dispatcher_concurrency: int = 1  # serial per queue (host.json:5-9)
    journal_path: typing.Optional[str] = None
    lease_seconds: float = 300.0
    native_broker: bool = False
    native_store: bool = False
    push_ttl_seconds: float = 300.0  # event TTL 5 min (deploy_event_grid_subscription.sh:37)
    push_max_attempts: int = 3       # max delivery attempts (same line)
    push_window: int = 256           # concurrent in-flight deliveries
    # Stuck-task watchdog (taskstore/reaper.py): rescue tasks stuck in
    # "running" after a worker died post-adoption. None disables.
    reaper_running_timeout: typing.Optional[float] = None
    reaper_interval: float = 30.0
    reaper_max_requeues: int = 3
    # Terminal-history retention (s): evict completed/failed tasks older
    # than this — the memory bound a sustained-traffic control plane needs
    # (a 20-min 200 req/s soak grew an unevicted store ~12 MB/min). Unset
    # = AUTO: 15 min on the Python store (bounds that workload's steady
    # state at ~180 MB), off on the native store (which has no eviction).
    # 0 = evict terminal tasks immediately; negative = keep forever.
    reaper_terminal_retention: typing.Optional[float] = None
    # Object-store result offload (assign_storage_auth_to_aks.sh:9-17 slot):
    # results >= threshold bytes land under result_dir instead of store memory.
    result_dir: typing.Optional[str] = None
    result_offload_threshold: int = 1048576
    # Control-plane HA (taskstore/replication.py): primary URL to replicate
    # from — set on the STANDBY replica (requires journal_path); a watchdog
    # promotes it when the primary dies.
    replicate_from: typing.Optional[str] = None
    failover_interval: float = 2.0
    failover_down_after: int = 3
    # Subscription key for the primary's keyed control-plane port (the
    # journal stream rides behind the gateway key middleware).
    replicate_api_key: typing.Optional[str] = None
    # This node's control-plane URL as peers reach it — after a promotion
    # the fencing prober sends it in demote calls so the deposed primary
    # rejoins the new primary automatically (split-brain fencing).
    advertise_url: typing.Optional[str] = None
    # Inference result cache + single-flight coalescing (docs/rescache.md).
    # Off by default: enabling is a semantic statement that identical
    # payloads may share results; per-request opt-out via X-Cache-Bypass.
    result_cache: bool = False
    cache_max_entries: int = 4096
    cache_max_bytes: int = 268435456          # 256 MiB resident payloads
    cache_ttl_seconds: typing.Optional[float] = 300.0
    # Admission control (docs/admission.md): deadline propagation
    # (X-Deadline-Ms/X-Priority), priority shedding with computed
    # Retry-After, adaptive gateway-sync/dispatcher concurrency. Off by
    # default: enabling it means the platform may refuse or expire work
    # (terminal `expired` status) instead of serving arbitrarily late.
    admission: bool = False
    admission_min_limit: int = 1
    admission_max_limit: int = 256
    admission_initial_limit: int = 8
    admission_max_backlog: int = 1024
    # Resilient routing (docs/resilience.md): per-backend circuit breakers
    # shared by the sync proxy and every dispatcher, health-aware weighted
    # picks (open backends ejected), budget-bounded retries with failover
    # on connection error, 5xx treated as transient (redelivered). Off by
    # default: enabling it changes failure semantics — a 5xx is no longer
    # instantly terminal.
    resilience: bool = False
    resilience_failure_threshold: int = 5
    resilience_window: int = 16
    resilience_error_rate: float = 0.5
    resilience_recovery_seconds: float = 30.0
    resilience_max_attempts: int = 3
    resilience_retry_base_s: float = 0.05
    resilience_retry_budget_ratio: float = 0.2
    # Deadline-aware orchestration (docs/orchestration.md): per-request
    # placement across unequal backends on predicted completion-within-
    # deadline, the brownout degradation ladder, and predictive
    # autoscaling. Requires admission AND resilience (it composes their
    # signals).
    orchestration: bool = False
    orchestration_confidence: float = 0.75
    orchestration_window: int = 256
    orchestration_horizon_s: float = 60.0
    # "substring=cost,..." per-backend relative cost (first match wins;
    # unmatched backends cost 1.0).
    orchestration_costs: typing.Optional[str] = None
    orchestration_ladder_up: float = 0.3
    orchestration_ladder_down: float = 0.1
    orchestration_ladder_hold_s: float = 5.0
    orchestration_scale_horizon_s: float = 10.0
    # Sharded task store (docs/sharding.md): N independent shards over a
    # consistent-hash slot ring, each with its own journal, passive
    # replicas, and epoch-fenced failover. 1 = today's single store.
    task_shards: int = 1
    task_shard_slots: int = 64
    task_shard_replicas: int = 1
    shard_tail_interval: float = 0.25
    shard_feed_recent: int = 4096
    # Request observability (docs/observability.md): per-task hop
    # ledger, tail-sampled flight recorder (GET /v1/debug/flight), and
    # per-route e2e latency/outcome telemetry. Off = byte-identical
    # assembly.
    observability: bool = False
    flight_capacity: int = 512
    flight_sample: float = 0.05
    flight_slow_ms: float = 1000.0
    # Per-route SLO objectives + multi-window burn-rate engine
    # (observability/slo.py): "/route=<latency_ms>:<target_pct>" or
    # "/route=goodput:<target_pct>", comma-separated. Requires
    # observability (the engine reads its histograms). Unset = no
    # engine.
    slo_objectives: typing.Optional[str] = None
    slo_tick_s: float = 5.0
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    # Feed sustained SLO breaches to the degradation ladder as an extra
    # miss-evidence source (requires orchestration).
    slo_ladder: bool = False
    # First-class pipeline DAGs (docs/pipelines.md): declared multi-stage
    # compositions executed under one TaskId by the coordinator, plus the
    # SSE streaming surface GET /v1/taskmanagement/task/{id}/events.
    # Requires the Python store/broker + queue transport. Off =
    # byte-identical assembly.
    pipeline: bool = False
    # Per-task event replay buffer for late-attaching streams, and the
    # maximum SSE stream duration per request (seconds).
    pipeline_event_replay: int = 256
    pipeline_stream_max_s: float = 300.0
    # Separate bound for CHUNK events (token streams): a late attacher
    # replays at most this many trailing chunks, older ones are dropped
    # with a single `truncated` marker — a slow client must never hold
    # unbounded token history (docs/streaming.md).
    pipeline_chunk_replay: int = 128

    def to_platform_config(self):
        from .platform_assembly import PlatformConfig
        return PlatformConfig(
            transport=self.transport,
            retry_delay=self.retry_delay,
            max_delivery_count=self.max_delivery_count,
            dispatcher_concurrency=self.dispatcher_concurrency,
            journal_path=self.journal_path,
            lease_seconds=self.lease_seconds,
            native_broker=self.native_broker,
            native_store=self.native_store,
            push_ttl_seconds=self.push_ttl_seconds,
            push_max_attempts=self.push_max_attempts,
            push_window=self.push_window,
            reaper_running_timeout=self.reaper_running_timeout,
            reaper_interval=self.reaper_interval,
            reaper_max_requeues=self.reaper_max_requeues,
            reaper_terminal_retention=self.reaper_terminal_retention,
            result_dir=self.result_dir,
            result_offload_threshold=self.result_offload_threshold,
            replicate_from=self.replicate_from,
            failover_interval=self.failover_interval,
            failover_down_after=self.failover_down_after,
            replicate_api_key=next(
                (k.strip() for k in (self.replicate_api_key or "").split(",")
                 if k.strip()), None),
            advertise_url=self.advertise_url,
            result_cache=self.result_cache,
            cache_max_entries=self.cache_max_entries,
            cache_max_bytes=self.cache_max_bytes,
            cache_ttl_seconds=self.cache_ttl_seconds,
            admission=self.admission,
            admission_min_limit=self.admission_min_limit,
            admission_max_limit=self.admission_max_limit,
            admission_initial_limit=self.admission_initial_limit,
            admission_max_backlog=self.admission_max_backlog,
            resilience=self.resilience,
            resilience_failure_threshold=self.resilience_failure_threshold,
            resilience_window=self.resilience_window,
            resilience_error_rate=self.resilience_error_rate,
            resilience_recovery_seconds=self.resilience_recovery_seconds,
            resilience_max_attempts=self.resilience_max_attempts,
            resilience_retry_base_s=self.resilience_retry_base_s,
            resilience_retry_budget_ratio=self.resilience_retry_budget_ratio,
            orchestration=self.orchestration,
            orchestration_confidence=self.orchestration_confidence,
            orchestration_window=self.orchestration_window,
            orchestration_horizon_s=self.orchestration_horizon_s,
            orchestration_costs=self.orchestration_costs,
            orchestration_ladder_up=self.orchestration_ladder_up,
            orchestration_ladder_down=self.orchestration_ladder_down,
            orchestration_ladder_hold_s=self.orchestration_ladder_hold_s,
            orchestration_scale_horizon_s=self.orchestration_scale_horizon_s,
            task_shards=self.task_shards,
            task_shard_slots=self.task_shard_slots,
            task_shard_replicas=self.task_shard_replicas,
            shard_tail_interval=self.shard_tail_interval,
            shard_feed_recent=self.shard_feed_recent,
            observability=self.observability,
            flight_capacity=self.flight_capacity,
            flight_sample=self.flight_sample,
            flight_slow_ms=self.flight_slow_ms,
            slo_objectives=self.slo_objectives,
            slo_tick_s=self.slo_tick_s,
            slo_fast_window_s=self.slo_fast_window_s,
            slo_slow_window_s=self.slo_slow_window_s,
            slo_ladder=self.slo_ladder,
            pipeline=self.pipeline,
            pipeline_event_replay=self.pipeline_event_replay,
            pipeline_stream_max_s=self.pipeline_stream_max_s,
            pipeline_chunk_replay=self.pipeline_chunk_replay,
        )


@_env_section("AI4E_SERVICE_")
class ServiceSection:
    """In-container service shell knobs (ai4e_service.py:19-22 tier)."""
    host: str = "0.0.0.0"
    port: int = 8081
    executor_workers: int = 8
    drain_timeout: float = 30.0
    # Cross-replica in-flight reporter (REQUEST_REPORTER_URI +
    # SERVICE_CLUSTER in ai4e_service.py:21,135-146); None disables.
    reporter_uri: typing.Optional[str] = None
    cluster: str = "local"
    # Subscription key the worker attaches to task-store calls when the
    # control plane runs with gateway api_keys (same secret).
    taskstore_api_key: typing.Optional[str] = None
    # Direct-to-storage results: large outputs write to this shared mount
    # (the SAME root the control plane serves via AI4E_PLATFORM_RESULT_DIR)
    # and only a pointer registration crosses the control network.
    result_dir: typing.Optional[str] = None
    result_offload_threshold: int = 1048576


@_env_section("AI4E_RUNTIME_")
class RuntimeSection:
    """TPU runtime knobs — no reference analogue (containers were opaque)."""
    platform: typing.Optional[str] = None  # pin jax_platforms (e.g. "cpu")
    batch_max_wait_ms: float = 5.0
    batch_max_pending: int = 256
    # In-flight device batches (MicroBatcher pipeline window). 2 = double
    # buffering: one batch transfers while one executes. Not re-measured
    # on a locally attached chip.
    batch_pipeline_depth: int = 2
    # Priority-class batching (batch-API stacks run at background priority):
    # fraction of batch_max_pending reserved for interactive admissions, and
    # the seconds of waiting that promote a background item one class
    # (0 = strict priority).
    batch_interactive_reserve: float = 0.25
    batch_priority_aging_s: float = 2.0
    # Double-buffered device transfers (docs/device_path.md): h2d/execute/
    # d2h on dedicated threads with an alternating staging-buffer ring so
    # batch N+1's device_put overlaps batch N's execute. Off = the fused
    # single-executor path, byte-identical to the pre-double-buffer worker.
    batch_double_buffer: bool = False
    # Traffic-tuned bucket ladders (runtime/ladder.py, docs/device_path.md):
    # derive each servable's batch buckets from the live cut-size histogram,
    # AOT-compile in the background, swap atomically, persist beside the
    # compile cache. Off = static factory ladders, byte-identical batch
    # path and /metrics.
    ladder_derive: bool = False
    ladder_window_s: float = 300.0       # histogram decay half-life
    ladder_max_programs: int = 16        # compiled-programs budget per model
    ladder_period_s: float = 60.0        # re-derive cadence per model
    ladder_dwell_s: float = 120.0        # min seconds between ladder swaps
    # Persisted derived-ladder file; unset = ladders.json in
    # compile_cache_dir() (beside the persistent compilation cache, so a
    # restart AOT-warms the traffic-tuned ladder).
    ladder_path: typing.Optional[str] = None
    buckets: typing.Tuple[int, ...] = (1, 8, 32, 64)
    # Continuous-batching decode engine (runtime/decode.py,
    # docs/streaming.md): iteration-level scheduling over a KV-cache
    # slot pool with per-token `chunk` streaming. Off = the engine is
    # never constructed — the batch path and /metrics exposition are
    # byte-identical to the decode-less worker.
    decode_enable: bool = False
    decode_max_pending: int = 64       # queued streams before 503
    # Prompt-padding bucket ladder; empty = the factory
    # ladder.DECODE_PROMPT_BUCKETS (the KV length is always appended as
    # the covering top bucket).
    decode_prompt_buckets: typing.Tuple[int, ...] = ()
    # KV-cache slot-pool geometry (runtime/kvcache.py): concurrent
    # decoding sequences per model, and the per-slot cache length
    # (prompt + generated tokens must fit under it).
    kv_slots: int = 8
    kv_max_len: int = 256
    checkpoint_dir: typing.Optional[str] = None
    donate_batch: bool = False
    # mesh axes; 0 = infer from device count
    dp: int = 0
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    # Mesh serving plane (runtime/mesh/, docs/mesh_serving.md): the
    # declarative serving-mesh spec — "dp=8", "dp=2,tp=2", optionally
    # ",sp=N" — validated at boot and exposed on GET /v1/models. Empty =
    # mesh serving off (byte-identical worker); mutually exclusive with
    # the low-level dp/fsdp/tp/sp/ep axis knobs above.
    mesh_spec: str = ""
    # Consecutive poisoned batches attributed to one mesh process before
    # the endpoint flips unhealthy (admission answers 500; breakers
    # eject it). One clean batch marks it healthy again.
    mesh_unhealthy_after: int = 3


@_env_section("AI4E_GATEWAY_")
class GatewaySection:
    """Edge router knobs (APIManagement tier). The upsert/get URIs are the
    CACHE_CONNECTOR_UPSERT_URI / _GET_URI pattern (distributed_api_task.py:14-15)."""
    host: str = "0.0.0.0"
    port: int = 8080
    taskstore_upsert_uri: typing.Optional[str] = None
    taskstore_get_uri: typing.Optional[str] = None
    # Comma-separated subscription keys; set → every published API and
    # /v1/taskmanagement call must carry one (Ocp-Apim-Subscription-Key or
    # X-Api-Key header) — the reference's APIM front-door contract.
    api_keys: typing.Optional[str] = None
    # Edge payload cap (bytes) for published APIs: oversized POSTs are
    # refused with 413 before any task/ORIG body is stored. 0 = unlimited.
    max_body_bytes: int = 134217728
    # Separate cap for result uploads on the task-store surface — batch
    # results are routinely larger than request bodies. 0 = unlimited.
    max_result_bytes: int = 1073741824
    # Per-key request-rate throttle on the published surface (the APIM
    # product-throttling slot). 0 disables; burst 0 → 2×rps.
    rate_limit_rps: float = 0.0
    rate_limit_burst: float = 0.0
    # Per-key overrides: "key=rps[:burst],..." (gateway/ratelimit.py).
    rate_limits: typing.Optional[str] = None
    # Per-key request QUOTA (APIM product quota; 403 on exhaustion):
    # default "N[/window_seconds]" (bare N = per hour); empty disables.
    quota: typing.Optional[str] = None
    # Per-key overrides: "key=N[/window_seconds],...".
    quotas: typing.Optional[str] = None


@_env_section("AI4E_OBSERVABILITY_")
class ObservabilitySection:
    """Tracing/metrics knobs (OCAGENT_TRACE_EXPORTER_ENDPOINT analogue,
    prod-values.yaml:29)."""
    trace_enabled: bool = True
    trace_sample_rate: float = 1.0   # App Insights sampled 50 items/s (host.json:5-8)
    trace_export_path: typing.Optional[str] = None  # JSONL span log; None → log only
    # OTLP/HTTP traces URL of a collector (deploy/charts/otel-collector.yaml
    # serves http://ai4e-otel-collector:4318/v1/traces) — the deployable
    # span sink, parity with the reference's Istio→App Insights adapter.
    trace_otlp_endpoint: typing.Optional[str] = None
    queue_depth_interval: float = 30.0      # TaskQueueLogger.cs:19 (30 s)
    process_depth_interval: float = 300.0   # TaskProcessLogger.cs:21 (5 min)
    # Per-process runtime vitals (observability/vitals.py): event-loop
    # lag, GC pauses, RSS/CPU/fd/steal from /proc, exported as
    # ai4e_process_* in the process's own registry. Started by the CLI
    # launchers (control-plane AND worker); rig roles always sample.
    # Off = no sampler task, no series — the launcher is byte-identical.
    vitals: bool = False
    vitals_interval: float = 1.0
    # Worker-side hop-ledger participation (docs/observability.md): the
    # batcher measures device phases (h2d/compile/execute/d2h + overlap
    # ratio) and the worker flushes each request's timeline to the task
    # store — pair with AI4E_PLATFORM_OBSERVABILITY on the control
    # plane for the full cross-process ledger. Off = the pre-ledger
    # worker byte for byte.
    hop_ledger: bool = False

    def apply(self) -> None:
        """Install these settings on the process tracer (components without
        explicit tracer settings follow it live)."""
        from .observability import (FanoutExporter, JsonlExporter,
                                    configure_tracer)
        rate = self.trace_sample_rate if self.trace_enabled else 0.0
        exporters = []
        if self.trace_export_path:
            exporters.append(JsonlExporter(self.trace_export_path))
        if self.trace_otlp_endpoint:
            from .observability.otlp import OtlpHttpExporter
            exporters.append(OtlpHttpExporter(self.trace_otlp_endpoint))
        exporter = None
        if len(exporters) == 1:
            exporter = exporters[0]
        elif exporters:
            exporter = FanoutExporter(exporters)
        if exporter is not None and hasattr(exporter, "close"):
            # Flush buffered spans at process exit (the OTLP exporter holds
            # up to flush_interval of them) — the shutdown-time spans are
            # usually the interesting ones.
            import atexit
            atexit.register(exporter.close)
        configure_tracer(exporter=exporter, sample_rate=rate)


@_env_section("AI4E_TENANCY_")
class TenancySection:
    """Multi-tenancy knobs (tenancy/, docs/tenancy.md) — the analogue of
    the reference's per-product APIM subscription policy (rate + quota per
    product, ``create_async_api_management_api.sh:52-80``), plus the
    scheduler-share weight APIM never had."""
    # Master switch → PlatformConfig.tenancy.
    enabled: bool = False
    # Tenant spec "name=key1|key2[:weight[:rps[:burst]]]" comma-separated
    # (tenancy/registry.py parse_tenants).
    tenants: typing.Optional[str] = None
    # Defaults for omitted spec fields AND the default tenant's own policy
    # (rps 0 = unlimited).
    default_weight: float = 1.0
    default_rps: float = 0.0
    default_burst: float = 0.0
    # Bounded metric-label cardinality: first N declared tenants keep
    # their id, the rest collapse into "other" (AIL013's blessed mapper).
    label_top_n: int = 8
    # Goodput target the per-tenant SLO-burn gauge normalizes against.
    goodput_target: float = 0.99
    # Floor on a lane's DRR credit per ring visit.
    min_quantum: float = 0.05


@_env_section("AI4E_ROLLOUT_")
class RolloutSection:
    """Zero-downtime rollout knobs (rollout/, docs/deployment.md#rollouts):
    the drain budget the worker's drain verb enforces and the canary
    ladder/burn bars the rollout controller promotes against."""
    # Per-worker graceful-drain budget: in-flight device batches, active
    # decode sequences and in-flight reloads get this long to finish
    # before stragglers are force-retired (each redelivers per task).
    drain_timeout_ms: float = 30000.0
    # Canary traffic-share ladder in percent, increasing, ending at 100
    # (rollout/controller.parse_steps).
    canary_steps: str = "25,50,100"
    # Clean fast+slow burn window held at each ladder step before
    # promoting to the next.
    step_hold_s: float = 10.0
    # Burn/breaker sampling period inside a hold.
    guard_tick_s: float = 1.0
    # Burn bars: roll back only when BOTH windows breach (the SLO
    # engine's multi-window page shape, observability/slo.py).
    burn_fast_max: float = 1.0
    burn_slow_max: float = 1.0
    # How long a drain-marked backend stays ejected from placement per
    # X-Draining observation (resilience/health.mark_draining).
    drain_eject_ttl_s: float = 30.0
    # The deploy generation this process serves (registry's
    # ServableModel.generation default for reloads that don't name one).
    generation: int = 0


@dataclass
class FrameworkConfig:
    """The whole platform's config tree."""
    platform: PlatformSection = field(default_factory=PlatformSection)
    service: ServiceSection = field(default_factory=ServiceSection)
    runtime: RuntimeSection = field(default_factory=RuntimeSection)
    gateway: GatewaySection = field(default_factory=GatewaySection)
    observability: ObservabilitySection = field(
        default_factory=ObservabilitySection)
    tenancy: TenancySection = field(default_factory=TenancySection)
    rollout: RolloutSection = field(default_factory=RolloutSection)

    @classmethod
    def from_env(cls, env: typing.Mapping[str, str] | None = None
                 ) -> "FrameworkConfig":
        hints = typing.get_type_hints(cls)
        sections = {f.name: hints[f.name] for f in fields(cls)}
        # Per-section checks only catch misspelled *fields*; a misspelled
        # *section* ("AI4E_OBSERVABILTY_...") matches no section prefix and
        # would silently keep every default — catch it here.
        env_map = os.environ if env is None else env
        prefixes = tuple(s._env_prefix for s in sections.values())
        unknown = [k for k in env_map
                   if k.startswith("AI4E_") and not k.startswith(prefixes)
                   and not k.startswith(OUT_OF_BAND_ENV_PREFIXES)]
        if unknown:
            raise ConfigError(
                f"unknown config section in variable(s) {sorted(unknown)}; "
                f"valid section prefixes: {sorted(prefixes)}")
        return cls(**{name: sec.from_env(env)
                      for name, sec in sections.items()})

    def to_platform_config(self):
        """The fully-wired ``PlatformConfig``: transport knobs from the
        platform section, depth-logger intervals from observability."""
        pc = self.platform.to_platform_config()
        pc.queue_depth_interval = self.observability.queue_depth_interval
        pc.process_depth_interval = self.observability.process_depth_interval
        pc.tenancy = self.tenancy.enabled
        pc.tenancy_tenants = self.tenancy.tenants
        pc.tenancy_default_weight = self.tenancy.default_weight
        pc.tenancy_default_rps = self.tenancy.default_rps
        pc.tenancy_default_burst = self.tenancy.default_burst
        pc.tenancy_label_top_n = self.tenancy.label_top_n
        pc.tenancy_goodput_target = self.tenancy.goodput_target
        pc.tenancy_min_quantum = self.tenancy.min_quantum
        pc.rollout_drain_eject_ttl_s = self.rollout.drain_eject_ttl_s
        return pc

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)
