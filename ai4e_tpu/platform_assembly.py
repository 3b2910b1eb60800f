"""Single-process platform assembly — store + broker + dispatchers + gateway.

The reference wires its components together with 15 bash deployment scripts
(``InfrastructureDeployment/deploy_infrastructure.sh:5-38``); this module is
the same wiring as code, used by tests, local development, and single-host
deployments. Multi-host deployments run the pieces separately (taskstore HTTP
service + broker + gateway) — see ``deploy/``.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass

from .broker import DispatcherPool, InMemoryBroker
from .gateway import Gateway
from .metrics import DEFAULT_REGISTRY, MetricsRegistry
from .service import APIService, LocalTaskManager
from .utils.backends import Weighted, normalize_backends
from .taskstore import InMemoryTaskStore, TaskStatus, endpoint_path


@dataclass
class PlatformConfig:
    transport: str = "queue"        # "queue" | "push" (setup_env.sh:11 TRANSPORT_TYPE)
    retry_delay: float = 60.0       # dispatcher backoff on 429/503 (setup_env.sh:74)
    max_delivery_count: int = 1440  # broker patience (setup_env.sh:65)
    dispatcher_concurrency: int = 1  # serial per queue (host.json:5-9)
    journal_path: str | None = None  # None → pure in-memory store
    lease_seconds: float = 300.0
    native_broker: bool = False      # C++ broker core (native/broker_core.cpp)
    native_store: bool = False       # C++ task-store core (native/taskstore_core.cpp)
    queue_depth_interval: float = 30.0    # TaskQueueLogger.cs:19
    process_depth_interval: float = 300.0  # TaskProcessLogger.cs:21
    # push-transport delivery policy (deploy_event_grid_subscription.sh:37)
    push_ttl_seconds: float = 300.0
    push_max_attempts: int = 3
    push_window: int = 256          # concurrent in-flight deliveries
    # stuck-task watchdog (taskstore/reaper.py); None disables
    reaper_running_timeout: float | None = None
    reaper_interval: float = 30.0
    reaper_max_requeues: int = 3
    # Terminal-history retention (seconds): completed/failed tasks older
    # than this are evicted (memory + journal bound); None keeps forever.
    # None = AUTO (15 min on the Python store, off on the native store);
    # >=0 = explicit retention seconds (0 = evict terminal tasks
    # immediately, the pre-r5 meaning, preserved); < 0 = explicitly keep
    # history forever.
    reaper_terminal_retention: float | None = None
    # Object-store slot for large results (assign_storage_auth_to_aks.sh:9-17):
    # results >= the threshold are written under result_dir (a local dir, PD,
    # or GCS FUSE mount) instead of store memory. None dir disables offload.
    result_dir: str | None = None
    result_offload_threshold: int = 1024 * 1024
    # Control-plane HA (taskstore/replication.py): when set, this platform
    # boots as a STANDBY — its store is a FollowerTaskStore tailing the
    # primary's journal stream at this URL; a watchdog promotes it (and
    # starts transport + re-seeds dispatch) when the primary dies. Requires
    # journal_path. The availability slot managed Redis filled for the
    # reference (deploy_cache_prerequisites.sh:15-31).
    replicate_from: str | None = None
    failover_interval: float = 2.0
    failover_down_after: int = 3
    # Subscription key for the journal stream when the primary's control
    # plane runs keyed (the task-store surface rides the gateway app behind
    # the key middleware — an unkeyed replicator would 401 forever and the
    # standby would never sync).
    replicate_api_key: str | None = None
    # This node's control-plane URL as PEERS reach it. After a promotion
    # the fencing prober includes it in demote calls so the deposed
    # primary's platform rejoins the new primary as a follower
    # automatically; unset, deposed peers are fenced (writes refused) but
    # must be re-seeded by the deployment.
    advertise_url: str | None = None
    # Inference result cache + single-flight coalescing (rescache/): the
    # gateway answers repeat requests without dispatching, concurrent
    # identical requests share ONE execution, and dispatchers complete
    # redeliveries from the cache. Off by default — enabling it is a
    # semantic statement that identical payloads may share results
    # (docs/rescache.md; per-request opt-out via X-Cache-Bypass).
    result_cache: bool = False
    cache_max_entries: int = 4096
    cache_max_bytes: int = 256 * 1024 * 1024
    # Entry lifetime bound. In a single-process deployment the reload hook
    # invalidates synchronously; TTL is the staleness backstop for caches
    # that a remote worker's reload cannot reach. None = no TTL.
    cache_ttl_seconds: float | None = 300.0
    # Admission control (admission/, docs/admission.md): end-to-end
    # deadline propagation (X-Deadline-Ms / X-Priority / X-Shed-Reason),
    # priority load shedding with drain-rate-derived Retry-After, and an
    # adaptive (gradient/AIMD) concurrency limit replacing the fixed
    # gateway sync cap and dispatcher fan-out. Off by default — enabling
    # it is a semantic statement that the platform may refuse or expire
    # work (terminal `expired` status) instead of carrying every request
    # to completion however late.
    admission: bool = False
    admission_min_limit: int = 1
    admission_max_limit: int = 256
    admission_initial_limit: int = 8
    # Async-edge backlog capacity the priority shedder fractions divide
    # (created-set depth per route; background sheds first at 60%).
    admission_max_backlog: int = 1024
    # Resilient routing under failure (resilience/, docs/resilience.md):
    # a per-backend circuit breaker shared by the gateway sync proxy and
    # every dispatcher (open backends ejected from weighted picks, their
    # weight redistributed; half-open probes re-admit them), plus
    # budget-bounded in-delivery retries with failover to a different
    # backend on connection error and 5xx-as-transient redelivery. Off by
    # default — enabling it is a semantic statement that 5xx responses
    # are transient (retried/redelivered, not instantly terminal) and
    # that redeliveries of already-terminal tasks are suppressed.
    resilience: bool = False
    resilience_failure_threshold: int = 5   # consecutive failures to trip
    resilience_window: int = 16             # rolling error-rate window
    resilience_error_rate: float = 0.5      # window fraction that trips
    resilience_recovery_seconds: float = 30.0  # open → half-open cooldown
    resilience_max_attempts: int = 3        # POST attempts per delivery
    resilience_retry_base_s: float = 0.05   # first in-delivery retry delay
    resilience_retry_budget_ratio: float = 0.2  # retries per request, steady
    # Deadline-aware orchestration over unequal backends (orchestration/,
    # docs/orchestration.md): per-request placement on predicted
    # completion-within-deadline and per-backend cost (replacing the
    # health-weighted random pick in the dispatchers and sync proxy), the
    # brownout degradation ladder consulted by the admission shedder, and
    # predictive autoscaling (arrival/drain projection instead of raw
    # depth; per-shard decisions through one actuator on sharded routes).
    # Off by default — enabling it is a semantic statement that backends
    # are UNEQUAL (placement prefers cheap tiers that clear the deadline
    # bar) and that sustained predicted-miss pressure may brown the
    # platform out class by class. Requires admission AND resilience —
    # it composes their signals rather than inventing new ones.
    orchestration: bool = False
    orchestration_confidence: float = 0.75   # p_within bar a backend clears
    orchestration_window: int = 256          # RTT samples per backend sketch
    orchestration_horizon_s: float = 60.0    # sample decay horizon (s)
    # "substring=cost,..." relative backend cost (first match wins,
    # unmatched = 1.0) — e.g. "tpu=3,cpu-fallback=1,remote=5".
    orchestration_costs: str | None = None
    orchestration_ladder_up: float = 0.3     # pressure that steps the ladder up
    orchestration_ladder_down: float = 0.1   # pressure that steps it down
    orchestration_ladder_hold_s: float = 5.0  # sustain per step (hysteresis)
    orchestration_scale_horizon_s: float = 10.0  # predictive-scale projection
    # Sharded task store (taskstore/sharding.py, docs/sharding.md): split
    # the task keyspace over N independent shards — each with its own
    # journal, passive replicas (with journal_path), and epoch-fenced
    # failover — so one shard primary's death degrades 1/N of the keyspace
    # for the promotion window instead of everything. 1 (default) keeps
    # today's single-store assembly byte for byte. >1 requires the Python
    # store/broker and is exclusive with the whole-store HA pair
    # (replicate_from) — shard replicas ARE the availability story.
    task_shards: int = 1
    # Hash-slot count the ring divides the keyspace into (a rebalance moves
    # whole slots); must be >= task_shards.
    task_shard_slots: int = 64
    # Passive replicas per shard (journal_path required for them to absorb);
    # 0 disables per-shard failover.
    task_shard_replicas: int = 1
    # Replica journal-tail poll interval (seconds).
    shard_tail_interval: float = 0.25
    # Per-shard change-feed replay window (terminal records retained for
    # the long-poll attach race; taskstore/feed.py).
    shard_feed_recent: int = 4096
    # Request observability (observability/, docs/observability.md):
    # per-task hop ledger stamped at every hop and carried on the task
    # record (``GET /v1/taskmanagement/task/{id}?ledger=1``, the trace
    # CLI), a tail-sampled flight recorder keeping 100% of slow/failed/
    # expired/shed/failovered request timelines (``GET /v1/debug/flight``,
    # dumped by the chaos harness on invariant violation), and the
    # per-route e2e latency/outcome telemetry the SLO engine reads. Off
    # by default — the assembly is byte-identical without it (asserted
    # in tests); requires the Python store (the native core has no
    # ledger slot).
    observability: bool = False
    flight_capacity: int = 512
    flight_sample: float = 0.05       # kept fraction of boring requests
    flight_slow_ms: float = 1000.0    # e2e latency that makes one interesting
    # Per-route SLO objectives ("/route=<latency_ms>:<target_pct>" or
    # "/route=goodput:<target_pct>", comma-separated) + the multi-window
    # burn-rate engine exporting ai4e_slo_* (observability/slo.py).
    # Requires observability=True (the engine reads its histograms).
    slo_objectives: str | None = None
    slo_tick_s: float = 5.0
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    # Sustained SLO breaches feed the degradation ladder as an extra
    # miss-evidence source (requires orchestration).
    slo_ladder: bool = False
    # First-class pipeline DAGs (pipeline/, docs/pipelines.md): declared
    # multi-stage compositions (fan-out/fan-in joins with a failure
    # quorum, per-stage deadline fractions carved from X-Deadline-Ms,
    # per-stage result-cache reuse) executed under ONE TaskId by a
    # coordinator riding the existing store/broker/dispatcher fabric,
    # plus the streaming surface GET /v1/taskmanagement/task/{id}/events
    # (SSE: stage-by-stage partial results before the terminal answer).
    # Off by default — the assembly is byte-identical without it
    # (asserted in tests); requires the Python store/broker and the
    # queue transport (the coordinator consumes entry queues).
    pipeline: bool = False
    # Per-task event replay buffer (events a late-attaching stream still
    # sees) and the SSE stream's maximum duration per request (seconds;
    # ?wait= may only shorten it).
    pipeline_event_replay: int = 256
    pipeline_stream_max_s: float = 300.0
    # Trailing CHUNK events (token streams) a late attacher replays
    # before the bounded history drops to a single `truncated` marker
    # (docs/streaming.md).
    pipeline_chunk_replay: int = 128
    # Multi-tenancy (tenancy/, docs/tenancy.md): subscription keys resolve
    # to tenants once at the gateway edge; work-creating requests spend a
    # per-tenant token bucket (429 + drain-derived Retry-After, composed
    # with the priority shedder); the broker's per-shard sub-queues dequeue
    # deficit-round-robin across per-tenant lanes so a flooded tenant fills
    # its own lane, never another's; the dispatcher charges placement cost
    # per tenant; and goodput/SLO-burn series carry a bounded-cardinality
    # tenant label (top-N + "other", never raw keys). Off by default — the
    # assembly is byte-identical without it (asserted in tests); requires
    # the Python store/broker and the queue transport (the native broker's
    # C structs carry no tenant slot, and the push transport has no queue
    # to lane).
    tenancy: bool = False
    # Tenant spec "name=key1|key2[:weight[:rps[:burst]]]" comma-separated
    # (tenancy/registry.py parse_tenants); None/"" = no declared tenants
    # (all traffic rides the default tenant's lane and bucket).
    tenancy_tenants: str | None = None
    # Defaults for spec entries that omit a field — and the default
    # tenant's own policy (rps 0 = unlimited).
    tenancy_default_weight: float = 1.0
    tenancy_default_rps: float = 0.0
    tenancy_default_burst: float = 0.0
    # Frozen metric-label cardinality bound: first N declared tenants keep
    # their id as label value, the rest collapse into "other".
    tenancy_label_top_n: int = 8
    # Goodput target the per-tenant SLO-burn gauge normalizes against
    # (burn 1.0 = failing exactly (1 - target) of the window).
    tenancy_goodput_target: float = 0.99
    # Floor on a lane's DRR credit per ring visit (guards pathological
    # weights; tenancy/lanes.py).
    tenancy_min_quantum: float = 0.05
    # How long a drain-marked backend (503 + X-Draining) stays ejected
    # from placement per observation (rollout/; AI4E_ROLLOUT_
    # DRAIN_EJECT_TTL_S feeds this through FrameworkConfig).
    rollout_drain_eject_ttl_s: float = 30.0


class LocalPlatform:
    """Everything the async path needs, in one event loop.

    Usage::

        platform = LocalPlatform(PlatformConfig(retry_delay=0.05))
        svc = platform.make_service("megadetector", prefix="v1/camera-trap")
        ... register endpoints on svc ...
        platform.publish_async_api("/v1/camera-trap/detect",
                                   backend_uri="http://127.0.0.1:8083/v1/camera-trap/detect")
        await platform.start()
    """

    def __init__(self, config: PlatformConfig | None = None,
                 metrics: MetricsRegistry | None = None):
        self.config = config or PlatformConfig()
        self.metrics = metrics or DEFAULT_REGISTRY
        result_backend = None
        if self.config.result_dir:
            from .taskstore.results import FileResultBackend
            result_backend = FileResultBackend(self.config.result_dir)
        result_kwargs = dict(
            result_backend=result_backend,
            result_offload_threshold=(self.config.result_offload_threshold
                                      if result_backend else None))
        # Journal-bearing stores additionally get the assembly registry
        # (ai4e_journal_* metrics must land beside the platform's own
        # /metrics, not in the process default — AIL002); their fsync
        # policy is AI4E_TASKSTORE_FSYNC's (docs/durability.md).
        journal_kwargs = dict(result_kwargs, metrics=self.metrics)
        if self.config.task_shards > 1:
            if self.config.native_store or self.config.native_broker:
                raise ValueError(
                    "task_shards > 1 requires the Python store and broker "
                    "(the native cores hold no ring/fence state)")
            if self.config.replicate_from:
                raise ValueError(
                    "task_shards > 1 is exclusive with replicate_from: "
                    "per-shard replicas are the sharded availability "
                    "story (docs/sharding.md)")
            from .taskstore.sharding import ShardedTaskStore
            self.store = ShardedTaskStore(
                self.config.task_shards,
                slots=self.config.task_shard_slots,
                journal_path=self.config.journal_path,
                replicas=(self.config.task_shard_replicas
                          if self.config.journal_path else 0),
                tail_interval=self.config.shard_tail_interval,
                feed_recent=self.config.shard_feed_recent,
                **journal_kwargs)
        elif self.config.replicate_from:
            if not self.config.journal_path:
                raise ValueError(
                    "replicate_from (standby mode) requires journal_path — "
                    "the follower journals the absorbed stream")
            if self.config.native_store:
                raise ValueError("standby mode requires the Python store")
            from .taskstore.store import FollowerTaskStore
            self.store = FollowerTaskStore(self.config.journal_path,
                                           **journal_kwargs)
        elif self.config.journal_path:
            if self.config.native_store:
                raise ValueError(
                    "native_store has no journal; use journal_path with the "
                    "Python store or native_store without durability")
            # Born-primary FollowerTaskStore, not a plain JournaledTaskStore:
            # behaviorally identical while primary, but carries the
            # demote()/note_epoch() fence — so a journaled primary in an HA
            # pair can be deposed by a promoted standby (split-brain
            # fencing, VERDICT r4 #3) instead of silently accepting
            # doomed writes.
            from .taskstore.store import FollowerTaskStore
            self.store = FollowerTaskStore(self.config.journal_path,
                                           start_as_primary=True,
                                           **journal_kwargs)
        elif self.config.native_store:
            from .taskstore.native import NativeTaskStore
            if result_backend is not None:
                raise ValueError(
                    "result_dir offload requires the Python store "
                    "(the native store keeps results in its own memory)")
            ret = self.config.reaper_terminal_retention
            if ret is not None and ret >= 0:
                # Fail loudly on an EXPLICIT retention: a knob that
                # silently never evicts is exactly the OOM it exists to
                # prevent. (AUTO/None and negative opt-out both mean no
                # eviction here — the native store has none.)
                raise ValueError(
                    "reaper_terminal_retention requires the Python store "
                    "(the native store has no eviction)")
            self.store = NativeTaskStore()
        else:
            self.store = InMemoryTaskStore(**result_kwargs)
        self.task_manager = LocalTaskManager(self.store)
        self.result_cache = None
        if self.config.result_cache:
            from .rescache import ResultCache, attach_store
            self.result_cache = ResultCache(
                max_entries=self.config.cache_max_entries,
                max_bytes=self.config.cache_max_bytes,
                ttl_s=self.config.cache_ttl_seconds,
                metrics=self.metrics)
            if hasattr(self.store, "add_listener"):
                # The async path's fill point: the store's change feed
                # copies results into the cache on terminal transitions and
                # releases single-flight leaders (rescache/wiring.py).
                # Every store qualifies — the native facade shares the
                # StoreSideEffects listener plumbing and carries CacheKey
                # in a Python-side sidecar (native.py) — the hasattr is
                # only a guard for exotic store substitutes in tests.
                attach_store(self.store, self.result_cache)
        self.admission = None
        if self.config.admission:
            if self.config.native_store or self.config.native_broker:
                # The C cores have no deadline/priority slots on their
                # record/message structs and no `expired` status bucket in
                # their canonical sets — admission there would silently
                # drop the very state it exists to enforce. Same loud-fail
                # pattern as retention/journal on the native store.
                raise ValueError(
                    "admission control requires the Python store and "
                    "broker (the native cores carry no deadline/priority "
                    "state)")
            from .admission import AdmissionController
            self.admission = AdmissionController(
                metrics=self.metrics,
                min_limit=self.config.admission_min_limit,
                max_limit=self.config.admission_max_limit,
                initial_limit=self.config.admission_initial_limit,
                max_backlog=self.config.admission_max_backlog)
            if hasattr(self.store, "add_listener"):
                # Terminal transitions feed the drain-rate estimator (the
                # Retry-After on every shed/standby response) and score
                # goodput — the same change feed the long-poll waiters and
                # the result cache ride.
                self.admission.attach_store(self.store)
        self.resilience = None
        if self.config.resilience:
            # ONE health model per assembly: the sync proxy and every
            # dispatcher record into (and route around) the same breakers,
            # so a backend melting under queue deliveries is ejected from
            # sync picks too.
            from .resilience import BackendHealth, ResiliencePolicy
            self.resilience = BackendHealth(
                policy=ResiliencePolicy(
                    failure_threshold=self.config.resilience_failure_threshold,
                    window=self.config.resilience_window,
                    error_rate=self.config.resilience_error_rate,
                    recovery_seconds=self.config.resilience_recovery_seconds,
                    max_attempts=self.config.resilience_max_attempts,
                    retry_base_s=self.config.resilience_retry_base_s,
                    retry_budget_ratio=(
                        self.config.resilience_retry_budget_ratio),
                    drain_eject_ttl_s=(
                        self.config.rollout_drain_eject_ttl_s)),
                metrics=self.metrics)
        self.orchestration = None
        if self.config.orchestration:
            if self.admission is None or self.resilience is None:
                # The orchestrator composes the admission layer's
                # deadline/drain signals and the resilience layer's
                # breaker state — without either it would be guessing.
                # Loud fail, same pattern as admission-on-native.
                raise ValueError(
                    "orchestration=True requires admission=True and "
                    "resilience=True (it composes their signals — "
                    "docs/orchestration.md)")
            from .orchestration import (Orchestrator, OrchestrationPolicy,
                                        parse_costs)
            self.orchestration = Orchestrator(
                self.resilience,
                policy=OrchestrationPolicy(
                    confidence=self.config.orchestration_confidence,
                    window=self.config.orchestration_window,
                    horizon_s=self.config.orchestration_horizon_s,
                    costs=parse_costs(self.config.orchestration_costs),
                    ladder_up=self.config.orchestration_ladder_up,
                    ladder_down=self.config.orchestration_ladder_down,
                    ladder_hold_s=self.config.orchestration_ladder_hold_s,
                    scale_horizon_s=(
                        self.config.orchestration_scale_horizon_s)),
                metrics=self.metrics)
            # The admission shedder consults the ladder on every decision,
            # and its store listener feeds the ladder actual deadline
            # outcomes (late/expired) — the brownout's evidence loop.
            self.admission.set_ladder(self.orchestration.ladder)
        self.observability = None
        self.slo = None
        if self.config.observability:
            if self.config.native_store:
                # The C store has no ledger slot; silently running the
                # layer without timelines would be the worst outcome —
                # same loud-fail pattern as admission-on-native.
                raise ValueError(
                    "observability=True requires the Python store "
                    "(the native core carries no hop-ledger state)")
            from .observability.flight import FlightRecorder
            from .observability.hub import RequestObservability
            self.observability = RequestObservability(
                self.store, metrics=self.metrics,
                flight=FlightRecorder(
                    capacity=self.config.flight_capacity,
                    sample=self.config.flight_sample,
                    slow_ms=self.config.flight_slow_ms,
                    metrics=self.metrics))
        if self.config.slo_objectives:
            if self.observability is None:
                raise ValueError(
                    "slo_objectives requires observability=True — the "
                    "SLO engine reads the e2e histograms the "
                    "observability layer maintains "
                    "(docs/observability.md)")
            from .observability.slo import SloEngine, parse_objectives
            self.slo = SloEngine(
                parse_objectives(self.config.slo_objectives),
                metrics=self.metrics,
                fast_window_s=self.config.slo_fast_window_s,
                slow_window_s=self.config.slo_slow_window_s,
                tick_s=self.config.slo_tick_s)
        if self.config.slo_ladder:
            if self.slo is None or self.orchestration is None:
                raise ValueError(
                    "slo_ladder=True requires slo_objectives AND "
                    "orchestration=True — it feeds SLO breaches to the "
                    "degradation ladder (docs/observability.md)")
            self.slo.attach_ladder(self.orchestration.ladder)
        self.tenancy = None
        if self.config.tenancy:
            if self.config.transport != "queue":
                raise ValueError(
                    "tenancy=True requires the queue transport — the "
                    "weighted-fair lanes live inside the broker's queues "
                    "(docs/tenancy.md)")
            if self.config.native_store or self.config.native_broker:
                # The C structs have no tenant slot; running the layer
                # there would silently drop the very scope it enforces —
                # same loud-fail pattern as admission-on-native.
                raise ValueError(
                    "tenancy=True requires the Python store and broker "
                    "(the native cores carry no tenant state)")
            from .tenancy import Tenancy
            self.tenancy = Tenancy.from_spec(
                self.config.tenancy_tenants,
                metrics=self.metrics,
                default_weight=self.config.tenancy_default_weight,
                default_rps=self.config.tenancy_default_rps,
                default_burst=self.config.tenancy_default_burst,
                label_top_n=self.config.tenancy_label_top_n,
                goodput_target=self.config.tenancy_goodput_target,
                min_quantum=self.config.tenancy_min_quantum)
            if hasattr(self.store, "add_listener"):
                # Terminal transitions label the per-tenant outcome/burn
                # series — the same change feed admission's goodput scorer
                # rides, attached independently so per-tenant series exist
                # without the observability layer.
                self.tenancy.attach_store(self.store)
        self.broker = None
        self.dispatchers = None
        self.topic = None
        self.webhook = None
        self._webhook_runner = None
        if self.config.transport == "push":
            # Webhook routes are recorded so a demoted-then-re-promoted
            # node can rebuild the push transport (demote_now closes it).
            self._push_routes: list[tuple[str, Weighted]] = []
            self._build_push()
        elif self.config.transport == "queue":
            if self.config.native_broker:
                from .broker.native import NativeBroker
                self.broker = NativeBroker(
                    max_delivery_count=self.config.max_delivery_count,
                    lease_seconds=self.config.lease_seconds)
            else:
                self.broker = InMemoryBroker(
                    max_delivery_count=self.config.max_delivery_count,
                    lease_seconds=self.config.lease_seconds,
                    metrics=self.metrics,
                    # Sharded store → per-shard sub-queues, so each shard's
                    # dispatchers drain independently (broker/queue.py).
                    shard_router=(self.store.shard_for
                                  if self.config.task_shards > 1 else None),
                    # Tenancy → per-tenant DRR lanes inside every queue,
                    # shard sub-queues included (broker/queue.py).
                    fair=(self.tenancy.lanes
                          if self.tenancy is not None else None))
            self.store.set_publisher(self.broker.publish)
            self.dispatchers = DispatcherPool(
                self.broker, self.task_manager,
                retry_delay=self.config.retry_delay,
                concurrency=self.config.dispatcher_concurrency,
                result_cache=self.result_cache,
                result_store=(self.store if self.result_cache is not None
                              and hasattr(self.store, "set_result")
                              else None),
                admission=self.admission,
                resilience=self.resilience,
                orchestration=self.orchestration,
                observability=self.observability,
                tenancy=self.tenancy,
                metrics=self.metrics)
        else:
            raise ValueError(
                f"unknown transport {self.config.transport!r}; "
                "expected 'queue' or 'push'")
        self.pipeline = None
        self.task_events = None
        if self.config.pipeline:
            if self.config.transport != "queue":
                raise ValueError(
                    "pipeline=True requires the queue transport — the "
                    "coordinator consumes pipeline entry queues "
                    "(docs/pipelines.md)")
            if self.config.native_store or self.config.native_broker:
                raise ValueError(
                    "pipeline=True requires the Python store and broker "
                    "(the coordinator rides the store change feed and "
                    "stage sub-records)")
            from .pipeline import PipelineCoordinator, TaskEventHub
            self.task_events = TaskEventHub(
                replay=self.config.pipeline_event_replay,
                chunk_replay=self.config.pipeline_chunk_replay,
                metrics=self.metrics)
            # Every transition of a tracked/streamed task becomes a
            # `status` event; terminal transitions close streams — the
            # same change feed the long-poll waiters and the result
            # cache ride.
            self.task_events.attach_store(self.store)
            queue_names = None
            if self.config.task_shards > 1:
                from .broker.queue import shard_queue_name
                n = self.config.task_shards

                def queue_names(path, _n=n):
                    return [shard_queue_name(path, i) for i in range(_n)]

            self.pipeline = PipelineCoordinator(
                self.store, self.broker, hub=self.task_events,
                result_cache=self.result_cache, admission=self.admission,
                observability=self.observability, metrics=self.metrics,
                queue_names=queue_names)
        self.gateway = Gateway(self.store, metrics=self.metrics)
        if self.result_cache is not None:
            self.gateway.set_result_cache(self.result_cache)
        if self.admission is not None:
            self.gateway.set_admission(self.admission)
        if self.resilience is not None:
            self.gateway.set_resilience(self.resilience)
        if self.orchestration is not None:
            self.gateway.set_orchestration(self.orchestration)
        if self.observability is not None:
            self.gateway.set_observability(self.observability)
        if self.tenancy is not None:
            self.gateway.set_tenancy(self.tenancy)
        if self.task_events is not None:
            self.gateway.set_event_stream(
                self.task_events,
                max_stream_s=self.config.pipeline_stream_max_s)
        # Terminal-history retention: None = AUTO — 15 min on the Python
        # store, sized to the soak evidence (unevicted terminal history
        # grows ~12 MB/min at 200 req/s → AUTO bounds steady-state at
        # ~180 MB, the level the retention-on soak measured flat:
        # scripts/soak.sh). 0 keeps its pre-r5 meaning (evict
        # terminal tasks immediately); NEGATIVE opts out of eviction
        # entirely. Nothing on the native store (no eviction support).
        # Redis expiry played this role for the reference.
        retention = self.config.reaper_terminal_retention
        if retention is None and not self.config.native_store:
            retention = 900.0
        if retention is not None and retention < 0:
            retention = None
        self.reaper = None
        if (self.config.reaper_running_timeout is not None
                or retention is not None):
            from .taskstore.reaper import TaskReaper
            self.reaper = TaskReaper(
                self.store,
                running_timeout=self.config.reaper_running_timeout,
                interval=self.config.reaper_interval,
                max_requeues=self.config.reaper_max_requeues,
                terminal_retention=retention,
                metrics=self.metrics)
        from .observability import DepthLogger
        self.depth_logger = DepthLogger(
            self.store, metrics=self.metrics,
            queue_interval=self.config.queue_depth_interval,
            process_interval=self.config.process_depth_interval)
        self.services: list[APIService] = []
        self.autoscalers: list = []
        self.replicator = None
        self.watchdog = None
        self.prober = None
        self._transport_running = False
        self._started = False
        # Strong refs to fire-and-forget background work (dead-letter
        # terminal transitions): the event loop holds tasks WEAKLY, so a
        # dropped create_task handle can be garbage-collected mid-flight
        # and the task it was failing sits non-terminal forever (AIL004).
        self._bg_tasks: set[asyncio.Task] = set()

    # -- assembly ----------------------------------------------------------

    def _build_push(self) -> None:
        """(Re)construct the push transport: topic + webhook dispatcher +
        recorded routes, and point the store's publish hook at the new
        topic. Called at assembly and again after a demotion closed the
        previous topic (PushTopic.aclose is terminal — a re-promotion
        needs a fresh one)."""
        from .broker.push import PushTopic, WebhookDispatcher
        self.topic = PushTopic(
            ttl_seconds=self.config.push_ttl_seconds,
            max_attempts=self.config.push_max_attempts,
            retry_delay=self.config.retry_delay,
            window=self.config.push_window,
            metrics=self.metrics)
        self.webhook = WebhookDispatcher(self.task_manager,
                                         metrics=self.metrics)
        for queue_name, backend_uri in self._push_routes:
            self.webhook.add_route(queue_name, backend_uri)
        self.store.set_publisher(self.topic.publish)

    def make_service(self, name: str, prefix: str = "") -> APIService:
        svc = APIService(name, prefix=prefix,
                         task_manager=self.task_manager, metrics=self.metrics)
        self.services.append(svc)
        return svc

    def publish_async_api(self, public_prefix: str, backend_uri,
                          retry_delay: float | None = None,
                          concurrency: int | None = None,
                          autoscale=None,
                          autoscale_interval: float = 5.0,
                          max_body_bytes: int | None = None) -> None:
        """Register an async API end-to-end: gateway route + dispatcher for
        its queue (the reference needs an APIM operation + a Service Bus queue
        + a function app per API; here it's one call). Passing an
        ``AutoscalePolicy`` as ``autoscale`` attaches the HPA-style control
        loop (the reference's per-API ``autoscaler.yaml``) to the
        dispatcher's delivery fan-out. ``backend_uri`` may be a weighted
        backend LIST (canary; ``utils/backends.py``) — the recorded task
        Endpoint is the primary's (path identity is shared by
        construction), deliveries split per the weights."""
        backends = normalize_backends(backend_uri)
        # The gateway derives cacheability from the backend set itself
        # (weighted canary splits are uncacheable — Route.cacheable).
        self.gateway.add_async_route(public_prefix, backends,
                                     max_body_bytes=max_body_bytes)
        self.register_internal_route(backends, retry_delay=retry_delay,
                                     concurrency=concurrency,
                                     autoscale=autoscale,
                                     autoscale_interval=autoscale_interval)

    def register_internal_route(self, backend_uri,
                                retry_delay: float | None = None,
                                concurrency: int | None = None,
                                autoscale=None,
                                autoscale_interval: float = 5.0) -> None:
        """Transport consumer for a backend WITHOUT a public gateway route —
        internal pipeline stages (e.g. the classifier batch endpoint a
        detector's crops handoff targets) are reachable only by republished
        tasks, never by clients. Accepts a weighted backend list (canary)."""
        backend_uri = normalize_backends(backend_uri)
        queue_name = endpoint_path(backend_uri[0][0])
        if self.config.transport == "push":
            if autoscale is not None or retry_delay is not None or concurrency is not None:
                raise ValueError(
                    "autoscale/retry_delay/concurrency are queue-transport "
                    "knobs; push retry policy is topic-wide "
                    "(PlatformConfig.retry_delay/push_max_attempts)")
            self._push_routes.append((queue_name, backend_uri))
            self.webhook.add_route(queue_name, backend_uri)
            return
        self.broker.register_queue(queue_name)
        if self.config.task_shards > 1:
            if autoscale is not None and self.orchestration is None:
                # PR 6's two-loops/one-actuator refusal, now relaxed ONLY
                # under orchestration: the predictive sharded controller
                # makes per-shard decisions but routes them through one
                # actuator (ShardScaleTarget), so there is still exactly
                # one writer per dispatcher's concurrency.
                raise ValueError(
                    "autoscale policies are per-dispatcher; with "
                    "task_shards > 1 use admission's adaptive control "
                    "(one limiter per shard sub-queue) instead — or "
                    "enable orchestration, whose predictive scaler "
                    "routes per-shard decisions through one actuator "
                    "(docs/orchestration.md)")
            from .broker.queue import shard_queue_name
            queue_names = [shard_queue_name(queue_name, i)
                           for i in range(self.config.task_shards)]
        else:
            queue_names = [queue_name]
        dispatchers = [self.dispatchers.register(qn, backend_uri,
                                                 retry_delay=retry_delay,
                                                 concurrency=concurrency)
                       for qn in queue_names]
        if autoscale is not None:
            self._attach_autoscaler(queue_names, dispatchers, autoscale,
                                    autoscale_interval)
        elif self.admission is not None:
            # The adaptive controller owns each dispatcher's fan-out: its
            # per-queue limiter (fed by delivery RTTs + backpressure
            # backoffs) replaces the fixed concurrency constant. An
            # explicit AutoscalePolicy wins — two control loops driving
            # one actuator would fight.
            for qn, dispatcher in zip(queue_names, dispatchers):
                self.admission.add_target("dispatch:" + qn,
                                          dispatcher.set_concurrency)

    def _attach_autoscaler(self, queue_names, dispatchers, policy,
                           interval) -> None:
        """HPA-style scaling for a route's dispatcher(s). Under
        orchestration the signal is PREDICTIVE — projected backlog from
        the admission controller's arrival/drain estimators
        (``scaling.predictive_signal``) instead of raw depth, so loops
        scale ahead of the deadline-miss cliff; sharded routes get one
        ``ShardedAutoscaleController`` (per-shard decisions, one
        actuator)."""
        from .scaling import (AutoscaleController, DispatcherScaleTarget,
                              ShardScaleTarget, ShardedAutoscaleController,
                              predictive_signal)
        base_path = dispatchers[0].route_path
        if len(dispatchers) > 1:
            # Sharded (only reachable under orchestration — the refusal
            # above): per-shard depth from each shard's own store, the
            # global arrival/drain imbalance split evenly across shards
            # (the ring spreads TaskIds uniformly).
            horizon = self.orchestration.policy.scale_horizon_s
            n = len(dispatchers)

            def shard_depth(i, p=base_path):
                def depth() -> float:
                    # Resolved per tick, not captured: a shard failover
                    # swaps the promoted replica in for the dead primary,
                    # and a captured store object would read the corpse's
                    # frozen counts forever.
                    s = self.store.shard_stores()[i]
                    return (s.set_len(p, "created")
                            + s.set_len(p, "running"))
                return depth

            shards = []
            for i, qn in enumerate(queue_names):
                # THIS route's rates (not the platform-global ones — a
                # flooded sibling route must not inflate this route's
                # projection), split evenly across its shards (the ring
                # spreads TaskIds uniformly).
                shards.append((qn, predictive_signal(
                    shard_depth(i),
                    lambda p=base_path, n=n: (
                        self.admission.arrival_rate(route=p) / n),
                    lambda p=base_path, n=n: (
                        self.admission.route_drain_rate(p) / n),
                    horizon)))
            self.autoscalers.append(ShardedAutoscaleController(
                shards, ShardScaleTarget(dispatchers), policy=policy,
                interval=interval, metrics=self.metrics))
            return
        signal = None
        if self.orchestration is not None:
            store = self.store
            signal = predictive_signal(
                lambda: (store.set_len(base_path, "created")
                         + store.set_len(base_path, "running")),
                lambda p=base_path: self.admission.arrival_rate(route=p),
                lambda p=base_path: self.admission.route_drain_rate(p),
                self.orchestration.policy.scale_horizon_s)
        self.autoscalers.append(AutoscaleController(
            self.store, queue_names[0],
            DispatcherScaleTarget(dispatchers[0]),
            policy=policy, interval=interval, signal=signal,
            metrics=self.metrics))

    def register_pipeline(self, spec, max_body_bytes: int | None = None
                          ) -> None:
        """Publish a declared pipeline DAG (``pipeline.PipelineSpec``,
        ``docs/pipelines.md``): one gateway async route at ``spec.prefix``
        whose tasks are consumed by the pipeline coordinator instead of a
        backend dispatcher — stages then run as sub-tasks through the
        ordinary fabric. Stage ENDPOINTS still need transport consumers:
        register each one with ``register_internal_route`` (internal
        stages) or ``publish_async_api`` (stages that are also public
        APIs), exactly like hop-to-hop pipeline stages today."""
        if self.pipeline is None:
            raise ValueError(
                "register_pipeline requires PlatformConfig(pipeline=True)")
        self.gateway.add_async_route(spec.prefix, spec.entry_path,
                                     max_body_bytes=max_body_bytes)
        self.pipeline.register(spec)

    def publish_sync_api(self, public_prefix: str, backend_uri,
                         max_body_bytes: int | None = None) -> None:
        self.gateway.add_sync_route(public_prefix, backend_uri,
                                    max_body_bytes=max_body_bytes)

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        if self.config.replicate_from:
            # Standby: tail the primary's journal, serve reads, refuse
            # writes; the watchdog promotes us (and only then does the
            # transport start — a standby must never double-dispatch tasks
            # the primary is already delivering).
            from .taskstore.replication import (FailoverWatchdog,
                                                JournalReplicator)
            self.replicator = JournalReplicator(
                self.store, self.config.replicate_from,
                api_key=self.config.replicate_api_key,
                metrics=self.metrics)
            self.replicator.start()
            self.watchdog = FailoverWatchdog(
                self.replicator,
                interval=self.config.failover_interval,
                down_after=self.config.failover_down_after,
                on_promote=self._on_promoted)
            self.watchdog.start()
            await self.depth_logger.start()
            self._started = True
            return
        if hasattr(self.store, "passive_fencing"):
            # A primary with NO configured HA peer must not be demotable by
            # a forged or stale X-Store-Epoch header — there is no standby
            # to take over, so passive fencing evidence would only convert
            # a bogus header into a total write outage. advertise_url is
            # the HA-pair marker (both charts set it); the explicit
            # /demote endpoint stays available either way.
            self.store.passive_fencing = bool(self.config.advertise_url)
        if hasattr(self.store, "start_replication"):
            # Sharded store: per-shard replica journal tails (sharding.py).
            await self.store.start_replication()
        await self._start_transport(loop)
        await self.depth_logger.start()
        if self.reaper is not None:
            await self.reaper.start()
        if self.slo is not None:
            await self.slo.start()
        for scaler in self.autoscalers:
            await scaler.start()
        self._reseed_unfinished()
        self._started = True

    async def _start_transport(self, loop: asyncio.AbstractEventLoop) -> None:
        self._transport_running = True
        if self.config.transport == "push":
            if self.topic is None:
                # A demotion closed the previous topic/webhook; a
                # re-promotion (fail-back) rebuilds them.
                self._build_push()
            await self._start_push(loop)
        else:
            self.broker.bind_loop(loop)

            def on_dead_letter(msg) -> None:
                # Runs on the event loop (queues are loop-bound); fail the
                # task asynchronously so it never sits non-terminal after its
                # message is gone.
                self._spawn_bg(loop, self._fail_dead_letter(msg.task_id))

            self.broker.set_dead_letter_handler(on_dead_letter)
            await self.dispatchers.start()
            if self.pipeline is not None:
                # The coordinator starts WITH the transport (never on a
                # standby — a follower must not drive pipeline runs the
                # primary is already driving) and its entry-queue
                # consumption precedes the restart re-seed, which is the
                # pipeline resume path.
                await self.pipeline.start()

    async def _on_promoted(self) -> None:
        """Watchdog fired: this standby is now the primary. Start transport
        + watchdogs and re-dispatch EVERY unfinished task (they arrived via
        replication, so none has a broker message here) — exactly the
        restart re-seed, with the replicated store as the journal."""
        import logging
        logging.getLogger("ai4e_tpu.platform").warning(
            "promoted to primary; starting transport and re-seeding "
            "%d unfinished tasks", len(self.store.unfinished_tasks()))
        # Release the replicator: the watchdog stopped its loop but the
        # REFERENCE must clear too — demote_now gates auto-rejoin on
        # `replicator is None`, and the /role endpoint's "replicating"
        # field reads the same attribute (a stale object here would make a
        # future fail-back silently skip rejoin). The watchdog reference
        # stays: its run loop returns right after this hook, and its
        # `promoted` event is part of the observable surface.
        if self.replicator is not None:
            await self.replicator.aclose()
            self.replicator = None
        loop = asyncio.get_running_loop()
        await self._start_transport(loop)
        if self.reaper is not None:
            await self.reaper.start()
        if self.slo is not None:
            await self.slo.start()
        for scaler in self.autoscalers:
            await scaler.start()
        publish = (self.topic.publish if self.config.transport == "push"
                   else self.broker.publish)
        for task in self.store.unfinished_tasks():
            publish(task)
        # Actively fence the deposed primary (split-brain closure): keep
        # knocking on its door so it demotes — and rejoins us — the moment
        # the partition heals, even if no client traffic ever reaches it.
        if self.config.replicate_from:
            from .taskstore.replication import FencingProber
            self.prober = FencingProber(
                self.store, self.config.replicate_from,
                advertise_url=self.config.advertise_url,
                api_key=self.config.replicate_api_key,
                interval=self.config.failover_interval)
            self.prober.start()

    async def promote_now(self) -> None:
        """Manual-failover entry (HTTP ``POST /v1/taskstore/promote`` routes
        here via make_app's ``lifecycle``): the same sequence the watchdog
        runs — replication torn down FIRST, so a racing poll can never
        resync-wipe the newly-promoted primary (ADVICE r4 high)."""
        if self.watchdog is not None:
            await self.watchdog.stop()
            self.watchdog = None
        if self.replicator is not None:
            await self.replicator.aclose()
            self.replicator = None
        if getattr(self.store, "role", "primary") == "primary":
            return  # already primary — idempotent
        self.store.promote()
        await self._on_promoted()

    async def demote_now(self, epoch: int, primary_url: str | None = None
                         ) -> None:
        """Fence this node out of the primary role (HTTP ``POST
        /v1/taskstore/demote`` routes here). The store flip is first and
        synchronous — writes refuse before this returns; raises
        ``StaleEpochError`` (handler: 409) when the caller's epoch is not
        newer. Then the primary-side machinery stops, and with
        ``primary_url`` the node rejoins the new primary as a standby —
        watchdog armed, so the pair can fail back."""
        self.store.demote(epoch)
        # Stop the primary-side machinery if it is still running. Keyed on
        # actual transport state, not on the role at call time: a PASSIVE
        # demotion (a client's epoch header flipped the bare store mid-
        # request) leaves the platform's dispatchers running — the prober's
        # follow-up demote call cleans that up here.
        if self._transport_running:
            import logging
            logging.getLogger("ai4e_tpu.platform").warning(
                "demoted at epoch %d (new primary: %s); stopping transport",
                epoch, primary_url or "unknown")
            self._transport_running = False
            if self.prober is not None:
                await self.prober.aclose()
                self.prober = None
            for scaler in self.autoscalers:
                await scaler.stop()
            if self.reaper is not None:
                await self.reaper.stop()
            if self.pipeline is not None:
                # Live runs abandon; the new primary's re-seed republishes
                # their (non-terminal) root tasks and ITS coordinator
                # resumes them — the same path as a restart.
                await self.pipeline.stop()
            if self.dispatchers is not None:
                await self.dispatchers.stop()
            if self.topic is not None:
                # Push transport: in-flight deliveries drain; their result
                # writes hit the store fence (NotPrimaryError → 503) and
                # the new primary's re-seed owns redelivery. aclose is
                # terminal, so drop the topic + webhook — a re-promotion
                # rebuilds them (_start_transport → _build_push).
                await self.topic.aclose()
                self.topic = None
                self.webhook = None
                self.store.set_publisher(None)
                if self._webhook_runner is not None:
                    await self._webhook_runner.cleanup()
                    self._webhook_runner = None
        if primary_url and self.replicator is None:
            from .taskstore.replication import (FailoverWatchdog,
                                                JournalReplicator)
            self.config.replicate_from = primary_url
            self.replicator = JournalReplicator(
                self.store, primary_url,
                api_key=self.config.replicate_api_key,
                metrics=self.metrics)
            self.replicator.start()
            self.watchdog = FailoverWatchdog(
                self.replicator,
                interval=self.config.failover_interval,
                down_after=self.config.failover_down_after,
                on_promote=self._on_promoted)
            self.watchdog.start()

    async def _start_push(self, loop: asyncio.AbstractEventLoop) -> None:
        """Push transport: serve the webhook dispatcher app, then validate
        the topic → webhook subscription (the reference's Event Grid
        subscription handshake, ``deploy_event_grid_subscription.sh``). The
        webhook runs on its own port so the topic→webhook leg is a real HTTP
        hop, exactly as process-separable as the reference's Functions."""
        from aiohttp import web as aioweb
        self.topic.bind_loop(loop)

        def on_dead_letter(event) -> None:
            self._spawn_bg(loop, self._fail_dead_letter(event.id))

        self.topic.set_dead_letter_handler(on_dead_letter)
        runner = aioweb.AppRunner(self.webhook.app)
        await runner.setup()
        site = aioweb.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        self._webhook_runner = runner
        await self.topic.subscribe(
            "backend-webhook", f"http://127.0.0.1:{port}/api/events")

    def _spawn_bg(self, loop: asyncio.AbstractEventLoop, coro) -> asyncio.Task:
        """Spawn background work with a STRONG reference held until done
        (AIL004): the loop's weak ref alone lets the garbage collector kill
        the task mid-flight, silently dropping the terminal transition."""
        task = loop.create_task(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    async def _fail_dead_letter(self, task_id: str) -> None:
        try:
            task = self.store.get(task_id)
            if task.canonical_status not in TaskStatus.TERMINAL:
                await self.task_manager.fail_task(
                    task_id, TaskStatus.DEAD_LETTER)
        except Exception:  # noqa: BLE001 — best-effort terminal transition
            import logging
            logging.getLogger("ai4e_tpu.platform").exception(
                "could not fail dead-lettered task %s", task_id)

    def _reseed_unfinished(self) -> None:
        """Re-enqueue tasks restored from the journal in a non-terminal state
        — the redelivery the reference gets from Service Bus persistence
        (autoComplete:false, BackendQueueProcessor/host.json:7): a crashed
        worker's task is dispatched again on platform restart. Only
        journal-*restored* tasks are re-seeded; tasks created in this process
        already have their broker message."""
        restored = getattr(self.store, "replayed_task_ids", None)
        if not restored:
            return
        publish = (self.topic.publish if self.config.transport == "push"
                   else self.broker.publish)
        for task in self.store.unfinished_tasks():
            if task.task_id in restored:
                publish(task)

    async def stop(self) -> None:
        if self.watchdog is not None:
            await self.watchdog.stop()
            self.watchdog = None
        if self.replicator is not None:
            await self.replicator.aclose()
            self.replicator = None
        if self.prober is not None:
            await self.prober.aclose()
            self.prober = None
        if self._started:
            for scaler in self.autoscalers:
                await scaler.stop()
            if self.pipeline is not None:
                await self.pipeline.stop()
            if self.dispatchers is not None:
                await self.dispatchers.stop()
            if self.reaper is not None:
                await self.reaper.stop()
            if self.slo is not None:
                await self.slo.stop()
            await self.depth_logger.stop()
            if hasattr(self.store, "stop_replication"):
                await self.store.stop_replication()
            self._started = False
        for svc in self.services:
            await svc.drain(timeout=5.0)
        # Transport teardown AFTER service drain: a draining async task may
        # still hand off a pipeline stage, which must publish — the queue
        # broker stays open until here too. (Push cleanup also runs when
        # start() failed mid-way, e.g. a handshake error after the webhook
        # site was bound.)
        if self.topic is not None:
            await self.topic.aclose()
        if self._webhook_runner is not None:
            await self._webhook_runner.cleanup()
            self._webhook_runner = None
        if self.broker is not None and hasattr(self.broker, "close"):
            self.broker.close()
