"""Model registry — servable JAX models with bucketed compiled programs.

The reference's "model registry" is a container registry: each model API is an
opaque Docker image lazy-loading weights at startup (``APIs/Charts/templates/
async-gpu/templates/deployment.yaml:14-55``). Here a servable is code+params
in-process: an apply function compiled per (batch-bucket) shape onto the
device mesh, with explicit warmup (the compile-time management SURVEY.md §7
lists as a hard part — containers lazy-load; TPU programs must precompile).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.tracing import device_trace
from .ladder import DEFAULT_BUCKETS
# The one sanctioned device→host fetch (AIL014: every other transfer on
# the serving path must carry an explicit placement).
from .mesh.placement import fetch_to_host

log = logging.getLogger("ai4e_tpu.runtime")

Preprocess = Callable[[bytes, str], np.ndarray]
Postprocess = Callable[[Any], Any]


@dataclass
class ServableModel:
    """One deployable model API.

    - ``apply_fn(params, batch) -> outputs``: pure function of a dense batch;
    - ``preprocess(body, content_type) -> example``: request payload → one
      example array of ``input_shape`` (raises ValueError on bad input — that
      fails one task, never a batch);
    - ``postprocess(example_outputs) -> result``: one example's slice of the
      outputs → JSON-able result.
    - ``batch_buckets``: allowed batch sizes, ascending. Requests are padded
      up to the smallest fitting bucket so XLA compiles exactly
      ``len(batch_buckets)`` programs per model.
    """

    name: str
    apply_fn: Callable
    params: Any
    input_shape: tuple[int, ...]
    preprocess: Preprocess
    postprocess: Postprocess
    batch_buckets: tuple[int, ...] = DEFAULT_BUCKETS
    input_dtype: Any = np.float32
    version: str = "1.0"
    # Weights provenance for hot reload: the checkpoint this servable's
    # params were restored from (None = init/in-memory weights), and a
    # monotonic version bumped by every successful reload_params — the
    # /models introspection exposes both so operators can confirm a
    # rollout landed.
    checkpoint_path: str | None = None
    params_version: int = 1
    # Rollout generation (rollout/, docs/deployment.md): which fleet-wide
    # deploy this servable's weights belong to. params_version is a local
    # monotonic swap counter; generation is the cross-replica coordinate
    # the canary split routes on — the reload verb sets it from the
    # controller's payload, /models exposes it.
    generation: int = 1
    # Param-path → PartitionSpec rules applied at register() — how a family
    # declares model-parallel placement (e.g. MoE experts over ep) that must
    # survive the runtime's own param placement.
    param_sharding_rules: dict | None = None
    # Batch-STACK ingestion for servables whose device input shape differs
    # from the natural payload shape (e.g. the yuv420 wire's flat planes):
    # stacks arrive as (N, *stack_item_shape) in stack_item_dtype and each
    # item passes through stack_adapter to become an input_shape example.
    # None = stacks match input_shape directly.
    stack_item_shape: tuple[int, ...] | None = None
    stack_item_dtype: Any = None
    stack_adapter: Callable | None = None
    # Value-level validation of the RAW decoded stack, before any dtype
    # cast (token servables reject floats / out-of-range ids here — a
    # post-cast check would pass ids that wrapped into range).
    stack_validator: Callable | None = None
    # Inverse for HOST consumers of a preprocessed example (pipeline
    # handoffs crop the stage's input image): example → natural image.
    # None = the example already is the natural payload.
    example_decoder: Callable | None = None
    _compiled: Callable | None = field(default=None, repr=False)
    _batch_sharding: Any = field(default=None, repr=False)

    def bucket_for(self, n: int) -> int:
        for b in self.batch_buckets:
            if b >= n:
                return b
        return self.batch_buckets[-1]

    @property
    def max_bucket(self) -> int:
        return self.batch_buckets[-1]


class ModelRuntime:
    """Owns the mesh, compiled programs, and parameter placement.

    This is the slot where the reference's CUDA-container black box becomes a
    first-class runtime: ``jit`` with a batch sharding over the mesh's data
    axes; XLA lays matmuls/convs onto the MXU and inserts ICI collectives for
    any model-parallel params.
    """

    def __init__(self, mesh: Mesh | None = None, donate_batch: bool = False,
                 replicate_outputs: bool | None = None):
        from ..parallel.sharding import make_mesh
        self.mesh = mesh if mesh is not None else make_mesh()
        self.models: dict[str, ServableModel] = {}
        self._donate = donate_batch
        # Multi-host (mesh spans processes): outputs must come back fully
        # replicated so every process — in particular the primary serving
        # results — can read them without a cross-host gather on the response
        # path (inference outputs are small). Single-host: XLA's choice.
        if replicate_outputs is None:
            replicate_outputs = jax.process_count() > 1
        self._replicate_outputs = replicate_outputs
        # (model, padded-batch-size) programs this process has executed —
        # what apply_ladder checks before a swap. Append-only.
        self._executed_shapes: set[tuple[str, int]] = set()

    @property
    def data_axis_size(self) -> int:
        return (self.mesh.shape["dp"] * self.mesh.shape["fsdp"])

    def register(self, servable: ServableModel,
                 param_sharding_rules: dict | None = None) -> ServableModel:
        """Place params on the mesh and build per-bucket compiled fns."""
        from ..parallel.sharding import pad_to_multiple, shard_params
        rules = (param_sharding_rules if param_sharding_rules is not None
                 else servable.param_sharding_rules)
        servable.params = shard_params(servable.params, self.mesh, rules)
        # SPMD constraint: every batch bucket must divide evenly over the
        # data axes, so buckets round up to mesh multiples (on 1 chip they
        # stay as configured; on a v5e-4 dp mesh they become multiples of 4).
        servable.batch_buckets = tuple(sorted({
            pad_to_multiple(b, self.data_axis_size)
            for b in servable.batch_buckets}))
        batch_sharding = NamedSharding(
            self.mesh, P(("dp", "fsdp"), *([None] * len(servable.input_shape))))
        servable._batch_sharding = batch_sharding

        servable._compiled = jax.jit(
            servable.apply_fn,
            in_shardings=(None, batch_sharding),
            # A single sharding as out_shardings applies to every output leaf.
            out_shardings=(NamedSharding(self.mesh, P())
                           if self._replicate_outputs else None),
            donate_argnums=(1,) if self._donate else (),
        )
        self.models[servable.name] = servable
        return servable

    def warmup(self, names: list[str] | None = None,
               parallel: bool = True) -> dict[str, float]:
        """Precompile every (model, bucket) program. Returns compile seconds
        per model: a worker's boot carries them as attributes of its
        ``boot.batch_warmup`` span, whose seconds are
        ``ai4e_boot_seconds{phase="batch_warmup"}`` (``observability/
        boot.py``).

        ``parallel`` (default): all (model, bucket) programs are AOT
        lowered+compiled concurrently first — XLA releases the GIL during
        compilation, so N programs cost ~max not ~sum on a multi-core host
        — then each bucket executes once through ``run_batch`` (hitting
        the now-warm caches) so the execute path is proven too. Serial mode is kept for
        multi-host runtimes, where every process must enter compiles in
        the same order."""
        todo = [(name, servable) for name, servable in self.models.items()
                if names is None or name in names]

        compile_s = 0.0
        if parallel and not jax.config.jax_compilation_cache_dir:
            # AOT lower().compile() does NOT seed the jit dispatch cache —
            # only the persistent compilation cache carries its work over to
            # the run_batch pass. Without one, parallel mode would compile
            # every program twice; serial is strictly better then.
            log.warning("warmup: persistent compilation cache not enabled "
                        "(enable_compilation_cache(); see docs/"
                        "device_path.md#compile-cache-and-aot-warmup); "
                        "using serial warmup")
            parallel = False
        if parallel and jax.process_count() == 1:
            jobs = [(s, b) for _, s in todo for b in s.batch_buckets]
            compile_s = self._aot_compile(jobs)

        # The concurrent compile phase serves every model at once, so its
        # wall time is amortised evenly across the per-model figures — the
        # returned dict must keep meaning "pod-start seconds attributable
        # to this model", the metric operators watch.
        times: dict[str, float] = {}
        for name, servable in todo:
            t0 = time.perf_counter()
            for bucket in servable.batch_buckets:
                dummy = np.zeros((bucket, *servable.input_shape),
                                 servable.input_dtype)
                # Through run_batch so multi-host input conversion applies.
                self.run_batch(name, dummy)
            times[name] = (time.perf_counter() - t0
                           + compile_s / max(1, len(todo)))
        return times

    def _aot_compile(self, jobs) -> float:
        """Concurrently lower+compile ``(servable, bucket)`` programs —
        the warmup fast path, reused by ``prepare_buckets`` so a derived
        ladder's background compile costs ~max, not ~sum, of its
        programs. Returns wall seconds; surfaces the first compile
        error."""
        from concurrent.futures import ThreadPoolExecutor

        def compile_one(servable, bucket):
            # Described as ``run_batch`` will pass it — with the batch
            # sharding — so the dispatch path finds this lowering in
            # JAX's in-process lowering cache instead of lowering again.
            # A second lowering of a program holding Pallas kernels need
            # not serialise them to the same bytes, and would then miss
            # the persistent-cache entry compiled here: on a cold v5e the
            # execute pass recompiled all three landcover buckets
            # serially (31 s after a 23 s concurrent pass).
            dummy = jax.ShapeDtypeStruct(
                (bucket, *servable.input_shape),
                np.dtype(servable.input_dtype),
                sharding=servable._batch_sharding)
            servable._compiled.lower(servable.params, dummy).compile()

        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=min(8, max(1, len(jobs)))) as ex:
            for f in [ex.submit(compile_one, s, b) for s, b in jobs]:
                f.result()
        return time.perf_counter() - t0

    def prepare_buckets(self, name: str, buckets) -> tuple[int, ...]:
        """Compile + warm-execute a candidate ladder for ``name`` WITHOUT
        swapping it in (the ladder deriver's background step,
        docs/device_path.md). Buckets are rounded up to the mesh's data-
        axis multiple (same SPMD rule ``register`` applies), AOT-compiled
        concurrently when the persistent compilation cache is enabled,
        and each previously-unseen bucket is executed once through
        ``run_batch`` so the jit dispatch cache is warm and the program
        is marked executed — after this returns, ``apply_ladder`` can
        swap with zero serving-path compiles. Returns the aligned tuple
        to pass to ``apply_ladder``."""
        from ..parallel.sharding import pad_to_multiple
        servable = self.models[name]
        aligned = tuple(sorted({
            pad_to_multiple(int(b), self.data_axis_size) for b in buckets}))
        if not aligned:
            raise ValueError(f"empty ladder for {name}")
        todo = [b for b in aligned
                if (name, b) not in self._executed_shapes]
        if not todo:
            return aligned
        if jax.process_count() == 1 and jax.config.jax_compilation_cache_dir:
            self._aot_compile([(servable, b) for b in todo])
        for bucket in todo:
            dummy = np.zeros((bucket, *servable.input_shape),
                             servable.input_dtype)
            self.run_batch(name, dummy)
        return aligned

    def apply_ladder(self, name: str, buckets) -> tuple[int, ...]:
        """Atomically swap ``name``'s serving ladder to ``buckets`` (the
        tuple ``prepare_buckets`` returned). The swap is one attribute
        assignment — in-flight batch cuts hold the old tuple, whose
        programs stay compiled (``_executed_shapes`` is append-only), so
        no request on either side of the swap ever pads to a bucket
        without a compiled program. Refuses any bucket that has not been
        executed — the invariant the ladder-swap interleaving regression
        (tests/test_race_regressions.py) pins."""
        servable = self.models[name]
        aligned = tuple(sorted({int(b) for b in buckets}))
        missing = [b for b in aligned
                   if (name, b) not in self._executed_shapes]
        if missing:
            raise RuntimeError(
                f"apply_ladder({name}): buckets {missing} have no "
                f"executed program — call prepare_buckets first")
        servable.batch_buckets = aligned
        return aligned

    def reload_params(self, name: str, new_params) -> "ServableModel":
        """Hot-swap a registered servable's weights — zero-downtime model
        update (the reference rolls whole containers for this,
        ``APIs/Charts/templates/async-gpu``; here the jitted programs take
        params as an ARGUMENT, so new weights need no recompile).

        The new tree must match the current one exactly (structure, shapes,
        dtypes) — reload updates weights, never architecture; a geometry
        change is a new model spec + restart. The swap is a single attribute
        assignment: in-flight batches already hold the old reference and
        complete on it; every later ``run_batch`` picks up the new params.
        """
        from ..parallel.sharding import shard_params
        servable = self.models[name]  # KeyError → caller's 404

        def spec_of(tree):
            return jax.tree.map(
                lambda a: (tuple(a.shape), jnp.result_type(a).name), tree)

        old_spec, new_spec = spec_of(servable.params), spec_of(new_params)
        if old_spec != new_spec:
            raise ValueError(
                f"checkpoint tree does not match the served model: "
                f"served {old_spec} vs reload {new_spec}")
        placed = shard_params(new_params, self.mesh,
                              servable.param_sharding_rules)
        servable.params = placed
        servable.params_version += 1
        return servable

    def run_batch(self, name: str, batch: np.ndarray):
        """Execute one padded batch; blocking (call from an executor)."""
        servable = self.models[name]
        if isinstance(batch, np.ndarray):
            if jax.process_count() > 1:
                # A raw numpy batch on a multi-host slice means every
                # process holds the identical full array (warmup dummies);
                # carve out this process's shards to form the global device
                # array the multi-host jit requires. Serving batches arrive
                # pre-assembled as global jax.Arrays from MultihostRuntime's
                # sharded ingestion.
                batch = jax.make_array_from_process_local_data(
                    servable._batch_sharding, batch,
                    global_shape=batch.shape)
            else:
                # Placed exactly as the phased and split-phase paths place
                # it. The jit's dispatch cache keys on how its argument
                # arrived: a raw numpy batch here and a sharded device
                # array there are two entries, and warmup (which comes
                # through here) would leave the phased serving path to
                # trace, lower and compile every bucket again on its first
                # request — seconds per bucket for the deployed UNet on a
                # v5e, filed under ``execute``.
                batch = jax.device_put(batch, servable._batch_sharding)
        out = servable._compiled(servable.params, batch)
        self._executed_shapes.add((name, batch.shape[0]))
        return fetch_to_host(out)

    def _execute_blocked(self, name: str, device_batch
                         ) -> tuple[object, str, tuple[float, float]]:
        """The compiled program on a resident batch, blocked until its
        outputs materialise: ``(device_outputs, label, (t0, t1))``. The
        label is ``"compile"`` when the call grew the jit's dispatch cache
        — it traced and compiled (or loaded from the persistent cache)
        instead of dispatching a program warmup had built. Read off the
        cache itself, not off bookkeeping, so the stall warmup exists to
        pre-pay cannot hide under ``execute``."""
        servable = self.models[name]
        programs = servable._compiled._cache_size()
        t0 = time.perf_counter()
        out = servable._compiled(servable.params, device_batch)
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        compiled = servable._compiled._cache_size() > programs
        self._executed_shapes.add((name, device_batch.shape[0]))
        return out, ("compile" if compiled else "execute"), (t0, t1)

    def run_batch_report(self, name: str, batch: np.ndarray
                         ) -> tuple[object, frozenset]:
        """``run_batch`` plus a poisoned-rows report — uniform surface with
        ``MultihostRuntime.run_batch_report`` so the batcher can fail exactly
        the rows a degraded follower invalidated. A single-runtime execution
        has no partial-degrade mode: the set is always empty (a device
        failure raises and fails the whole batch)."""
        return self.run_batch(name, batch), frozenset()

    def run_batch_phases(self, name: str, batch: np.ndarray
                         ) -> tuple[object, frozenset, dict[str, float]]:
        """``run_batch_report`` with the device boundary decomposed into
        measured phases (observability/, docs/observability.md):

        - ``h2d``: explicit ``device_put`` of the padded batch onto the
          mesh sharding, blocked until resident;
        - ``execute``: the compiled program on the already-resident
          batch, blocked until outputs materialize — reported as
          ``compile`` instead when the call had to build the program
          (``_execute_blocked``; warmup normally eats these; a
          serving-path compile is exactly the stall an operator needs to
          see named);
        - ``d2h``: ``device_get`` of the outputs.

        Returns ``(host_outputs, poisoned_rows, {phase: seconds})``.
        Single-host only — the batcher falls back to ``run_batch_report``
        (one undecomposed ``execute``) on runtimes without this method
        (multi-host mirrors every call and must not diverge per phase).
        """
        servable = self.models[name]
        if jax.process_count() > 1:
            # Phase decomposition would desynchronise the follower
            # mirror-loop's single-call contract; undecomposed fallback.
            out, poisoned = self.run_batch_report(name, batch)
            return out, poisoned, {}
        phases: dict[str, float] = {}
        rows = batch.shape[0]
        t0 = time.perf_counter()
        with device_trace("ai4e.batch.h2d", model=name, rows=rows):
            device_batch = jax.device_put(batch, servable._batch_sharding)
            jax.block_until_ready(device_batch)
        phases["h2d"] = time.perf_counter() - t0
        with device_trace("ai4e.batch.execute", model=name, rows=rows):
            out, label, (t0, t1) = self._execute_blocked(name, device_batch)
        phases[label] = t1 - t0
        t0 = time.perf_counter()
        with device_trace("ai4e.batch.d2h", model=name, rows=rows):
            host = fetch_to_host(out)
        phases["d2h"] = time.perf_counter() - t0
        return host, frozenset(), phases

    # -- split-phase surface (double-buffered batcher) ---------------------
    #
    # The three device-boundary steps of run_batch_phases as separate
    # blocking calls, each returning its (perf-counter start, end) wall
    # window — the MicroBatcher's double-buffered path runs them on
    # separate single-thread executors so batch N+1's h2d genuinely
    # overlaps batch N's execute and batch N's d2h overlaps batch N+1's
    # execute (docs/device_path.md#double-buffered-transfers). Single-
    # host only: the batcher falls back to the fused path on runtimes
    # without ``supports_split_phases`` (MultihostRuntime mirrors every
    # call and must not diverge per phase).

    def supports_split_phases(self) -> bool:
        return jax.process_count() == 1

    def h2d_resident(self, name: str, batch: np.ndarray):
        """``device_put`` the padded batch onto the mesh sharding,
        blocked until resident. Returns ``(device_batch, (t0, t1))``."""
        servable = self.models[name]
        t0 = time.perf_counter()
        device_batch = jax.device_put(batch, servable._batch_sharding)
        jax.block_until_ready(device_batch)
        return device_batch, (t0, time.perf_counter())

    def execute_resident(self, name: str, device_batch):
        """Run the compiled program on an already-resident batch, blocked
        until outputs materialize on device. Returns ``(device_outputs,
        label, (t0, t1))`` where label is ``"compile"`` when the call had
        to build the program — warmup normally eats these — else
        ``"execute"``."""
        return self._execute_blocked(name, device_batch)

    def fetch_resident(self, out):
        """``device_get`` the outputs. Returns ``(host_outputs,
        (t0, t1))``."""
        t0 = time.perf_counter()
        host = fetch_to_host(out)
        return host, (t0, time.perf_counter())


def enable_compilation_cache() -> str:
    """Persistent XLA compilation cache: restarts skip recompiles (the
    warmup-at-start requirement in SURVEY.md §7 hard parts), and parallel
    warmup hands its AOT compiles to the jit dispatch path through it.

    The directory is decided outside the program (``config.
    compile_cache_dir``): where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has
    already read it and nothing is set here, so whoever runs the program
    owns where the cache lives and whether it survives the machine;
    otherwise it is one fixed git-ignored path in the checkout, the same
    from every process. Returns the directory in force.

    Does not touch a backend: ``cli.build_worker`` calls this before
    ``jax.distributed`` comes up.
    """
    import os

    from ..config import compile_cache_dir
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # Persist every program, not only those over JAX's 1 s default: warmup's
    # concurrent AOT pass reaches the run_batch pass only through this
    # cache, so a program under the threshold would compile twice. Disk
    # cost is a few KB each.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_report(mesh: Mesh | None = None) -> dict:
    """What this process executes on, as JAX reports it — the worker's
    posture line and ``GET {prefix}/models`` carry it so no result is read
    without knowing the device behind it. ``mesh`` adds the serving mesh's
    axis sizes. Initialises the backend."""
    from importlib import metadata
    devices = jax.devices()

    def version(dist: str) -> str | None:
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            return None

    report = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "device_count": len(devices),
              "versions": {d: version(d)
                           for d in ("jax", "jaxlib", "libtpu")}}
    if mesh is not None:
        report["mesh"] = {axis: int(n) for axis, n in mesh.shape.items()}
    # Per-device HBM, where the backend keeps the figures (XLA:CPU does
    # not): the evidence for which devices a sharded batch really touched.
    memory = [(d.id, d.memory_stats()) for d in jax.local_devices()]
    if all(stats for _, stats in memory):
        report["memory"] = [
            {"device": i, "bytes_in_use": stats["bytes_in_use"],
             "peak_bytes_in_use": stats["peak_bytes_in_use"]}
            for i, stats in memory]
    return report
