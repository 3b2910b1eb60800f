"""Continuous-batching decode engine — iteration-level scheduling.

The MicroBatcher's contract is whole-batch-in/whole-batch-out: a batch
is cut, runs to completion, fans out. Autoregressive decoding under that
contract is a throughput disaster — one 512-token sequence holds a batch
of 8-token completions hostage for its entire decode. This engine is the
second serving path, beside the batcher, where scheduling happens
*inside* the device loop:

- new requests join the running batch BETWEEN decode steps: a prefill is
  admitted into a free KV-cache slot the moment one exists (padded to
  the prompt-bucket ladder, ``ladder.DECODE_PROMPT_BUCKETS`` discipline);
- every decode step advances EVERY active sequence by one token; each
  token is handed to the request's ``on_token`` callback the moment it
  exists (the worker publishes it as a ``chunk`` event through the
  ``TaskEventHub``, so ``GET /task/{id}/events`` streams tokens live);
- finished sequences (EOS / ``max_new_tokens`` / KV-cache slot full)
  leave between steps and free their slot immediately;
- a per-step deadline sweep frees an EXPIRED sequence's slot mid-decode
  instead of completing it late (admission/: dead work never holds a
  slot), and a cancelled waiter (client gone) is retired the same way;
- a hot weight reload (``params_version`` bump) invalidates the pooled
  KV cache — same contract as rescache — and active sequences are
  re-prefilled from their token history under the new weights, keeping
  their slots.

The loop runs ONE STEP BEHIND the device, always: step N+1 is launched
before step N's ids are read, so the device has its next step queued while
the host fetches, notes tokens, sweeps and admits. What a launch needs but
the ids — positions, the rung, an end by token budget or cache length — the
host counts ahead; the ids themselves stay with the backend between steps,
and only a slot re-prefilled since the last launch is fed from the host. A
sequence that ends by count is never in the next launch; one that ends by
``eos_id``, a cancel, an expiry or a drain while a launched step holds it
costs that one slot-step, discarded at its fetch and counted.

A join does not stop the loop either. ``_admit`` dispatches each queued
prompt's prefill (``join``) and goes straight on to the next: a prompt's
FIRST id stays with the backend too, the step launched after the pass feeds
the slot from it, and the host reads it with that step's fetch (``fed``),
beside the second. So a sequence is: dispatched, carried by a step, read. A
request for one token, or whose first id is ``eos_id``, is not known to be
finished when that step is launched and rides it as a discarded slot-step;
where nothing else rides, nothing is launched and the ids are read alone
(``first_ids``).

Slot conservation is THE invariant (tests/test_race_regressions.py):
a slot is never double-assigned, never leaked, and freed exactly once.
Every release funnels through ``_retire`` — a single-segment method
(docs/concurrency.md): the ``done`` guard and the slot release share one
atomicity segment, and every post-``await`` consumer re-checks ``done``
before acting on a sequence (the step/prefill awaits are the suspension
windows a cancel or expiry sweep can slot into; so is the window between
a step's launch and its fetch). A slot whose sequence is retired while a
launched step still has it live stays busy — parked — until that step is
fetched or voided: a join is never written under a step launched for the
slot's previous tenant.

Backpressure: ``pending_count`` at ``max_pending`` → ``submit`` raises
``DecodeSaturated`` and the worker answers 503 through the existing
admission path, exactly like ``BatcherSaturated``.

This module imports neither JAX nor numpy: the device work lives behind
the backend interface (``runtime/kvcache.py``), so the race-smoke CI job
(no JAX toolchain) explores the real engine under the deterministic
scheduler.
"""

from __future__ import annotations

import asyncio
import contextlib
import inspect
import logging
import time
from collections import deque
from dataclasses import dataclass, field

from ..admission.deadline import DeadlineExceeded, priority_name
from ..metrics import DEFAULT_REGISTRY, MetricsRegistry
from ..observability.tracing import device_trace
from ..rollout.drain import DrainingError

log = logging.getLogger("ai4e_tpu.decode")

# One step's submit to the next step's submit, partitioned where the work
# happens (docs/observability.md "Decode-tick decomposition"). ``yield`` is
# the rest of the interval: reload check, sweep, the loop given to others.
TICK_PHASES = ("prepare", "handoff", "dispatch", "device_wait", "return",
               "bookkeeping", "admit", "yield")
# Most phases are 50 us - 1 ms, the device's run tens of ms: the default
# ladder starts at 1 ms and would put seven of the eight in its first bucket.
_TICK_BUCKETS = (25e-6, 50e-6, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 0.01,
                 0.025, 0.05, 0.1, 0.25, 1.0, float("inf"))
# The device thread's ledger (docs/observability.md "The device thread's
# ledger"): why the device had nothing queued, what a join's seconds waited
# for, what a request's queue wait was spent behind.
UNQUEUED_CAUSES = ("empty", "join", "loop")
JOIN_PARTS = ("hops", "dispatch", "behind_step", "run", "turnaround")
QUEUE_WAIT_PARTS = ("slot", "joins", "tick")


class DecodeSaturated(RuntimeError):
    """No pending capacity — the worker's admission path answers 503."""


class SlotError(RuntimeError):
    """A slot-conservation violation (double release / foreign release /
    double assignment) — raised immediately so the interleaving explorer
    and the chaos invariants see the exact violating step."""


class SlotPool:
    """KV-cache slot accounting. Pure bookkeeping — the device-side
    buffers live in ``runtime/kvcache.py``; this object is the single
    source of truth for which slots are free, and it RAISES on any
    conservation violation instead of silently absorbing it."""

    def __init__(self, slots: int):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.slots = slots
        self._free = list(range(slots - 1, -1, -1))  # LIFO: slot 0 first
        self._busy: set[int] = set()

    def acquire(self) -> int | None:
        """A free slot, or None when the pool is full."""
        if not self._free:
            return None
        slot = self._free.pop()
        if slot in self._busy:
            raise SlotError(f"slot {slot} double-assigned")
        self._busy.add(slot)
        return slot

    def release(self, slot: int) -> None:
        if slot not in self._busy:
            raise SlotError(
                f"slot {slot} released while not held (double free or "
                f"foreign free); busy={sorted(self._busy)}")
        self._busy.remove(slot)
        self._free.append(slot)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def busy_count(self) -> int:
        return len(self._busy)

    def check_conservation(self) -> None:
        """Every slot is exactly one of free/busy — the post-run check
        the race regressions assert."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise SlotError(f"free list holds duplicates: {self._free}")
        if free & self._busy:
            raise SlotError(
                f"slots both free and busy: {sorted(free & self._busy)}")
        if len(free) + len(self._busy) != self.slots:
            raise SlotError(
                f"slot leak: {len(free)} free + {len(self._busy)} busy "
                f"!= {self.slots}")


@dataclass
class LaunchedStep:
    """One decode step as its backend launched it. What the backend worked
    out at the launch travels with the step it describes; ``fetch`` fills in
    what only the device knew."""

    bound: int                    # positions of every slot the step covers
    # K/V positions its attention reads; None: ``slots x bound``.
    attended: int | None = None
    # Positions its softmax kept, a layer, where the read selects; None:
    # it keeps all it reads.
    selected: int | None = None
    # Bytes of each kind of cache it reads and writes, ``{kind: bytes}``.
    cache_bytes: dict = field(default_factory=dict)
    # Of the fixed-size state: ``moved`` (what it reads and writes) and
    # ``live`` (the part of that which belongs to slots with a live
    # sequence); empty from a backend whose slots hold K/V alone.
    state_bytes: dict = field(default_factory=dict)
    active: list = field(default_factory=list)   # the launch's live slots
    out: object = None            # the backend's own hold on the unread ids
    # The step before it was unread and already finished at this launch: the
    # device was idle, and the host set the pace.
    starved: bool = False
    ids: list | None = None       # after ``fetch``: next token id per slot
    # After ``fetch``: the token each slot was fed. None at a slot: the
    # backend kept that to itself (one the host fed, or a step's id).
    fed: list | None = None
    # After ``fetch``: the model's figures of this step, ``{name: value}``.
    report: dict = field(default_factory=dict)


class _Adapted:
    """``join`` / ``launch`` / ``fetch`` / ``first_ids`` over a backend that
    lacks some of them, so the engine has one loop whatever it is handed. A
    backend with only ``prefill_into`` (sync, or async as the race tests'
    fakes) joins by running it: the first id it returned waits here and is
    fed to the slot at the next launch, as it waits on the device in
    ``runtime/kvcache.py``. One with only a blocking ``step(tokens,
    positions, active) -> ids`` launches by running the whole step and
    holding its result; the ids of the last step stay here."""

    def __init__(self, backend):
        self.backend = backend
        self._own_steps = not hasattr(backend, "launch")
        self._ids = [0] * backend.slots
        self._first = {}   # slot -> a join's first id, not yet fed
        if inspect.iscoroutinefunction(backend.prefill_into):
            self.join = self._join_async
        if inspect.iscoroutinefunction(
                backend.step if self._own_steps else backend.launch):
            self.launch = self._launch_async

    def join(self, slot, tokens) -> None:
        self._first[slot] = int(self.backend.prefill_into(slot, tokens))

    async def _join_async(self, slot, tokens) -> None:
        self._first[slot] = int(await self.backend.prefill_into(slot, tokens))

    def first_ids(self) -> list:
        return [self._first.pop(slot, None)
                for slot in range(self.backend.slots)]

    def _start(self, fresh, positions, active):
        """The backend's call of one launch, and what it feeds each slot:
        the host's token, else a joined prompt's first id, else — where the
        last step's ids stay here — that step's."""
        fed = [self._first.pop(slot, None) if token is None else token
               for slot, token in enumerate(fresh)]
        if not self._own_steps:
            return self.backend.launch(fed, positions, active), fed
        fed = [0 if not live else self._ids[slot] if token is None else token
               for slot, (token, live) in enumerate(zip(fed, active))]
        return self.backend.step(fed, positions, active), fed

    def _record(self, step, fed, active) -> LaunchedStep:
        if self._own_steps:
            backend = self.backend
            self._ids = [int(t) for t in step]
            step = LaunchedStep(
                bound=getattr(backend, "step_bound", backend.max_len),
                attended=getattr(backend, "step_attended", None),
                cache_bytes=dict(getattr(backend, "step_cache_bytes", {})),
                state_bytes=dict(getattr(backend, "step_state_bytes", {})),
                active=active, ids=self._ids,
                report=dict(getattr(backend, "step_report", {})))
        step.fed = fed
        return step

    def launch(self, fresh, positions, active) -> LaunchedStep:
        step, fed = self._start(fresh, positions, active)
        return self._record(step, fed, active)

    async def _launch_async(self, fresh, positions, active) -> LaunchedStep:
        step, fed = self._start(fresh, positions, active)
        return self._record(await step, fed, active)

    def fetch(self, step: LaunchedStep):
        return step if self._own_steps else self.backend.fetch(step)


class _CallClock:
    """``time.perf_counter`` of one backend call, read where each thing
    happens: ``submit`` on the loop before the hop to the device thread,
    ``entered``/``left`` as the first and last lines inside that thread,
    ``resumed`` when the awaiting coroutine runs again. ``wait`` is what the
    backend's ``phase_hook`` reported as blocked on the device (None from a
    backend without the hook); ``ledger`` is the request the call serves,
    for the hook's ``compile`` stamp; ``join`` is None, or of a join the
    seconds the hook reported by part (``behind_step``, ``run``)."""

    __slots__ = ("submit", "entered", "left", "resumed", "wait", "ledger",
                 "join")

    def __init__(self, ledger=None, join=None):
        self.wait = None
        self.ledger = ledger
        self.join = join

    def run(self, fn, args):
        self.entered = time.perf_counter()
        try:
            return fn(*args)
        finally:
            self.left = time.perf_counter()


@dataclass
class _Flight:
    """A launched step the host has not read: who rides it. ``step`` is the
    backend's record once the launching call has returned."""

    # (slot, sequence, position, first) of its launch; ``first``: the slot
    # was fed a joined prompt's first id, which the host has not seen.
    snapshot: list
    tick: int                     # the tick that launched it
    step: LaunchedStep | None = None

    def __post_init__(self):
        self._slots = frozenset(entry[0] for entry in self.snapshot)

    def holds(self, slot: int) -> bool:
        """Whether this step has ``slot`` live."""
        return slot in self._slots


@dataclass
class _Sequence:
    """One streaming request's decode state."""

    prompt: tuple  # int token ids
    future: asyncio.Future
    max_new_tokens: int
    on_token: object = None       # callable (index, token) -> None
    priority: int = 0
    deadline_at: float = 0.0      # absolute unix seconds; 0.0 = none
    ledger: object = None         # observability.ledger.HopLedger | None
    tokens: list = field(default_factory=list)  # generated ids
    slot: int | None = None
    position: int = 0             # next KV write index (= prompt + generated)
    done: bool = False
    enqueued: float = field(default_factory=time.perf_counter)
    last_token_at: float = 0.0
    first_tick: int = 0           # the tick that gave it its slot
    lacked: float = 0.0           # ``_lacked_slot`` when it was enqueued


class DecodeEngine:
    """The iteration-level scheduling loop over a decode-step backend.

    ``backend`` (``runtime/kvcache.py`` for the real device; tests
    inject fakes) exposes:

    - ``slots`` / ``max_len`` / ``eos_id`` / ``name``;
    - ``params_version`` (property): bumped by hot reload — the pooled
      cache key, checked every tick;
    - ``reset_cache()``: drop + reallocate the pooled cache (reload
      invalidation);
    - ``prefill_into(slot, tokens) -> first generated token id``: the join
      that blocks and reads (a reload's re-prefill);
    - ``join(slot, tokens)``: dispatch the same prefill and return without
      reading it. The first id stays with the backend: the next launch
      feeds the slot from it where ``fresh[slot]`` is None, and that step's
      fetch hands it over as ``fed[slot]``. The backend keeps at most two
      joins in flight on the device;
    - ``first_ids() -> ids``: block until every dispatched join has run and
      return, a slot, the id the next launch would feed it — for a pass
      whose requests all want one token, which no step carries;
    - ``launch(fresh, positions, active) -> LaunchedStep``: start one decode
      step and return without reading it. Plain lists, one entry a slot:
      ``fresh[slot]`` is the token the host feeds (a slot re-prefilled since
      the last launch) or None — the slot's token is the id the LAST
      LAUNCHED step, or the prefill joined since, gave it, which the backend
      kept. The record
      carries what the backend worked out at the launch: ``bound`` (observed
      as ``ai4e_decode_step_bound``), ``attended`` (counted as attended K/V
      positions), ``selected`` (counted beside them where the read selects),
      ``cache_bytes`` (``ai4e_decode_cache_bytes_total{kind}``),
      ``state_bytes`` (``ai4e_decode_state_bytes_total{kind}``);
    - ``fetch(step) -> step``: block until that step has run and fill in
      ``ids`` (next token id per slot), ``fed`` (the token each slot was
      fed) and ``report`` (``{name: value}``, observed as
      ``ai4e_decode_<name>``). Steps are fetched in the order they were
      launched; a failure — a step's or a joined prefill's — surfaces here;
    - or, in place of ``launch`` and ``fetch``, only a blocking
      ``step(tokens, positions, active) -> ids`` with its figures left in
      ``step_bound`` / ``step_attended`` / ``step_cache_bytes`` /
      ``step_state_bytes`` / ``step_report`` attributes, and in place of
      ``join`` and ``first_ids`` only ``prefill_into`` (the tests' fakes):
      ``_Adapted`` supplies what is missing;
    - optionally ``step_report_series`` (attribute, ``{name: (help,
      buckets)}``): what a model that reports on its step declares;
    - optionally ``prefill_report(n) -> {series: {kind: count}}``: what the
      prefill of a prompt of ``n`` tokens computes, from the host's length
      alone, counted after every join as
      ``ai4e_decode_prefill_<series>_total{kind}`` (``tokens``: real,
      padded; ``pairs``: by the kinds the backend's cache declares). A
      backend without it registers neither series;
    - optionally ``report_kinds`` (attribute, names) and ``join_report(slot)
      -> {kind: count}``: what the prefill last joined into ``slot`` counted
      on the device, handed over once, after the read that brought its first
      id to the host (a ``fetch``, ``first_ids``, ``prefill_into``) brought
      it along — counted as
      ``ai4e_decode_prefill_expert_passes_total{kind}`` (``first``,
      ``extra``). A backend whose ``report_kinds`` is empty registers
      nothing;
    - optionally ``bound_for(longest)``: the bound a step whose largest
      live position is ``longest`` will run — the ``bound=`` of the
      ``ai4e.decode.tick`` region, which opens before the launch;
    - optionally ``phase_hook`` (attribute, None until the engine installs
      ``hook(phase, seconds)``): called inside a backend call with
      ``device_wait`` (seconds of ``fetch`` blocked on the device; the rest
      of the in-thread time is ``dispatch``) and ``compile`` (a call that
      grew a program's dispatch cache). Without it the whole in-thread time
      of a step is booked as ``device_wait``. The same hook feeds the device
      thread's ledger — ``enqueue`` (0 seconds: the line before a prefill or
      a step is dispatched), ``behind_step`` and ``run`` (a join's two
      waits), ``readback`` (of a fetch's ``device_wait``, the ids' copy) —
      and a backend without it registers none of that ledger's series.

    Backend methods may be sync (run on the engine's single device
    executor thread — the device is the serial resource, same discipline
    as the batcher) or async (the race tests' fakes, explored under the
    virtual loop).
    """

    def __init__(self, backend, max_pending: int = 64,
                 metrics: MetricsRegistry | None = None):
        self.backend = backend
        # The one surface of joins and steps: a backend with only a blocking
        # ``prefill_into`` or ``step`` is adapted here and nowhere else.
        self._steps = (backend if hasattr(backend, "join")
                       and hasattr(backend, "launch") else _Adapted(backend))
        self._advance = (
            self._advance_async
            if inspect.iscoroutinefunction(self._steps.launch)
            else self._advance_in_thread)
        self.max_pending = max_pending
        self.pool = SlotPool(backend.slots)
        self._queue: deque[_Sequence] = deque()
        self._active: dict[int, _Sequence] = {}
        # Steps launched and not yet read, oldest first: one between ticks,
        # two while a tick's call launches the next and fetches the last.
        self._launched: deque[_Flight] = deque()
        # Slots whose sequence was retired while a launched step had them
        # live: busy in ``pool`` with no tenant, released once no launched
        # step holds them.
        self._parked: set[int] = set()
        self._wakeup = asyncio.Event()
        self._stop = False
        # Rollout drain (rollout/drain.py): stop admitting prefills but
        # let ACTIVE sequences decode to completion — bounded by the
        # caller's drain budget, after which ``force_drain`` retires the
        # stragglers (each redelivers through the broker per task).
        self._draining = False
        self._loop_task: asyncio.Task | None = None
        self._executor = None
        self._cache_version = None
        self.metrics = metrics or DEFAULT_REGISTRY
        name = getattr(backend, "name", "lm")
        self._model = name
        self._ttft = self.metrics.histogram(
            "ai4e_decode_ttft_seconds",
            "Submit-to-first-token latency per streaming request")
        self._intertoken = self.metrics.histogram(
            "ai4e_decode_intertoken_seconds",
            "Gap between consecutive tokens of one sequence")
        self._step_hist = self.metrics.histogram(
            "ai4e_decode_step_seconds",
            "Host clock around the executor call of one engine step, by "
            "phase (prefill/decode): thread hops, launch, the device's run "
            "and the fetch of the ids")
        self._tick_hist = self.metrics.histogram(
            "ai4e_decode_tick_seconds",
            "One step's submit to the next step's submit, partitioned by "
            "phase (prepare/handoff/dispatch/device_wait/return/"
            "bookkeeping/admit/yield)", buckets=_TICK_BUCKETS)
        self._queue_wait = self.metrics.histogram(
            "ai4e_decode_queue_wait_seconds",
            "Engine enqueue to KV-cache slot acquired, per request")
        self._step_active = self.metrics.histogram(
            "ai4e_decode_step_active_slots",
            "Active slots of each decode step (the batch size per step)",
            buckets=(*range(1, backend.slots + 1), float("inf")))
        self._step_bound = self.metrics.histogram(
            "ai4e_decode_step_bound",
            "Positions of every slot each decode step covered (the rung of "
            "the backend's step programs it ran; max_len without rungs)",
            buckets=(*getattr(backend, "step_bounds", (backend.max_len,)),
                     float("inf")))
        # What a backend whose model reports on its step declares; a model
        # that reports nothing registers nothing.
        self._step_report = {
            name: self.metrics.histogram(f"ai4e_decode_{name}", help_text,
                                         buckets=buckets)
            for name, (help_text, buckets) in getattr(
                backend, "step_report_series", {}).items()}
        self._kv_positions = self.metrics.counter(
            "ai4e_decode_kv_positions_total",
            "K/V positions per decode step: live (sum of position + 1 over "
            "active slots), attended (what the step's attention read: "
            "the backend's count, else slots x the step's bound) and, from a "
            "backend whose read selects, selected (what its softmax kept)")
        # What a backend that reports on its prefills (``prefill_report``)
        # counts; one that does not registers nothing.
        self._prefill_work = {} if not hasattr(
            backend, "prefill_report") else {
            series: self.metrics.counter(
                f"ai4e_decode_prefill_{series}_total", help_text)
            for series, help_text in (
                ("tokens", "Tokens the joined prefills computed, by kind: "
                 "real (the prompt's) and padded (the bucket's), as the "
                 "backend counts them"),
                ("pairs", "(query, key) pairs of the joined prefills' "
                 "attention, a layer, by the kinds the backend's cache "
                 "declares"))}
        # What a backend's prefills report from the device beside their first
        # id (``join_report``): the passes their expert layers took. None
        # from a backend that reports nothing.
        self._expert_passes = None
        if getattr(backend, "report_kinds", ()):
            self._expert_passes = self.metrics.counter(
                "ai4e_decode_prefill_expert_passes_total",
                "Passes the joined prefills' expert layers took over their "
                "window of held (row, pick) pairs, by kind: first (a layer's "
                "one pass) and extra (those beyond it: more pairs landed on "
                "the experts held here than one and a half times an even "
                "router's share)")
        self._cache_bytes = self.metrics.counter(
            "ai4e_decode_cache_bytes_total",
            "Bytes of the slots' cache a decode step read and wrote, by "
            "kind: kv (the K/V rows its attention read and the row a live "
            "slot wrote) and state (fixed-size per-slot state, read and "
            "written whole), as the backend counts them")
        self._state_bytes = self.metrics.counter(
            "ai4e_decode_state_bytes_total",
            "Bytes of the slots' fixed-size state a decode step read and "
            "wrote, by kind: moved (all of them) and live (those of slots "
            "with a live sequence), as the backend counts them; live / "
            "moved is what a step over live slots only would leave")
        self._tick_joins = self.metrics.histogram(
            "ai4e_decode_tick_joins",
            "Prefills admitted between two launched decode steps, observed "
            "on every tick that admitted at least one: each stops every "
            "live stream for its duration",
            buckets=(*range(1, backend.slots + 1), float("inf")))
        self._occupancy = self.metrics.gauge(
            "ai4e_decode_slot_occupancy",
            "Occupied KV-cache slots / total slots per model")
        self._pending_gauge = self.metrics.gauge(
            "ai4e_decode_pending",
            "Streaming requests waiting for a KV-cache slot")
        self._tokens_total = self.metrics.counter(
            "ai4e_decode_tokens_total", "Generated tokens per model")
        self._sequences_total = self.metrics.counter(
            "ai4e_decode_sequences_total",
            "Finished sequences by model and outcome")
        self._reprefills_total = self.metrics.counter(
            "ai4e_decode_reprefills_total",
            "Active sequences re-prefilled after a hot-reload "
            "KV-cache invalidation")
        self._expired_total = self.metrics.counter(
            "ai4e_admission_expired_total",
            "Requests dropped on deadline expiry, by hop/priority")
        self._launches = self.metrics.counter(
            "ai4e_decode_step_launches_total",
            "Decode steps launched, by kind: all, and ahead (launched while "
            "the step before it was unread, so the device had it queued "
            "before the host read the last ids)")
        self._joins_total = self.metrics.counter(
            "ai4e_decode_joins_total",
            "Prefills joined into a slot, by kind: all, and ahead (the host "
            "did not wait for its first id: read with the fetch of the first "
            "step that carried the slot)")
        self._discarded = self.metrics.counter(
            "ai4e_decode_discarded_slot_steps_total",
            "Slot-steps computed for a sequence that had ended (EOS, "
            "cancel, expiry, drain) while a launched step held it")
        # The device thread's ledger. ``_drained``: the instant that thread
        # saw the device's queue empty (a blocking prefill's wait returned,
        # or a fetch with nothing launched after it) and whether a prefill's
        # wait it was; None while the device has work queued — as after a
        # join, which returns with its prefill queued — or nobody knows.
        self._books = hasattr(backend, "phase_hook")
        self._drained: tuple[float, bool] | None = None
        self._idled = self._idling = False   # the idle wait: passed, inside
        # Seconds an ``_admit`` pass had returned for want of a free slot:
        # the closed periods, and since when the open one.
        self._no_slot_s, self._no_slot_from = 0.0, None
        if self._books:
            self._unqueued = self.metrics.counter(
                "ai4e_decode_device_unqueued_seconds_total",
                "Seconds the device had nothing queued by the decode thread, "
                "by cause: from that thread seeing the queue drained (a "
                "blocking prefill's wait returned, only its insert trailing; "
                "or a fetch with no later step launched) to its next "
                "dispatch. empty: the engine passed through its idle wait "
                "(the load's); join: begins at a blocking prefill's wait or "
                "ends at a prefill's dispatch; loop: the rest (a settle "
                "followed by a step)")
            # Every cause reads a number from the start: a join that returns
            # with its prefill queued books none, and a worker that is never
            # idle would otherwise have no series at all.
            for cause in UNQUEUED_CAUSES:
                self._unqueued.inc(0.0, model=self._model, cause=cause)
            self._join_hist = self.metrics.histogram(
                "ai4e_decode_join_seconds",
                "One join's seconds by what the device thread waited for "
                "(hops/dispatch/behind_step/run add up to its call, "
                "ai4e_decode_step_seconds{phase=prefill}; run: a prefill's "
                "run — the join two before it, at most two being in flight, "
                "and its own where the call reads the id), and turnaround: "
                "the join-caused unqueued interval that follows its wait, 0 "
                "where the call returned with its prefill queued",
                buckets=_TICK_BUCKETS)
            self._readback = self.metrics.histogram(
                "ai4e_decode_fetch_readback_seconds",
                "Of a fetch's device_wait, the ids' copy to the host after "
                "the step was seen finished", buckets=_TICK_BUCKETS)
            self._queue_part = self.metrics.histogram(
                "ai4e_decode_queue_wait_part_seconds",
                "ai4e_decode_queue_wait_seconds by what the request waited "
                "behind: slot (no free slot), joins (the same admit pass's "
                "earlier prefills), tick (the rest: the loop was elsewhere)")
        self._tick_no = 0
        self._joins = 0   # prefills admitted since the last launched step
        # Seconds booked since the previous step's submit, every phase but
        # ``yield``; ``_last_submit`` is None after an idle wait or a tick
        # without a step, so an idle engine is not a long tick.
        self._phase = dict.fromkeys(TICK_PHASES[:-1], 0.0)
        self._last_submit: float | None = None
        self._inflight: _CallClock | None = None
        if hasattr(backend, "phase_hook"):
            backend.phase_hook = self._on_backend_phase

    # -- request side ------------------------------------------------------

    @property
    def pending_count(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return len(self._active)

    async def submit(self, prompt, max_new_tokens: int, on_token=None,
                     priority: int = 0, deadline_at: float = 0.0,
                     ledger=None) -> list:
        """Queue one streaming generation; resolves to the generated
        token ids. ``on_token(index, token_id)`` fires on the engine
        loop the moment each token exists — the worker publishes chunks
        from it. Cancelling the await retires the sequence and frees its
        slot at the next sweep."""
        if self._stop:
            raise RuntimeError("decode engine stopped")
        if self._draining:
            raise DrainingError("decode engine draining; submit refused")
        if self.pending_count >= self.max_pending:
            raise DecodeSaturated(
                f"decode queue at {self.pending_count}/{self.max_pending} "
                f"pending")
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) >= self.backend.max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens leaves no room to "
                f"generate under the KV-cache length {self.backend.max_len}")
        fut = asyncio.get_running_loop().create_future()
        seq = _Sequence(prompt=prompt, future=fut,
                        max_new_tokens=max_new_tokens, on_token=on_token,
                        priority=priority, deadline_at=deadline_at,
                        ledger=ledger)
        seq.lacked = self._lacked_slot(seq.enqueued)
        self._queue.append(seq)
        if ledger is not None:
            ledger.stamp("queued", "decode")
        self._pending_gauge.set(self.pending_count, model=self._model)
        self._wakeup.set()
        return await fut

    def cancel(self, future: asyncio.Future) -> None:
        """Retire the sequence awaiting ``future`` (client gone). The
        sweep also catches cancelled futures; this frees the slot
        without waiting for the next tick."""
        for seq in list(self._active.values()) + list(self._queue):
            if seq.future is future:
                self._retire(seq, "cancelled")
                return

    # -- drain (rollout/drain.py drives these; docs/deployment.md) ---------

    def begin_drain(self) -> int:
        """Stop admitting prefills and retire every QUEUED sequence with
        ``DrainingError`` (each redelivers through the broker per task);
        active sequences keep decoding — ``drain_complete`` turns true
        when the last one finishes. Flip + retire are one synchronous
        step, so a concurrently scheduled ``_admit`` cannot prefill a
        sequence this sweep already failed."""
        self._draining = True
        retired = 0
        for seq in list(self._queue):
            if not seq.done:
                self._retire(seq, "cancelled",
                             error=DrainingError(
                                 "decode engine draining; redeliver"))
                retired += 1
        self._wakeup.set()
        return retired

    @property
    def drain_complete(self) -> bool:
        """Draining AND quiesced: no queued, no active sequences, no
        launched step unread."""
        return (self._draining and not self._active and not self._queue
                and not self._launched)

    def force_drain(self) -> int:
        """Retire the ACTIVE stragglers past the drain budget with
        ``DrainingError`` — each redelivers through the broker per task,
        the PR 17 poisoned-row path."""
        forced = 0
        for seq in list(self._active.values()):
            if not seq.done:
                self._retire(seq, "cancelled",
                             error=DrainingError(
                                 "decode drain budget exhausted; "
                                 "redeliver"))
                forced += 1
        # A step in flight holds their slots parked: the loop reads it,
        # discards what it computed for them and frees the slots.
        self._wakeup.set()
        return forced

    def resume_from_drain(self) -> None:
        """Re-arm after an aborted drain (rollback re-weights the worker
        back into service without a process restart)."""
        self._draining = False
        self._wakeup.set()

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        self._stop = False
        if self._books:
            self.metrics.scrape_hooks.append(self._book_idle_so_far)
        self._loop_task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        self._stop = True
        self._wakeup.set()
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None
        try:
            await self._settle()   # nothing stays unread on the executor
        except Exception:  # noqa: BLE001 — the stop goes on: every sequence is failed just below
            log.exception("decode step in flight failed at stop")
            self._void_launched()
        for seq in list(self._active.values()) + list(self._queue):
            self._retire(seq, "cancelled",
                         error=RuntimeError("decode engine stopped"))
        if self._executor is not None:
            self._executor.shutdown(wait=True)
        if self._books and (self._book_idle_so_far
                            in self.metrics.scrape_hooks):
            self.metrics.scrape_hooks.remove(self._book_idle_so_far)

    # -- engine loop -------------------------------------------------------

    async def _run(self) -> None:
        while not self._stop:
            if not self._active and not self._queue and not self._launched:
                self._last_submit = None
                self._idled = self._idling = True
                self._wakeup.clear()
                try:
                    await asyncio.wait_for(self._wakeup.wait(), timeout=0.5)
                except asyncio.TimeoutError:
                    continue
                finally:
                    self._idling = False
            if self._stop:
                return
            try:
                await self._tick()
            except Exception:  # noqa: BLE001 — a backend crash fails the affected sequences below, never the loop
                log.exception("decode tick failed; failing active sequences")
                self._last_submit = None
                # The step that failed and any launched after it are void.
                self._void_launched()
                for seq in list(self._active.values()):
                    self._retire(seq, "failed",
                                 error=RuntimeError("decode step failed"))

    async def _tick(self) -> None:
        """One scheduling iteration: reload check → expiry/cancel sweep →
        admission (prefill into free slots) → launch the next decode step
        and read the last one."""
        self._tick_no += 1
        await self._check_reload()
        self._sweep()
        t0 = time.perf_counter()
        await self._admit()
        self._phase["admit"] += time.perf_counter() - t0
        if self._joins:
            self._tick_joins.observe(self._joins, model=self._model)
            self._joins = 0
        await self._step()

    async def _check_reload(self) -> None:
        """Hot-reload invalidation: a ``params_version`` bump makes the
        pooled cache stale (it was computed under the old weights — the
        rescache contract). Re-prefill every active sequence from its
        token history under the new weights; slots are kept, never
        re-acquired, so conservation holds across the invalidation."""
        version = self.backend.params_version
        if version == self._cache_version:
            return
        # Never reset the cache under a launched step: read it first (its
        # ids were computed under the old weights, like every token before).
        await self._settle()
        first_attach = self._cache_version is None
        self._cache_version = version
        if first_attach and not self._active:
            return  # engine's first tick ever: nothing to invalidate
        reset = self.backend.reset_cache()
        if inspect.isawaitable(reset):
            await reset
        for seq in list(self._active.values()):
            if seq.done:
                continue
            history = seq.prompt + tuple(seq.tokens)
            if len(history) >= self.backend.max_len:
                # No room to re-derive the next token's KV: the sequence
                # was about to hit the context bound anyway.
                self._retire(seq, "completed")
                continue
            token = await self._prefill(seq, history)
            if token is None or seq.done:
                continue  # failed, or retired (cancel/expiry) meanwhile
            seq.position = len(history)
            self._reprefills_total.inc(model=self._model)
            self._note_token(seq, token)

    def _sweep(self) -> None:
        """Expiry + cancellation sweep, every iteration — single
        segment, no suspension points: the decision and the slot release
        cannot interleave with anything (docs/concurrency.md)."""
        now = time.time()
        for seq in list(self._active.values()) + list(self._queue):
            if seq.done:
                continue
            if seq.future.done():
                # Waiter cancelled (client disconnected): nothing to
                # deliver tokens to — free the slot now.
                self._retire(seq, "cancelled")
            elif seq.deadline_at and seq.deadline_at <= now:
                self._expired_total.inc(hop="decode",
                                        priority=priority_name(seq.priority))
                self._retire(seq, "expired",
                             error=DeadlineExceeded("decode",
                                                    seq.deadline_at))

    async def _admit(self) -> None:
        """Join queued requests into free KV-cache slots — BETWEEN decode
        steps, the continuous-batching join: each prompt's prefill is
        dispatched and the pass goes on to the next; the step launched
        after the pass carries every slot joined here, and its fetch brings
        their first ids."""
        if self._draining:
            # Anything that raced past the submit-side refusal is retired
            # here rather than prefilled onto a leaving worker.
            for seq in list(self._queue):
                if not seq.done:
                    self._retire(seq, "cancelled",
                                 error=DrainingError(
                                     "decode engine draining; redeliver"))
            return
        began = time.perf_counter()
        if self._no_slot_from is not None and self.pool.free_count:
            self._no_slot_s += began - self._no_slot_from
            self._no_slot_from = None
        while self._queue:
            slot = self.pool.acquire()
            if slot is None:
                self._no_slot_from = self._no_slot_from or time.perf_counter()
                return
            seq = self._queue.popleft()
            self._pending_gauge.set(self.pending_count, model=self._model)
            if seq.done or seq.future.done():
                # Swept/cancelled while queued: the slot was never its.
                self.pool.release(slot)
                if not seq.done:
                    self._retire(seq, "cancelled")
                continue
            seq.slot = slot
            seq.first_tick = self._tick_no
            self._active[slot] = seq
            self._occupancy.set(self.pool.busy_count / self.pool.slots,
                                model=self._model)
            now = time.perf_counter()
            wait = now - seq.enqueued
            self._queue_wait.observe(wait, model=self._model)
            if self._books:
                lacked = min(self._lacked_slot(now) - seq.lacked, wait)
                joins = min(now - max(seq.enqueued, began), wait - lacked)
                for part, value in zip(QUEUE_WAIT_PARTS, (
                        lacked, joins, wait - lacked - joins)):
                    self._queue_part.observe(value, part=part,
                                             model=self._model)
            if seq.ledger is not None:
                seq.ledger.stamp("slot", "decode", ms=wait * 1e3,
                                 reason=f"slot {slot} tick {self._tick_no}")
            self._joins += 1
            seq.position = len(seq.prompt)
            await self._prefill(seq, seq.prompt, ahead=True)

    def _count_join(self, slot: int) -> None:
        """Count what the prefill joined into ``slot`` reported from the
        device (``join_report``), now that the read of its first id has
        brought it to the host."""
        if self._expert_passes is not None:
            for kind, n in self.backend.join_report(slot).items():
                self._expert_passes.inc(n, model=self._model, kind=kind)

    async def _prefill(self, seq: _Sequence, tokens,
                       ahead: bool = False) -> int | None:
        """``tokens`` (the prompt or, after a reload, the history) through
        the backend's prefill into the sequence's slot. ``ahead``: a join —
        dispatched, its first id left with the backend for the next launch;
        else the call blocks and returns that id. A backend failure retires
        the sequence and returns None."""
        try:
            token, clock = await self._call(
                self._steps.join if ahead else self.backend.prefill_into,
                seq.slot, list(tokens), ledger=seq.ledger, join={})
        except Exception as exc:  # noqa: BLE001; ai4e: noqa[AIL005] — delivered to the sequence's waiter as its failure
            self._retire(seq, "failed", error=exc)
            return None
        seconds = clock.resumed - clock.submit
        self._step_hist.observe(seconds, phase="prefill", model=self._model)
        if ahead:
            self._joins_total.inc(model=self._model, kind="all")
        else:
            self._count_join(seq.slot)
        if self._prefill_work:
            for series, kinds in self.backend.prefill_report(
                    len(tokens)).items():
                for kind, n in kinds.items():
                    self._prefill_work[series].inc(n, model=self._model,
                                                   kind=kind)
        if self._books:
            if ahead and self._steps is self.backend:
                # It returned with its prefill queued: nothing drained.
                self._join_hist.observe(0.0, part="turnaround",
                                        model=self._model)
            else:
                self._drained = (clock.left, True)
            behind = clock.join.get("behind_step", 0.0)
            run = clock.join.get("run", 0.0)
            in_thread = clock.left - clock.entered
            for part, value in zip(JOIN_PARTS, (
                    seconds - in_thread, in_thread - behind - run, behind,
                    run)):
                self._join_hist.observe(value, part=part, model=self._model)
        if seq.ledger is not None:
            bucket_for = getattr(self.backend, "bucket_for", None)
            seq.ledger.stamp(
                "prefill", "decode", t=time.time() - seconds,
                ms=seconds * 1e3,
                reason=(f"bucket {bucket_for(len(tokens))}" if bucket_for
                        else f"{len(tokens)} tokens") + (
                    f" behind {behind * 1e3:.3f} run {run * 1e3:.3f}"
                    if self._books else ""))
        return None if ahead else int(token)

    async def _step(self, launch: bool = True) -> None:
        """Launch the next decode step over the whole slot pool (every
        active sequence advances one token; inactive slots ride along
        masked), then read the step launched before it — one call to the
        backend, in that order, so the device has the next step queued
        while the host fetches and notes the last one's ids."""
        entered = time.perf_counter()
        unread = self._launched[-1] if self._launched else None
        snapshot, alone = [], []
        if launch:
            for slot, seq in sorted(self._active.items()):
                if seq.done:
                    continue
                # A sequence the unread step carries has one token the host
                # has not seen, and one joined since its last read a first
                # id besides: count them. One that ends by count when the
                # unread step is read is never in this launch.
                ahead = unread is not None and unread.holds(slot)
                position = seq.position + ahead
                unseen = ahead + (not seq.tokens)
                if ahead and (len(seq.tokens) + unseen >= seq.max_new_tokens
                              or position >= self.backend.max_len):
                    continue
                # ``first``: this launch feeds the slot its prompt's first
                # id, which stayed with the backend.
                snapshot.append((slot, seq, position, ahead,
                                 not ahead and not seq.tokens))
            # A joined request for one token needs no step, only its first
            # id read: it rides a step others need, and where none does,
            # nothing is launched and the ids are read alone.
            if all(first and seq.max_new_tokens == 1
                   for _, seq, _, _, first in snapshot):
                snapshot, alone = [], snapshot
        if not snapshot and not alone and unread is None:
            self._last_submit = None
            return
        # One region a launched step, as numbered; a call that only reads
        # the last step of a burst opens none.
        region = contextlib.nullcontext()
        if snapshot:
            bound_for = getattr(self.backend, "bound_for", None)
            region = device_trace(
                "ai4e.decode.tick", tick=self._tick_no, active=len(snapshot),
                bound=(bound_for(max(entry[2] for entry in snapshot))
                       if bound_for else self.backend.max_len))
        with region:
            args = flight = None
            if snapshot:
                with device_trace("ai4e.decode.prepare"):
                    fresh = [None] * self.pool.slots
                    positions = [0] * self.pool.slots
                    active = [False] * self.pool.slots
                    for slot, seq, position, ahead, first in snapshot:
                        if not ahead and not first:   # the host has its last
                            fresh[slot] = seq.tokens[-1]
                        positions[slot] = position
                        active[slot] = True
                    args = (fresh, positions, active)
                flight = _Flight([(slot, seq, position, first) for
                                  slot, seq, position, _, first in snapshot],
                                 self._tick_no)
                self._launches.inc(model=self._model, kind="all")
                if unread is not None:
                    self._launches.inc(model=self._model, kind="ahead")
                # Registered before the call: a retire that runs while it
                # is awaited must see that this step holds the slot.
                self._launched.append(flight)
            (step, firsts), clock = await self._call(
                self._advance, args, unread and unread.step, bool(alone))
            phase = self._phase
            if flight is not None:
                flight.step = step
                if step.starved:
                    self._launches.inc(model=self._model, kind="starved")
                self._close_tick(clock.submit, entered)
            else:
                phase["prepare"] += clock.submit - entered
                if self._books:   # a fetch, and nothing launched after it
                    self._drained = (clock.left, False)
            # This call's own phases open (or, without a launch, extend)
            # the interval to the next launch.
            in_thread = clock.left - clock.entered
            wait = in_thread if clock.wait is None else clock.wait
            phase["handoff"] += clock.entered - clock.submit
            phase["dispatch"] += in_thread - wait
            phase["device_wait"] += wait
            phase["return"] += clock.resumed - clock.left
            if unread is not None:
                self._step_hist.observe(clock.resumed - clock.submit,
                                        phase="decode", model=self._model)
                self._launched.remove(unread)   # read: it holds nothing now
                with device_trace("ai4e.decode.bookkeeping"):
                    self._note_step(unread)
                self._release_parked()
            for slot, seq, *_ in alone:
                self._count_join(slot)
                if not seq.done and seq.slot == slot:
                    self._note_token(seq, int(firsts[slot]))
            phase["bookkeeping"] += time.perf_counter() - clock.resumed

    def _advance_in_thread(self, args, unread, alone):
        """On the device thread: launch, then fetch — in that order — or,
        with nothing to launch, the joined slots' first ids read alone."""
        step = self._steps.launch(*args) if args is not None else None
        if unread is not None:
            self._steps.fetch(unread)
        return step, self._steps.first_ids() if alone else None

    async def _advance_async(self, args, unread, alone):
        step = await self._steps.launch(*args) if args is not None else None
        if unread is not None:
            fetched = self._steps.fetch(unread)
            if inspect.isawaitable(fetched):
                await fetched
        return step, self._steps.first_ids() if alone else None

    async def _settle(self) -> None:
        """Read every launched step, launching nothing: what ``reset_cache``
        and ``stop`` need before they touch the cache or the executor."""
        while self._launched:
            await self._step(launch=False)

    def _void_launched(self) -> None:
        """Forget every launched step unread (a failure, a stop): nothing
        stays marked in flight, and the slots they held parked are freed."""
        self._launched.clear()
        self._drained = None
        self._release_parked()

    def _release_parked(self) -> None:
        for slot in [s for s in self._parked
                     if not any(f.holds(s) for f in self._launched)]:
            self._parked.discard(slot)
            self.pool.release(slot)
            self._occupancy.set(self.pool.busy_count / self.pool.slots,
                                model=self._model)

    def _note_step(self, flight: _Flight) -> None:
        """Account one step whose ids were just fetched — single segment:
        its counters, then each rider's token, and before it the first id of
        a rider joined under this step. A rider retired since the launch
        (or whose slot has a new tenant, or that its first id ended) is a
        discarded slot-step."""
        step, snapshot = flight.step, flight.snapshot
        self._step_active.observe(len(snapshot), model=self._model)
        self._step_bound.observe(step.bound, model=self._model)
        self._kv_positions.inc(
            sum(entry[2] + 1 for entry in snapshot),
            model=self._model, kind="live")
        self._kv_positions.inc(
            self.pool.slots * step.bound if step.attended is None
            else step.attended, model=self._model, kind="attended")
        if step.selected is not None:
            self._kv_positions.inc(step.selected, model=self._model,
                                   kind="selected")
        for kind, nbytes in step.cache_bytes.items():
            self._cache_bytes.inc(nbytes, model=self._model, kind=kind)
        for kind, nbytes in step.state_bytes.items():
            self._state_bytes.inc(nbytes, model=self._model, kind=kind)
        for name, value in step.report.items():
            self._step_report[name].observe(value, model=self._model)
        for slot, seq, position, first in snapshot:
            if first:
                self._count_join(slot)
            if first and not (seq.done or seq.slot != slot):
                # What the slot was fed is its prompt's first id, which the
                # host did not wait for: noted here, before the step's own.
                self._joins_total.inc(model=self._model, kind="ahead")
                self._note_token(seq, int(step.fed[slot]), flight.tick)
            if seq.done or seq.slot != slot:
                self._discarded.inc(model=self._model)
                continue
            seq.position = position + 1
            self._note_token(seq, int(step.ids[slot]), flight.tick)

    def _close_tick(self, submit: float, entered: float) -> None:
        """A step was submitted at ``submit``: observe the interval since
        the previous step's submit, one observation of each phase, and
        start the next. After an idle wait there is no interval to close."""
        phase = self._phase
        phase["prepare"] += submit - entered
        if self._last_submit is not None:
            rest = submit - self._last_submit - sum(phase.values())
            for name, seconds in phase.items():
                self._tick_hist.observe(seconds, phase=name,
                                        model=self._model)
            self._tick_hist.observe(max(rest, 0.0), phase="yield",
                                    model=self._model)
        self._last_submit = submit
        for name in phase:
            phase[name] = 0.0

    def _on_backend_phase(self, phase: str, seconds: float) -> None:
        """The backend's ``phase_hook``: runs on the device thread, inside
        the one backend call in flight."""
        clock = self._inflight
        if phase == "compile":
            # The existing family the batcher feeds: a warm worker counts 0,
            # and the series exists only once something compiled.
            self.metrics.histogram(
                "ai4e_device_phase_seconds",
                "Device-boundary phase durations (h2d/compile/execute/d2h)"
            ).observe(seconds, phase="compile", model=self._model)
            if clock is not None and clock.ledger is not None:
                clock.ledger.stamp("compile", "device",
                                   t=time.time() - seconds, ms=seconds * 1e3)
        elif clock is None:
            pass   # a call the engine did not make
        elif phase == "device_wait":
            clock.wait = (clock.wait or 0.0) + seconds
        elif phase == "enqueue":
            self._close_unqueued(clock.join is not None)
        elif phase == "readback":
            self._readback.observe(seconds, model=self._model)
        elif clock.join is not None:
            clock.join[phase] = seconds

    def _close_unqueued(self, at_prefill: bool) -> None:
        """The device thread is about to dispatch: book the seconds the
        device had nothing queued to their cause, decided here."""
        idled, self._idled = self._idled, False
        if self._drained is None:
            return
        (since, after_prefill), self._drained = self._drained, None
        seconds = time.perf_counter() - since
        cause = ("empty" if idled
                 else "join" if after_prefill or at_prefill else "loop")
        self._unqueued.inc(seconds, model=self._model, cause=cause)
        if cause == "join" and after_prefill:
            self._join_hist.observe(seconds, part="turnaround",
                                    model=self._model)

    def _book_idle_so_far(self) -> None:
        """The registry's scrape hook: an idle engine's open interval is
        booked up to now, not when the next request closes it, so two scrapes
        bound the idle seconds between them."""
        if self._idling and self._drained is not None:
            now = time.perf_counter()
            self._unqueued.inc(now - self._drained[0], model=self._model,
                               cause="empty")
            self._drained = (now, self._drained[1])

    def _lacked_slot(self, now: float) -> float:
        """Running total of the seconds, up to ``now``, during which the last
        ``_admit`` pass had returned for want of a free slot."""
        return self._no_slot_s + (
            0.0 if self._no_slot_from is None else now - self._no_slot_from)

    # -- bookkeeping (single-segment: no suspension points below) ---------

    def _note_token(self, seq: _Sequence, token: int,
                    tick: int | None = None) -> None:
        """Account one generated token: callback (chunk emission), TTFT /
        inter-token latency, and the finish decision (EOS, token budget,
        KV-cache slot full). ``tick``: the tick that launched the step it
        came from (a prefill's: this one)."""
        now = time.perf_counter()
        first = not seq.tokens
        seq.tokens.append(token)
        self._tokens_total.inc(model=self._model)
        if first:
            ttft = now - seq.enqueued
            self._ttft.observe(ttft, model=self._model)
            if seq.ledger is not None:
                # ONE chunk stamp per request (the ledger caps at 128
                # events — a 512-token stream must not eat the budget):
                # the first token, with TTFT as the duration.
                seq.ledger.stamp("chunk", "decode", ms=ttft * 1e3,
                                 reason="first token")
        else:
            self._intertoken.observe(now - seq.last_token_at,
                                     model=self._model)
        seq.last_token_at = now
        if seq.on_token is not None:
            try:
                seq.on_token(len(seq.tokens) - 1, token)
            except Exception:  # noqa: BLE001 — chunk fan-out is fail-open telemetry, never a decode error
                log.debug("on_token callback failed", exc_info=True)
        eos = getattr(self.backend, "eos_id", None)
        if (len(seq.tokens) >= seq.max_new_tokens
                or (eos is not None and token == eos)
                or seq.position >= self.backend.max_len):
            if seq.ledger is not None:
                seq.ledger.stamp(
                    "decoded", "decode",
                    reason=f"{len(seq.tokens)} tokens ticks {seq.first_tick}"
                           f"..{self._tick_no if tick is None else tick}")
            self._retire(seq, "completed")

    def _retire(self, seq: _Sequence, outcome: str, error=None) -> None:
        """THE slot-release funnel — single segment (no awaits), so the
        ``done`` guard and the release are atomic; idempotent, so every
        path (finish, expiry, cancel, failure, shutdown) may call it and
        the slot is still freed exactly once."""
        if seq.done:
            return
        seq.done = True
        if seq.slot is not None:
            self._active.pop(seq.slot, None)
            if any(flight.holds(seq.slot) for flight in self._launched):
                # A launched step has the slot live: it stays busy until
                # that step is read, so no join is written under it.
                self._parked.add(seq.slot)
            else:
                self.pool.release(seq.slot)
            seq.slot = None
            self._occupancy.set(self.pool.busy_count / self.pool.slots,
                                model=self._model)
        else:
            try:
                self._queue.remove(seq)
            except ValueError:
                pass  # already popped by admission
            self._pending_gauge.set(self.pending_count, model=self._model)
        self._sequences_total.inc(model=self._model, outcome=outcome)
        if not seq.future.done():
            if error is not None:
                seq.future.set_exception(error)
            else:
                seq.future.set_result(list(seq.tokens))

    async def _call(self, fn, /, *args, ledger=None, join=None):
        """Invoke a backend method: async backends (race-test fakes)
        await inline; sync backends (the JAX runtime) run on the single
        device executor thread — the device is the serial resource.
        Returns ``(result, clock)``: the one timer every caller reads."""
        clock = _CallClock(ledger, join)
        if inspect.iscoroutinefunction(fn):
            clock.submit = clock.entered = time.perf_counter()
            out = await fn(*args)
            clock.left = clock.resumed = time.perf_counter()
            return out, clock
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-decode")
        self._inflight = clock   # what the backend's phase_hook books to
        clock.submit = time.perf_counter()
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                self._executor, clock.run, fn, args)
        finally:
            self._inflight = None
        clock.resumed = time.perf_counter()
        return out, clock
