"""The device thread's ledger (runtime/decode.py, runtime/kvcache.py,
docs/observability.md "The device thread's ledger").

- a join's four parts add up to its ``ai4e_decode_step_seconds
  {phase="prefill"}`` — the join CALL: ``behind_step`` is 0 (it waits for no
  step), ``run`` is what the two-in-flight rule made it wait — and
  ``turnaround`` is observed once a join: 0 where the call returned with its
  prefill queued, the ``join`` seconds booked where it blocked;
- an idle wait books ``empty`` and nothing else, a scrape in the middle of
  it books what has passed and nothing twice; a ``_settle`` books ``loop``;
- the three parts of a request's queue wait add up to it, whatever it waited
  behind;
- ``starved`` counts the launches that found a step unread and finished;
- a backend without the hook registers none of it;
- the real runtime on a tiny LM reports every part, and the same ids.

The join's and the causes' cases run on a clock the test owns
(``ScriptedClock``, put in ``decode.time``'s place): only the scripted device
moves it, so every figure is a sum of the script's own numbers.
"""

import asyncio
import gc
import time
from collections import deque

import pytest

from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.runtime import decode
from ai4e_tpu.runtime.decode import (JOIN_PARTS, QUEUE_WAIT_PARTS,
                                     UNQUEUED_CAUSES, DecodeEngine,
                                     LaunchedStep)
from test_decode import wait_until
from test_decode_tick import AsyncBackend, SleepBackend, series

NEW_SERIES = ("ai4e_decode_device_unqueued_seconds_total",
              "ai4e_decode_join_seconds",
              "ai4e_decode_fetch_readback_seconds",
              "ai4e_decode_queue_wait_part_seconds")
MS = 1e-3


class RealClock:
    perf_counter = staticmethod(time.perf_counter)

    def advance(self, seconds):
        time.sleep(seconds)

    def wait(self, until):
        """Sleep until ``until``; the seconds waited."""
        t0 = time.perf_counter()
        time.sleep(max(0.0, until - t0))
        return time.perf_counter() - t0


class ScriptedClock:
    """``decode.time``'s stand-in: ``perf_counter`` moves only when the
    scripted device (or the test) moves it; the wall clock is the real
    one."""

    time = staticmethod(time.time)

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds

    def wait(self, until):
        waited = max(0.0, until - self.now)
        self.now += waited
        return waited


@pytest.fixture
def clock(monkeypatch):
    scripted = ScriptedClock()
    monkeypatch.setattr(decode, "time", scripted)
    return scripted


class BlockingLedger:
    """``prefill_into`` / ``launch`` / ``fetch`` with the hook, reporting as
    ``PagedDecodeRuntime`` does, over a scripted device: it runs what it is
    given in order, ``step_s`` a step and ``prefill_s`` a prefill;
    ``launch_s`` is the host's own work in a launch and ``dispatch_s`` the
    host's before anything it dispatches lands. Ids count up from the last
    prompt token. No ``join``: the engine joins through the blocking
    prefill."""

    max_len, eos_id, name, params_version = 64, None, "lm", 1

    def __init__(self, slots=2, step_s=0.004, prefill_s=0.006, launch_s=0.0,
                 dispatch_s=0.0, clock=None):
        self.slots = slots
        self.step_s, self.prefill_s, self.launch_s, self.dispatch_s = (
            step_s, prefill_s, launch_s, dispatch_s)
        self.clock = clock or RealClock()
        self.phase_hook = None
        self._free_at = 0.0          # when the device ends what it has queued
        self._ids = [0] * slots
        self._newest = None
        self.starved = []            # of every launch, in order
        self.calls = []              # every backend call, in order

    def reset_cache(self):
        pass

    def _enqueue(self, seconds):
        if self.dispatch_s:
            self.clock.advance(self.dispatch_s)
        self.phase_hook("enqueue", 0.0)
        self._free_at = max(self._free_at,
                            self.clock.perf_counter()) + seconds
        return self._free_at

    def prefill_into(self, slot, tokens):
        self.calls.append("prefill_into")
        done = self._enqueue(self.prefill_s)
        self.phase_hook("behind_step", 0.0)
        self.phase_hook("run", self.clock.wait(done))
        self._ids[slot] = int(tokens[-1]) + 1
        return self._ids[slot]

    def launch(self, fresh, positions, active):
        self.calls.append("launch")
        if self.launch_s:
            self.clock.advance(self.launch_s)
        newest = self._newest
        starved = (newest is not None and newest.out is not None
                   and self._free_at <= self.clock.perf_counter())
        self.starved.append(starved)
        fed = [self._ids[s] if t is None else t for s, t in enumerate(fresh)]
        self._ids = [t + 1 if live else 0 for t, live in zip(fed, active)]
        self._newest = LaunchedStep(
            bound=self.max_len, active=active, starved=starved,
            out=self._enqueue(self.step_s), ids=list(self._ids), fed=fed)
        return self._newest

    def fetch(self, step):
        self.calls.append("fetch")
        waited = self.clock.wait(step.out)
        step.out = None
        self.phase_hook("device_wait", waited + 1e-5)
        self.phase_hook("readback", 1e-5)
        return step


class LedgerBackend(BlockingLedger):
    """The same device with the join that does not block: dispatched, its
    first id left in ``_ids``, at most two in flight."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._joined = deque()       # when each join in flight ends
        self.in_flight = []          # joins not known finished, a dispatch

    def join(self, slot, tokens):
        self.calls.append("join")
        waited = (self.clock.wait(self._joined.popleft())
                  if len(self._joined) == 2 else 0.0)
        self._joined.append(self._enqueue(self.prefill_s))
        self.in_flight.append(len(self._joined))
        self._ids[slot] = int(tokens[-1]) + 1
        self.phase_hook("behind_step", 0.0)
        self.phase_hook("run", waited)

    def first_ids(self):
        self.calls.append("first_ids")
        self.phase_hook("device_wait", self.clock.wait(self._free_at))
        return list(self._ids)


def total(reg, name, **labels):
    """Sum of a histogram series, value of a counter; 0.0 where absent."""
    if name not in reg._metrics:
        return 0.0
    got = series(reg, name, **labels)
    return got[0] if isinstance(got, tuple) else got


def count(reg, name, **labels):
    return series(reg, name, **labels)[1] if name in reg._metrics else 0


def run(backend, script, reg=None):
    """``script(engine)`` on a started engine; stops it after."""
    reg = reg or MetricsRegistry()

    async def main():
        engine = DecodeEngine(backend, metrics=reg)
        await engine.start()
        out = await script(engine)
        await engine.stop()
        engine.pool.check_conservation()
        return out

    return reg, asyncio.run(main())


def join_parts(reg):
    """``({part: sum}, joins, the prefill observations' sum)``; every part
    observed once a join."""
    whole, joins = series(reg, "ai4e_decode_step_seconds", phase="prefill",
                          model="lm")
    parts = {}
    for part in JOIN_PARTS:
        parts[part], n = series(reg, "ai4e_decode_join_seconds", part=part,
                                model="lm")
        assert n == joins and parts[part] >= 0.0, part
    return parts, joins, whole


def booked(reg, cause):
    return total(reg, NEW_SERIES[0], cause=cause, model="lm")


class TestJoinParts:
    """A clump of joins on the scripted clock: 2 ms of the host before a
    dispatch lands, 6 ms a prefill on the device. Join ``i`` waits for join
    ``i - 2``: the third for 4 ms (the first ends at 8, it asks at 4), and
    so each one after it (a run less a dispatch)."""

    @pytest.mark.parametrize("requests,waited", [
        ([([1, 2], 6)], 0), ([([1, 2], 8), ([5], 5)], 0),
        ([([1], 4), ([2], 4), ([3], 4)], 4),
        ([([i + 1], 3) for i in range(5)], 4 + 4 + 4)],
        ids=["one", "two", "three", "five"])
    def test_four_parts_are_the_prefill_observation(self, clock, requests,
                                                    waited):
        backend = LedgerBackend(slots=5, dispatch_s=2 * MS, clock=clock)
        reg, out = run(backend, lambda e: asyncio.gather(*[
            e.submit(prompt, n) for prompt, n in requests]))
        assert [len(t) for t in out] == [n for _, n in requests]
        parts, joins, whole = join_parts(reg)
        assert joins == len(requests)
        assert sum(parts[p] for p in JOIN_PARTS[:4]) == pytest.approx(
            whole, abs=1e-9)
        # The call, not the prefill's run: no join waits for its own 6 ms.
        assert parts["dispatch"] == pytest.approx(2 * MS * joins, abs=1e-9)
        assert parts["run"] == pytest.approx(waited * MS, abs=1e-9)
        assert parts["behind_step"] == parts["hops"] == 0.0
        assert whole == pytest.approx((2 * joins + waited) * MS, abs=1e-9)

    @pytest.mark.parametrize("clump", [1, 2, 3, 7])
    def test_never_more_than_two_joins_in_flight(self, clock, clump):
        backend = LedgerBackend(slots=8, dispatch_s=1 * MS, clock=clock)
        run(backend, lambda e: asyncio.gather(*[
            e.submit([i + 1], 3) for i in range(clump)]))
        assert backend.calls[:clump + 1] == ["join"] * clump + ["launch"]
        assert backend.in_flight == [1, 2, 2, 2, 2, 2, 2][:clump]

    def test_a_join_among_running_steps_waits_for_no_step(self, clock):
        """The second request joins while the first's steps run, 20 ms each:
        its call costs its 2 ms of dispatch and no more, and the step that
        was launched before it is read only after the one that carries it
        has been launched."""
        backend = LedgerBackend(step_s=20 * MS, prefill_s=5 * MS,
                                dispatch_s=2 * MS, clock=clock)

        async def script(engine):
            first = asyncio.ensure_future(engine.submit([1], 12))
            await wait_until(lambda: backend.calls.count("fetch") >= 2)
            before = len(backend.calls)
            await engine.submit([20], 2)
            await first
            return before

        reg, before = run(backend, script)
        parts, joins, whole = join_parts(reg)
        assert joins == 2
        assert parts["behind_step"] == parts["run"] == 0.0
        assert whole == pytest.approx(2 * 2 * MS, abs=1e-9)
        at = backend.calls.index("join", before)
        assert backend.calls[at:at + 3] == ["join", "launch", "fetch"]

    @pytest.mark.parametrize("clump", [1, 2, 3])
    def test_turnaround_is_zero_where_the_call_left_its_prefill_queued(
            self, clock, clump):
        backend = LedgerBackend(slots=3, dispatch_s=2 * MS, clock=clock)
        reg, _ = run(backend, lambda e: asyncio.gather(*[
            e.submit([i + 1], 4) for i in range(clump)]))
        parts, joins, _ = join_parts(reg)
        assert joins == clump and parts["turnaround"] == 0.0
        assert "prefill_into" not in backend.calls
        for cause in UNQUEUED_CAUSES:
            assert booked(reg, cause) == 0.0, cause

    @pytest.mark.parametrize("clump", [1, 2, 3])
    def test_turnaround_of_a_join_that_blocked_is_the_join_seconds(
            self, clock, clump):
        """A backend with only the blocking prefill: after each the device
        has nothing queued for the 2 ms the host takes to dispatch what
        comes next — the next prefill, or the pass's step."""
        backend = BlockingLedger(slots=3, dispatch_s=2 * MS, clock=clock)
        reg, _ = run(backend, lambda e: asyncio.gather(*[
            e.submit([i + 1], 4) for i in range(clump)]))
        parts, joins, whole = join_parts(reg)
        assert joins == clump
        assert parts["turnaround"] == pytest.approx(2 * MS * clump, abs=1e-9)
        assert parts["turnaround"] == pytest.approx(booked(reg, "join"),
                                                    abs=1e-9)
        assert parts["run"] == pytest.approx(6 * MS * clump, abs=1e-9)
        assert whole == pytest.approx(8 * MS * clump, abs=1e-9)
        assert booked(reg, "empty") == booked(reg, "loop") == 0.0

    def test_the_prefill_stamp_names_both_waits(self):
        from ai4e_tpu.observability.ledger import HopLedger
        ledger = HopLedger()
        run(LedgerBackend(), lambda e: e.submit([1, 2], 3, ledger=ledger))
        stamp = next(ev for ev in ledger.events() if ev["e"] == "prefill")
        assert " behind " in stamp["r"] and " run " in stamp["r"]


class TestUnqueuedCauses:
    @pytest.mark.parametrize("backend_cls", [LedgerBackend, BlockingLedger],
                             ids=["ahead", "blocking"])
    def test_an_idle_wait_books_empty_and_nothing_else(self, clock,
                                                       backend_cls):
        """Both requests want one token and ride no step: the one interval
        that closes runs from the first id's read, through the idle wait, to
        the second's dispatch. A scrape inside it books what has passed,
        once."""
        reg = MetricsRegistry()
        seen = {}

        async def script(engine):
            assert await engine.submit([1], 1) == [2]
            await wait_until(lambda: engine._idling)
            clock.advance(0.15)
            reg.render_prometheus()
            seen["half"] = booked(reg, "empty")
            reg.render_prometheus()
            seen["again"] = booked(reg, "empty")
            clock.advance(0.15)
            assert await engine.submit([2], 1) == [3]

        backend = backend_cls(dispatch_s=2 * MS, clock=clock)
        run(backend, script, reg)
        assert seen["half"] == seen["again"] == pytest.approx(0.15, abs=1e-9)
        assert booked(reg, "empty") == pytest.approx(0.302, abs=1e-9)
        assert booked(reg, "join") == booked(reg, "loop") == 0.0
        # Once a join where the call left its prefill queued (0.0); never
        # closed, and so never observed, where it blocked and then idled.
        ahead = backend_cls is LedgerBackend
        assert series(reg, "ai4e_decode_join_seconds", part="turnaround",
                      model="lm") == ((0.0, 2) if ahead else (0.0, 0))
        assert backend.calls.count("first_ids") == (2 if ahead else 0)
        assert reg.scrape_hooks == []       # the engine took its hook back

    def test_a_settle_books_loop(self, clock):
        """Ticks driven by hand: a settle reads the launched step and
        launches nothing, so the device has nothing queued until the next
        tick's launch, with no prefill on either side and no idle wait."""
        reg = MetricsRegistry()
        backend = LedgerBackend(dispatch_s=2 * MS, clock=clock)

        async def main():
            engine = DecodeEngine(backend, metrics=reg)
            answer = asyncio.ensure_future(engine.submit([1], 6))
            await asyncio.sleep(0)
            await engine._tick()        # the join, and step 1
            await engine._tick()        # step 2 launched, step 1 read
            await engine._settle()
            clock.advance(0.01)
            assert booked(reg, "loop") == 0.0
            await engine._tick()        # step 3 closes the interval
            assert booked(reg, "loop") == pytest.approx(0.012, abs=1e-9)
            while not answer.done():
                await engine._tick()
                await asyncio.sleep(0)
            await engine.stop()
            return await answer

        assert asyncio.run(main()) == [2, 3, 4, 5, 6, 7]
        assert booked(reg, "empty") == booked(reg, "join") == 0.0

    def test_a_join_after_a_settle_books_join(self, clock):
        """The interval a settle opens ends at a prefill's dispatch: the
        join's, by the cause's second clause — and no turnaround, which is
        of an interval a prefill's wait opened."""
        reg = MetricsRegistry()
        backend = LedgerBackend(dispatch_s=2 * MS, clock=clock)

        async def main():
            engine = DecodeEngine(backend, metrics=reg)
            first = asyncio.ensure_future(engine.submit([1], 8))
            await asyncio.sleep(0)
            await engine._tick()
            await engine._tick()
            await engine._settle()
            clock.advance(0.01)
            second = asyncio.ensure_future(engine.submit([20], 2))
            await asyncio.sleep(0)
            await engine._tick()        # its join closes the interval
            assert booked(reg, "join") == pytest.approx(0.012, abs=1e-9)
            while not (first.done() and second.done()):
                await engine._tick()
                await asyncio.sleep(0)
            await engine.stop()
            return await first, await second

        assert asyncio.run(main()) == (list(range(2, 10)), [21, 22])
        assert booked(reg, "loop") == booked(reg, "empty") == 0.0
        assert series(reg, "ai4e_decode_join_seconds", part="turnaround",
                      model="lm") == (0.0, 2)

    def test_a_reloads_blocking_prefill_books_its_turnaround(self, clock):
        """Ticks driven by hand. The re-prefill after a reload reads its id:
        the settle before it leaves the device drained for the 2 ms its
        dispatch takes (``join``: the interval ends at a prefill), and after
        it the device has nothing queued for the 2 ms until the next step
        lands — the join's turnaround."""
        reg = MetricsRegistry()
        backend = LedgerBackend(dispatch_s=2 * MS, clock=clock)

        async def main():
            engine = DecodeEngine(backend, metrics=reg)
            answer = asyncio.ensure_future(engine.submit([1], 9))
            await asyncio.sleep(0)
            await engine._tick()
            await engine._tick()
            backend.params_version += 1
            while not answer.done():
                await engine._tick()
                await asyncio.sleep(0)
            await engine.stop()
            return await answer

        assert asyncio.run(main()) == list(range(2, 11))
        assert backend.calls.count("prefill_into") == 1
        assert booked(reg, "join") == pytest.approx(4 * MS, abs=1e-9)
        assert series(reg, "ai4e_decode_join_seconds", part="turnaround",
                      model="lm") == (pytest.approx(2 * MS, abs=1e-9), 2)
        assert booked(reg, "loop") == booked(reg, "empty") == 0.0

    def test_every_cause_reads_zero_from_the_start(self):
        """A worker that is never idle and whose joins leave their prefill
        queued books nothing: its series still read a number."""
        reg = MetricsRegistry()
        DecodeEngine(LedgerBackend(), metrics=reg)
        assert {labels["cause"]: value for _, _, labels, value in
                reg._metrics[NEW_SERIES[0]].collect()} == dict.fromkeys(
                    UNQUEUED_CAUSES, 0.0)

    def test_a_failed_step_leaves_no_interval_open(self):
        backend = LedgerBackend()
        fetch = backend.fetch

        def fail_once(step):
            backend.fetch = fetch
            step.out = None
            raise RuntimeError("the device fell over")

        async def script(engine):
            backend.fetch = fail_once
            with pytest.raises(RuntimeError):
                await engine.submit([1], 5)
            assert engine._drained is None
            await engine.submit([2], 3)

        reg, _ = run(backend, script)
        assert set(UNQUEUED_CAUSES) >= {
            labels["cause"] for _, _, labels, _ in
            reg._metrics[NEW_SERIES[0]].collect()}


class TestQueueWaitParts:
    @staticmethod
    async def for_a_slot(engine):
        """One slot: the second request waits for the first to end."""
        await asyncio.gather(engine.submit([1], 6), engine.submit([2], 2))

    @staticmethod
    async def behind_two_prefills(engine):
        await asyncio.gather(*[engine.submit([i + 1], 2) for i in range(4)])

    @staticmethod
    async def for_neither(engine):
        """It arrives while the loop is in a step, with slots free."""
        first = asyncio.ensure_future(engine.submit([1], 8))
        await asyncio.sleep(0.045)
        await engine.submit([2], 2)
        await first

    @pytest.mark.parametrize("script,slots,most", [
        ("for_a_slot", 1, "slot"), ("behind_two_prefills", 4, "joins"),
        ("for_neither", 2, "tick")])
    def test_three_parts_add_up_to_the_queue_wait(self, script, slots, most):
        backend = LedgerBackend(slots=slots, step_s=0.02, prefill_s=0.02)
        reg, _ = run(backend, getattr(self, script))
        whole, n = series(reg, "ai4e_decode_queue_wait_seconds", model="lm")
        parts = {}
        for part in QUEUE_WAIT_PARTS:
            parts[part], m = series(
                reg, "ai4e_decode_queue_wait_part_seconds", part=part,
                model="lm")
            assert m == n and parts[part] >= 0.0, part
        assert sum(parts.values()) == pytest.approx(whole, abs=1e-9)
        assert parts[most] == max(parts.values())
        if most == "slot":      # five 20 ms steps of the first request
            assert parts["slot"] >= 0.08
        elif most == "joins":   # the fourth's slot comes after the third's
            # call, which waited out the first's 20 ms run (two in flight)
            assert parts["joins"] >= 0.02 and parts["slot"] == 0.0
        else:
            assert parts["slot"] == 0.0 and parts["joins"] < parts["tick"]


class TestStarved:
    @pytest.mark.parametrize("step_s,launch_s,starved", [
        (0.05, 0.0, False), (0.0, 0.004, True)],
        ids=["device-sets-the-pace", "host-sets-the-pace"])
    def test_counts_launches_that_found_a_finished_step_unread(
            self, clock, step_s, launch_s, starved):
        backend = LedgerBackend(step_s=step_s, launch_s=launch_s,
                                prefill_s=0.001, clock=clock)
        reg, out = run(backend, lambda e: e.submit([1], 9))
        assert out == list(range(2, 11))
        launches = {kind: series(reg, "ai4e_decode_step_launches_total",
                                 kind=kind, model="lm")
                    for kind in ("all", "ahead", "starved")}
        assert (launches["all"], launches["ahead"]) == (8, 7)
        # A burst's first launch has no step unread, whatever the device did.
        assert backend.starved[0] is False
        assert launches["starved"] == (7 if starved else (0.0, 0))
        assert sum(backend.starved) == (7 if starved else 0)


class TestWithoutTheHook:
    @pytest.mark.parametrize("backend_cls", [SleepBackend, AsyncBackend])
    def test_registers_none_of_the_ledgers_series(self, backend_cls):
        reg, out = run(backend_cls(step_s=0.001), lambda e: asyncio.gather(
            e.submit([1, 2], 4), e.submit([5], 3)))
        assert [len(t) for t in out] == [4, 3]
        for name in NEW_SERIES:
            assert name not in reg._metrics, name
        assert series(reg, "ai4e_decode_step_launches_total", kind="starved",
                      model="lm") == (0.0, 0)
        assert series(reg, "ai4e_decode_queue_wait_seconds",
                      model="lm")[1] == 2
        assert reg.scrape_hooks == []


# -- the real runtime on a tiny LM --------------------------------------------

@pytest.fixture(scope="module")
def runtimes():
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    servable = build_lm_servable(family="seqformer-lm", name="lm",
                                 vocab_size=64, max_len=48, dim=32, depth=2,
                                 heads=4)
    told, plain = (PagedDecodeRuntime(servable, slots=2, prompt_buckets=(8,))
                   for _ in range(2))
    told.warm()
    plain.warm()
    yield told, plain
    # Two warmed runtimes are a great deal of garbage: collect it here, not in
    # the middle of whichever test this process times next.
    del told, plain, servable
    gc.collect()


class TestPagedRuntimeReports:
    def test_every_part_and_the_same_greedy_ids_as_step(self, runtimes):
        told, plain = runtimes
        phases = []
        told.phase_hook = lambda phase, seconds: phases.append(
            (phase, seconds))
        prompt = [5, 9, 12]
        first = told.prefill_into(0, prompt)
        assert first == plain.prefill_into(0, prompt)
        assert [p for p, _ in phases] == ["enqueue", "behind_step", "run"]
        assert all(seconds >= 0.0 for _, seconds in phases)
        ids, token, position = [], first, len(prompt)
        steps = []
        for _ in range(6):      # one step behind, as the engine runs it
            fresh = [token, None] if not steps else [None, None]
            del phases[:]
            steps.append(told.launch(fresh, [position + len(steps), 0],
                                     [True, False]))
            assert [p for p, _ in phases] == ["enqueue"]
            if len(steps) > 1:
                del phases[:]
                ids.append(told.fetch(steps[-2]).ids[0])
                assert [p for p, _ in phases] == ["device_wait", "readback"]
                (_, wait), (_, readback) = phases
                assert 0.0 <= readback <= wait
        ids.append(told.fetch(steps[-1]).ids[0])
        assert steps[0].starved is False        # no step was unread
        expect = []
        for i in range(6):
            token = plain.step([token, 0], [position + i, 0],
                               [True, False])[0]
            expect.append(token)
        assert ids == expect

    def test_starved_only_with_a_step_unread_and_finished(self, runtimes):
        told, _ = runtimes
        told.phase_hook = None
        told.reset_cache()
        first = told.prefill_into(0, [3, 4])
        one = told.launch([first, None], [2, 0], [True, False])
        assert one.starved is False             # nothing launched before it
        one.out.block_until_ready()
        two = told.launch([None, None], [3, 0], [True, False])
        assert two.starved is True              # unread, and finished
        told.fetch(one)
        told.fetch(two)
        three = told.launch([None, None], [4, 0], [True, False])
        assert three.starved is False           # finished, but read
        told.fetch(three)

    def test_behind_the_engine_the_ledger_fills(self, runtimes):
        told, _ = runtimes
        told.reset_cache()
        reg, out = run(told, lambda e: asyncio.gather(
            e.submit([5, 9, 12], 6), e.submit([7], 4)))
        told.phase_hook = None
        assert [len(t) for t in out] == [6, 4]
        whole, joins = series(reg, "ai4e_decode_step_seconds",
                              phase="prefill", model="lm")
        parts = [series(reg, "ai4e_decode_join_seconds", part=part,
                        model="lm") for part in JOIN_PARTS[:4]]
        assert [n for _, n in parts] == [joins] * 4 == [2] * 4
        assert all(seconds >= 0.0 for seconds, _ in parts)
        assert sum(s for s, _ in parts) == pytest.approx(whole, abs=1e-9)
        assert count(reg, "ai4e_decode_join_seconds", part="turnaround",
                     model="lm") == 2
        steps = series(reg, "ai4e_decode_step_seconds", phase="decode",
                       model="lm")[1]
        readback, n = series(reg, "ai4e_decode_fetch_readback_seconds",
                             model="lm")
        assert n == steps and readback >= 0.0
        wait = total(reg, "ai4e_decode_tick_seconds", phase="device_wait",
                     model="lm")
        assert wait >= 0.0
