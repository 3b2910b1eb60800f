"""The device thread's ledger (runtime/decode.py, runtime/kvcache.py,
docs/observability.md "The device thread's ledger").

- a prefill's four parts add up to its ``ai4e_decode_step_seconds
  {phase="prefill"}``; ``turnaround`` is observed once a join and is the
  ``join`` seconds booked;
- an idle wait books ``empty`` and nothing else, a scrape in the middle of
  it books what has passed and nothing twice; a ``_settle`` books ``loop``;
- the three parts of a request's queue wait add up to it, whatever it waited
  behind;
- ``starved`` counts the launches that found a step unread and finished;
- a backend without the hook registers none of it;
- the real runtime on a tiny LM reports every part, and the same ids.
"""

import asyncio
import gc
import time

import pytest

from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.runtime.decode import (JOIN_PARTS, QUEUE_WAIT_PARTS,
                                     UNQUEUED_CAUSES, DecodeEngine,
                                     LaunchedStep)
from test_decode_tick import AsyncBackend, SleepBackend, series

NEW_SERIES = ("ai4e_decode_device_unqueued_seconds_total",
              "ai4e_decode_join_seconds",
              "ai4e_decode_fetch_readback_seconds",
              "ai4e_decode_queue_wait_part_seconds")


class LedgerBackend:
    """``launch``/``fetch`` with the hook, reporting as ``PagedDecodeRuntime``
    does, over a scripted device: it runs what it is given in order,
    ``step_s`` a step and ``prefill_s`` a prefill, and ``launch_s`` is the
    host's own work in a launch. Ids count up from the last prompt token."""

    max_len, eos_id, name, params_version = 64, None, "lm", 1

    def __init__(self, slots=2, step_s=0.004, prefill_s=0.006, launch_s=0.0):
        self.slots = slots
        self.step_s, self.prefill_s, self.launch_s = (step_s, prefill_s,
                                                      launch_s)
        self.phase_hook = None
        self._free_at = 0.0          # when the device ends what it has queued
        self._ids = [0] * slots
        self._newest = None
        self.starved = []            # of every launch, in order

    def reset_cache(self):
        pass

    def _enqueue(self, seconds):
        self.phase_hook("enqueue", 0.0)
        self._free_at = max(self._free_at, time.perf_counter()) + seconds
        return self._free_at

    @staticmethod
    def _wait(until):
        t0 = time.perf_counter()
        time.sleep(max(0.0, until - t0))
        return time.perf_counter() - t0

    def prefill_into(self, slot, tokens):
        queued = self._free_at
        done = self._enqueue(self.prefill_s)
        self.phase_hook("behind_step", self._wait(queued))
        self.phase_hook("run", self._wait(done))
        return int(tokens[-1]) + 1

    def launch(self, fresh, positions, active):
        time.sleep(self.launch_s)
        newest = self._newest
        starved = (newest is not None and newest.out is not None
                   and newest.out <= time.perf_counter())
        self.starved.append(starved)
        self._ids = [(self._ids[s] if t is None else t) + 1 if live else 0
                     for s, (t, live) in enumerate(zip(fresh, active))]
        self._newest = LaunchedStep(
            bound=self.max_len, active=active, starved=starved,
            out=self._enqueue(self.step_s), ids=list(self._ids))
        return self._newest

    def fetch(self, step):
        waited = self._wait(step.out)
        step.out = None
        self.phase_hook("device_wait", waited + 1e-5)
        self.phase_hook("readback", 1e-5)
        return step


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


class TestJoinParts:
    @pytest.mark.parametrize("requests", [
        [([1, 2], 6)], [([1, 2], 8), ([5], 5)],
        [([1], 4), ([2], 4), ([3], 4)]], ids=["one", "two", "three"])
    def test_four_parts_are_the_prefill_observation(self, requests):
        reg, out = run(LedgerBackend(slots=3), lambda e: asyncio.gather(*[
            e.submit(prompt, n) for prompt, n in requests]))
        assert [len(t) for t in out] == [n for _, n in requests]
        whole, joins = series(reg, "ai4e_decode_step_seconds",
                              phase="prefill", model="lm")
        assert joins == len(requests)
        parts = {}
        for part in JOIN_PARTS[:4]:
            parts[part], n = series(reg, "ai4e_decode_join_seconds",
                                    part=part, model="lm")
            assert n == joins and parts[part] >= 0.0, part
        assert sum(parts.values()) == pytest.approx(whole, abs=1e-9)
        # No call returns before the device has run its 6 ms prefill.
        assert whole - parts["hops"] >= 0.0059 * joins
        # A prefill after the first waits out the step launched before it
        # only if one was: here all join in the first pass, before any step.
        assert parts["behind_step"] < 0.004

    def test_a_join_among_running_steps_waits_behind_the_step(self):
        backend = LedgerBackend(step_s=0.02, prefill_s=0.005)

        async def script(engine):
            first = asyncio.ensure_future(engine.submit([1], 12))
            await asyncio.sleep(0.05)
            await engine.submit([2], 2)
            await first

        reg, _ = run(backend, script)
        behind, joins = series(reg, "ai4e_decode_join_seconds",
                               part="behind_step", model="lm")
        assert joins == 2
        assert 0.002 <= behind <= 0.035    # what was left of one 20 ms step
        run_s = total(reg, "ai4e_decode_join_seconds", part="run", model="lm")
        assert 0.0 < run_s < 0.05           # two prefills of 5 ms

    @pytest.mark.parametrize("clump", [1, 2, 3])
    def test_turnaround_once_a_join_and_is_the_join_seconds(self, clump):
        reg, _ = run(LedgerBackend(slots=3), lambda e: asyncio.gather(*[
            e.submit([i + 1], 4) for i in range(clump)]))
        around, n = series(reg, "ai4e_decode_join_seconds",
                           part="turnaround", model="lm")
        assert n == clump
        assert around == pytest.approx(total(
            reg, "ai4e_decode_device_unqueued_seconds_total", cause="join",
            model="lm"), abs=1e-9)
        assert around > 0.0
        for cause in ("empty", "loop"):
            assert total(reg, "ai4e_decode_device_unqueued_seconds_total",
                         cause=cause, model="lm") == 0.0

    def test_the_prefill_stamp_names_both_waits(self):
        from ai4e_tpu.observability.ledger import HopLedger
        ledger = HopLedger()
        run(LedgerBackend(), lambda e: e.submit([1, 2], 3, ledger=ledger))
        stamp = next(ev for ev in ledger.events() if ev["e"] == "prefill")
        assert " behind " in stamp["r"] and " run " in stamp["r"]


class TestUnqueuedCauses:
    def test_an_idle_wait_books_empty_and_nothing_else(self):
        """Both requests end in their prefill: the one interval that closes
        runs from the first's wait, through the idle wait, to the second's
        dispatch. A scrape inside it books what has passed, once."""
        reg = MetricsRegistry()
        seen = {}

        async def script(engine):
            await engine.submit([1], 1)
            t0 = time.perf_counter()
            await asyncio.sleep(0.15)
            reg.render_prometheus()
            seen["half"] = total(reg, NEW_SERIES[0], cause="empty",
                                 model="lm")
            await asyncio.sleep(0.15)
            await engine.submit([2], 1)
            seen["gap"] = time.perf_counter() - t0

        run(LedgerBackend(), script, reg)
        empty = total(reg, NEW_SERIES[0], cause="empty", model="lm")
        assert 0.14 <= seen["half"] <= 0.3
        assert 0.29 <= empty <= seen["gap"] + 0.05
        for cause in ("join", "loop"):
            assert total(reg, NEW_SERIES[0], cause=cause, model="lm") == 0.0
        assert count(reg, "ai4e_decode_join_seconds", part="turnaround",
                     model="lm") == 0
        assert reg.scrape_hooks == []       # the engine took its hook back

    def test_a_settle_books_loop(self):
        """Ticks driven by hand: a settle reads the launched step and
        launches nothing, so the device has nothing queued until the next
        tick's launch, with no prefill on either side and no idle wait."""
        reg = MetricsRegistry()
        backend = LedgerBackend()

        async def main():
            engine = DecodeEngine(backend, metrics=reg)
            answer = asyncio.ensure_future(engine.submit([1], 6))
            await asyncio.sleep(0)
            await engine._tick()        # the join, and step 1
            await engine._tick()        # step 2 launched, step 1 read
            join = total(reg, NEW_SERIES[0], cause="join", model="lm")
            await engine._settle()
            await asyncio.sleep(0.01)
            assert total(reg, NEW_SERIES[0], cause="loop", model="lm") == 0.0
            await engine._tick()        # step 3 closes the interval
            loop = total(reg, NEW_SERIES[0], cause="loop", model="lm")
            assert 0.01 <= loop < 0.5
            assert total(reg, NEW_SERIES[0], cause="join",
                         model="lm") == join
            while not answer.done():
                await engine._tick()
                await asyncio.sleep(0)
            await engine.stop()
            return await answer

        assert len(asyncio.run(main())) == 6
        assert total(reg, NEW_SERIES[0], cause="empty", model="lm") == 0.0

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
        await asyncio.gather(*[engine.submit([i + 1], 2) for i in range(3)])

    @staticmethod
    async def for_neither(engine):
        """It arrives while the loop is in a step, with slots free."""
        first = asyncio.ensure_future(engine.submit([1], 8))
        await asyncio.sleep(0.045)
        await engine.submit([2], 2)
        await first

    @pytest.mark.parametrize("script,slots,most", [
        ("for_a_slot", 1, "slot"), ("behind_two_prefills", 3, "joins"),
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
        elif most == "joins":   # one prefill, then two, ahead of them
            assert parts["joins"] >= 0.055 and parts["slot"] == 0.0
        else:
            assert parts["slot"] == 0.0 and parts["joins"] < 0.01
            assert 0.0 < parts["tick"] <= 0.06


class TestStarved:
    @pytest.mark.parametrize("step_s,launch_s,starved", [
        (0.05, 0.0, False), (0.0, 0.004, True)],
        ids=["device-sets-the-pace", "host-sets-the-pace"])
    def test_counts_launches_that_found_a_finished_step_unread(
            self, step_s, launch_s, starved):
        backend = LedgerBackend(step_s=step_s, launch_s=launch_s,
                                prefill_s=0.001)
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
