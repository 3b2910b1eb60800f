"""The decode engine launches step N+1 before it reads step N
(``runtime/decode.py``; ``runtime/kvcache.py`` ``launch`` / ``fetch``).

- on tiny runtimes of the three LM families the engine's ids are those of
  a plain loop — prefill, then blocking steps one at a time, every token
  through the host — for a scripted mix: joins mid-stream, ends by budget,
  by ``eos_id`` and by a full cache, a cancel, an expiry, a reload, a
  failure at fetch, a drain and a stop, each with a step in flight, and
  the slot pool conserved after each;
- a scripted backend that records, at each launch, whether the step before
  it had been fetched: a steady run of n steps launches n - 1 ahead, a
  sequence that ends by count is absent from the launch after its last
  step, one that ends by ``eos_id`` costs exactly one discarded slot-step,
  tokens reach ``on_token`` in order and as soon as their step is fetched;
- a slot is not handed to a join while a launched step still has its
  previous tenant live;
- a join does not stop the loop either (``join`` / ``first_ids``): a clump's
  prefills are dispatched back to back, the first id of each stays with the
  backend, rides the next step and is read with its fetch, before the
  second — the plain loop's ids for clumps of 1, 2, 4 and 7 on three slots;
  a request for one token, a first id that is ``eos_id``, a cancel, an
  expiry, a failure, a reload and a drain between a join and its read; and
  the runtime never has more than two joins in flight.
"""

import asyncio

import numpy as np
import pytest

from ai4e_tpu.admission.deadline import DeadlineExceeded
from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.rollout.drain import DrainingError
from ai4e_tpu.runtime.decode import DecodeEngine, LaunchedStep
from test_decode import wait_until
from test_decode_tick import series

# -- the four families against the plain loop --------------------------------

MAX_LEN, SLOTS = 48, 3
FAMILIES = {
    "seqformer-lm": dict(vocab_size=64, dim=32, depth=2, heads=4),
    "olmoe": dict(vocab_size=64, dim=32, depth=2, heads=4, experts=8,
                  experts_per_token=2, expert_dim=32),
    "qwen3-next": dict(vocab_size=97, dim=64, depth=4, heads=4, kv_heads=2,
                       head_dim=32, rotary_dim=8, lin_k_heads=2,
                       lin_v_heads=4, lin_dim=16, experts=16, experts_held=8,
                       experts_per_token=3, expert_dim=32, shared_dim=32),
    "granite-hybrid": dict(vocab_size=97, dim=64, depth=4,
                           attention_layers=(2,), heads=8, kv_heads=2,
                           head_dim=16, mlp_dim=96, ssm_heads=4,
                           ssm_head_dim=16, ssm_state=16, chunk=8),
}
LONG = tuple(range(3, 3 + MAX_LEN - 5))      # leaves room for six tokens
PROMPTS = {"a": (5, 9, 12), "b": (7,), "c": LONG, "d": (11, 2, 30, 4),
           "e": (1, 2)}


def plain_loop(runtime, prompt, budget):
    """Prefill, then one blocking step a token, every id through the host:
    the order the engine kept before it ran a step behind."""
    tokens = [runtime.prefill_into(0, list(prompt))]
    position = len(prompt)
    rest = [0] * (runtime.slots - 1)
    while not (len(tokens) >= budget or tokens[-1] == runtime.eos_id
               or position >= runtime.max_len):
        tokens.append(runtime.step([tokens[-1], *rest], [position, *rest],
                                   [True] + [False] * len(rest))[0])
        position += 1
    return tokens


class Family:
    """One family's servable under two runtimes: ``served`` behind engines,
    ``plain`` for the loop the engine is held to."""

    def __init__(self, family):
        from ai4e_tpu.runtime.kvcache import (PagedDecodeRuntime,
                                              build_lm_servable)

        class Served(PagedDecodeRuntime):
            fail_fetch = False

            def fetch(self, step):
                if self.fail_fetch:
                    self.fail_fetch = False
                    self._ids = None      # as a device failure leaves it
                    raise RuntimeError("the device fell over")
                return super().fetch(step)

        servable = build_lm_servable(family=family, name="lm",
                                     max_len=MAX_LEN, **FAMILIES[family])
        self.served = Served(servable, slots=SLOTS, prompt_buckets=(8,))
        self.plain = PagedDecodeRuntime(servable, slots=SLOTS,
                                        prompt_buckets=(8,))
        self.served.warm()
        # An ``eos_id`` the long answer to "d" reaches a few tokens in, and
        # (where the tiny model allows) no other scripted answer does.
        free = {name: plain_loop(self.plain, prompt, 24)
                for name, prompt in PROMPTS.items()}
        others = {t for name in "abce" for t in free[name]}
        late = free["d"][2:8]
        self.eos_id = next((t for t in late if t not in others), late[0])
        self._want = {}

    def want(self, name, budget, eos=False):
        key = (name, budget, eos)
        if key not in self._want:
            self.plain.eos_id = self.eos_id if eos else None
            self._want[key] = plain_loop(self.plain, PROMPTS[name], budget)
        return self._want[key]

    def engine(self, eos=False):
        self.served.eos_id = self.eos_id if eos else None
        self.served.reset_cache()
        reg = MetricsRegistry()
        return DecodeEngine(self.served, metrics=reg), reg


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return Family(request.param)


def counted(reg, name, **labels):
    """A counter's value; 0 for one never incremented."""
    value = series(reg, name, model="lm", **labels)
    return 0 if value == (0.0, 0) else value


def settled(engine):
    """Nothing in flight, nothing parked, every slot free and accounted."""
    engine.pool.check_conservation()
    return (not engine._launched and not engine._parked
            and engine.pool.free_count == engine.pool.slots)


async def in_flight(engine, future_tokens=2):
    """Wait until a launched step is unread and every active sequence has
    a few tokens: the window each scenario acts in."""
    await wait_until(lambda: engine._launched and engine._active and all(
        len(seq.tokens) >= future_tokens for seq in engine._active.values()))


def sequence_of(engine, prompt):
    return next(seq for seq in engine._active.values()
                if seq.prompt == tuple(prompt))


class TestTheEngineGivesThePlainLoopsIds:
    def test_joins_and_every_kind_of_end(self, family):
        """A runs long; B joins mid-stream and ends by its budget while
        the others run on; C fills the cache; D ends by ``eos_id``."""
        async def main():
            engine, reg = family.engine(eos=True)
            await engine.start()
            a = asyncio.ensure_future(engine.submit(PROMPTS["a"], 24))
            await in_flight(engine, 3)
            b, c, d = (asyncio.ensure_future(engine.submit(PROMPTS[n], m))
                       for n, m in (("b", 4), ("c", 64), ("d", 24)))
            out = await asyncio.gather(a, b, c, d)
            await wait_until(lambda: settled(engine))
            await engine.stop()
            return out, reg

        (a, b, c, d), reg = asyncio.run(main())
        assert a == family.want("a", 24, eos=True)
        assert b == family.want("b", 4, eos=True)
        assert c == family.want("c", 64, eos=True)
        assert d == family.want("d", 24, eos=True)
        assert len(b) <= 4 and len(c) <= 6
        assert d[-1] == family.eos_id and len(d) < 24
        launched = counted(reg, "ai4e_decode_step_launches_total", kind="all")
        ahead = counted(reg, "ai4e_decode_step_launches_total", kind="ahead")
        assert launched - 2 <= ahead < launched
        # D's step after its EOS had been launched: computed, discarded.
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") >= 1

    @pytest.mark.parametrize("clump", [1, 2, 4, 7])
    def test_a_clump_joined_ahead_gives_the_plain_loops_ids(self, family,
                                                            clump):
        """``clump`` requests at once on three slots: every join of a pass
        is dispatched before anything is read, the first ids stay on the
        device, and each stream is the plain loop's — index 0 first."""
        wanted = [("abcde"[i % 5], (9, 1, 6, 2, 12, 5, 3)[i])
                  for i in range(clump)]
        seen = []

        async def main():
            engine, reg = family.engine()
            await engine.start()
            out = await asyncio.gather(*[
                engine.submit(PROMPTS[name], budget,
                              on_token=lambda i, t, k=k: seen.append(
                                  (k, i, t)))
                for k, (name, budget) in enumerate(wanted)])
            await wait_until(lambda: settled(engine))
            await engine.stop()
            return out, reg

        out, reg = asyncio.run(main())
        assert out == [family.want(name, budget) for name, budget in wanted]
        for k, tokens in enumerate(out):
            assert [(i, t) for j, i, t in seen if j == k] == list(
                enumerate(tokens))
        joins = "ai4e_decode_joins_total"
        assert counted(reg, joins, kind="all") == clump
        # All but a request for one token that found nothing else to ride.
        assert clump - 1 <= counted(reg, joins, kind="ahead") <= clump

    def test_a_cancel_and_an_expiry_with_a_step_in_flight(self, family):
        async def main():
            engine, reg = family.engine()
            await engine.start()
            a, x, y = (asyncio.ensure_future(engine.submit(PROMPTS[n], 40))
                       for n in "abd")
            await in_flight(engine)
            assert engine._launched
            sequence_of(engine, PROMPTS["d"]).deadline_at = 1.0  # long past
            x.cancel()
            got = await asyncio.gather(a, x, y, return_exceptions=True)
            await wait_until(lambda: settled(engine))
            await engine.stop()
            return got, reg

        (a, x, y), reg = asyncio.run(main())
        assert a == family.want("a", 40)
        assert isinstance(x, asyncio.CancelledError)
        assert isinstance(y, DeadlineExceeded)
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") >= 1

    def test_a_reload_with_a_step_in_flight(self, family):
        import jax

        async def main():
            engine, reg = family.engine()
            await engine.start()
            a = asyncio.ensure_future(engine.submit(PROMPTS["a"], 30))
            e = asyncio.ensure_future(engine.submit(PROMPTS["e"], 30))
            await in_flight(engine, 3)
            family.served.reload_params(jax.tree.map(
                lambda w: w + 0, family.served.servable.params))
            out = await asyncio.gather(a, e)
            await wait_until(lambda: settled(engine))
            await engine.stop()
            return out, reg

        (a, e), reg = asyncio.run(main())
        # The same weights again: re-prefilled, and the same ids.
        assert a == family.want("a", 30) and e == family.want("e", 30)
        assert counted(reg, "ai4e_decode_reprefills_total") == 2

    def test_a_failure_at_fetch_voids_the_steps_and_the_engine_goes_on(
            self, family):
        async def main():
            engine, _ = family.engine()
            await engine.start()
            a, b = (asyncio.ensure_future(engine.submit(PROMPTS[n], 40))
                    for n in "ab")
            await in_flight(engine)
            family.served.fail_fetch = True
            failed = await asyncio.gather(a, b, return_exceptions=True)
            await wait_until(lambda: settled(engine))
            after = await engine.submit(PROMPTS["e"], 10)
            await wait_until(lambda: settled(engine))
            await engine.stop()
            return failed, after

        failed, after = asyncio.run(main())
        assert all(isinstance(exc, RuntimeError)
                   and "decode step failed" in str(exc) for exc in failed)
        assert after == family.want("e", 10)

    def test_a_drain_and_a_stop_with_a_step_in_flight(self, family):
        async def main():
            engine, _ = family.engine()
            await engine.start()
            a = asyncio.ensure_future(engine.submit(PROMPTS["a"], 40))
            await in_flight(engine)
            assert engine.begin_drain() == 0 and not engine.drain_complete
            assert engine.force_drain() == 1
            with pytest.raises(DrainingError):
                await a
            await wait_until(lambda: engine.drain_complete)
            assert settled(engine)
            engine.resume_from_drain()
            again = await engine.submit(PROMPTS["a"], 12)
            b = asyncio.ensure_future(engine.submit(PROMPTS["b"], 40))
            await in_flight(engine)
            await engine.stop()
            assert settled(engine)
            with pytest.raises(RuntimeError, match="stopped"):
                await b
            return again

        assert asyncio.run(main()) == family.want("a", 12)


# -- a scripted backend that records the order of launches and fetches --------


class Scripted:
    """Counts up from the token a slot was fed, as the tests' blocking fakes
    do, behind ``launch`` / ``fetch``: the ids of the last launched step
    stay here. ``log`` holds every event in order; ``launches`` what each
    launch saw."""

    name, params_version = "lm", 1

    def __init__(self, slots=2, max_len=64, eos_id=None):
        self.slots, self.max_len, self.eos_id = slots, max_len, eos_id
        self._ids = [0] * slots
        self.unread = []          # launched, not fetched
        self.launches = []        # (active slots, previous step unread?)
        self.log = []

    def reset_cache(self):
        self.log.append("reset")
        assert not self.unread, "cache reset under a launched step"

    def _free_of_steps(self, slot):
        assert not any(step.active[slot] for step in self.unread), (
            f"join written into slot {slot} under a step launched for its "
            f"previous tenant")

    def prefill_into(self, slot, tokens):
        self._free_of_steps(slot)
        self.log.append(("prefill", slot))
        return int(tokens[-1]) + 1

    def launch(self, fresh, positions, active):
        tokens = [self._ids[slot] if token is None else token
                  for slot, token in enumerate(fresh)]
        self._ids = [t + 1 for t in tokens]
        step = LaunchedStep(bound=self.max_len, active=list(active),
                            out=list(self._ids), fed=tokens)
        self.launches.append(
            ([slot for slot, live in enumerate(active) if live],
             bool(self.unread)))
        self.unread.append(step)
        self.log.append(("launch", len(self.launches)))
        return step

    def fetch(self, step):
        assert self.unread and self.unread[0] is step, "fetched out of order"
        self.unread.pop(0)
        self.log.append(("fetch", len(self.launches) - len(self.unread)))
        step.ids, step.out = step.out, None
        return step


def serve_scripted(backend, requests, between=None):
    """Run ``requests`` (``(prompt, budget)`` in submit order) to their
    end; returns ``(outputs, registry)``."""
    reg = MetricsRegistry()

    async def main():
        engine = DecodeEngine(backend, metrics=reg)
        await engine.start()
        futures = [asyncio.ensure_future(engine.submit(
            prompt, budget,
            on_token=lambda i, t, p=prompt: backend.log.append(
                ("token", tuple(p), i, t))))
            for prompt, budget in requests]
        if between is not None:
            await between(engine, futures)
        out = await asyncio.gather(*futures, return_exceptions=True)
        await wait_until(lambda: settled(engine))
        await engine.stop()
        return out

    return asyncio.run(main()), reg


class TestTheOrderOfLaunchAndFetch:
    @pytest.mark.parametrize("steps", [1, 2, 9])
    def test_a_steady_run_of_n_steps_launches_all_but_the_first_ahead(
            self, steps):
        backend = Scripted()
        (out,), reg = serve_scripted(backend, [([1], steps + 1)])
        assert out == list(range(2, steps + 3))
        assert [ahead for _, ahead in backend.launches] == (
            [False] + [True] * (steps - 1))
        launches = "ai4e_decode_step_launches_total"
        assert counted(reg, launches, kind="all") == steps
        assert counted(reg, launches, kind="ahead") == steps - 1
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 0
        total, observed = series(reg, "ai4e_decode_step_active_slots",
                                 model="lm")
        assert (total, observed) == (steps, steps)

    def test_each_token_is_delivered_as_soon_as_its_step_is_fetched(self):
        """Launch N+1, fetch N, deliver N's token — before N+2 is launched;
        a sequence's tokens in order."""
        backend = Scripted(slots=1)
        serve_scripted(backend, [([1], 5)])
        events = [ev for ev in backend.log if ev[0] != "prefill"]
        assert events == [
            ("launch", 1),
            ("launch", 2), ("fetch", 1),
            ("token", (1,), 0, 2),       # the prefill's: read with step 1
            ("token", (1,), 1, 3),
            ("launch", 3), ("fetch", 2), ("token", (1,), 2, 4),
            ("launch", 4), ("fetch", 3), ("token", (1,), 3, 5),
            ("fetch", 4), ("token", (1,), 4, 6)]

    def test_a_sequence_that_ends_by_its_budget_is_not_in_the_next_launch(
            self):
        """A wants 3 steps, B 6: launch 4 goes out before step 3 is read,
        and without A."""
        backend = Scripted()
        (a, b), reg = serve_scripted(backend, [([10], 4), ([20], 7)])
        assert (a, b) == ([11, 12, 13, 14], list(range(21, 28)))
        assert [slots for slots, _ in backend.launches] == (
            [[0, 1]] * 3 + [[1]] * 3)
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 0

    def test_a_sequence_that_fills_the_cache_is_not_in_the_next_launch(self):
        backend = Scripted(slots=1, max_len=6)
        (out,), reg = serve_scripted(backend, [([1, 2, 3], 64)])
        assert len(out) == 4          # positions 3, 4, 5 written, then full
        assert len(backend.launches) == 3
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 0

    def test_an_end_by_eos_costs_exactly_one_discarded_slot_step(self):
        """A reaches ``eos_id`` at its third step; step four was launched
        with it before that was read. B, beside it, loses nothing."""
        backend = Scripted(eos_id=5)
        (a, b), reg = serve_scripted(backend, [([1], 64), ([20], 7)])
        assert a == [2, 3, 4, 5] and b == list(range(21, 28))
        assert [slots for slots, _ in backend.launches] == (
            [[0, 1]] * 4 + [[1]] * 2)
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 1
        # One observation a step, with the riders of its launch.
        total, observed = series(reg, "ai4e_decode_step_active_slots",
                                 model="lm")
        assert (total, observed) == (10, 6)


class TestASlotWaitsForTheStepsThatHoldIt:
    @pytest.mark.parametrize("ended_by", ["eos", "cancel", "expiry"])
    def test_no_join_under_a_step_launched_for_the_previous_tenant(
            self, ended_by):
        """One slot, a second request queued behind the first: however the
        first ends while a launched step holds it, the second's prefill
        comes after that step's fetch (``Scripted.prefill_into`` asserts
        it), and the pool is whole at every point in between."""
        backend = Scripted(slots=1, eos_id=5 if ended_by == "eos" else None)

        async def between(engine, futures):
            if ended_by == "eos":
                return
            await wait_until(lambda: backend.unread and engine._active
                             and len(engine._active[0].tokens) >= 2)
            seq = engine._active[0]
            if ended_by == "cancel":
                futures[0].cancel()
                await asyncio.sleep(0)
                engine.cancel(seq.future)
            else:
                seq.deadline_at = 1.0
            # Retired, its slot parked: busy, no tenant, not for a join.
            await wait_until(lambda: seq.done)
            engine.pool.check_conservation()

        (first, second), _ = serve_scripted(
            backend, [([1], 1000), ([20], 3)], between=between)
        assert second == [21, 22, 23]
        if ended_by == "eos":
            assert first == [2, 3, 4, 5]
        else:
            assert isinstance(first, (asyncio.CancelledError,
                                      DeadlineExceeded))
        joins = [i for i, ev in enumerate(backend.log)
                 if ev == ("prefill", 0)]
        assert len(joins) == 2
        # Every step launched before the second join was fetched before it.
        before = backend.log[:joins[1]]
        assert (sum(ev[0] == "launch" for ev in before)
                == sum(ev[0] == "fetch" for ev in before))

    def test_a_reload_reads_the_step_in_flight_before_the_cache_is_reset(
            self):
        backend = Scripted(slots=1)

        async def between(engine, futures):
            await wait_until(lambda: backend.unread)
            backend.params_version += 1

        (out,), reg = serve_scripted(backend, [([1], 12)], between=between)
        # Its own history re-prefilled: the count goes on where it was.
        assert out == list(range(2, 14))
        assert "reset" in backend.log   # which asserts nothing was unread
        assert counted(reg, "ai4e_decode_reprefills_total") == 1


class Ahead(Scripted):
    """``Scripted`` with the join that does not block: the first id stays
    in ``_ids``, where the next launch finds it."""

    def join(self, slot, tokens):
        self._free_of_steps(slot)
        self.log.append(("join", slot))
        self._ids[slot] = int(tokens[-1]) + 1

    def first_ids(self):
        self.log.append("first_ids")
        return list(self._ids)


def by_hand(backend, script):
    """``script(engine, tick)`` on an engine whose ticks the script drives;
    afterwards nothing is in flight and the pool is whole. Returns
    ``(what the script returned, registry)``."""
    reg = MetricsRegistry()

    async def main():
        engine = DecodeEngine(backend, metrics=reg)

        async def tick(n=1):
            for _ in range(n):
                await asyncio.sleep(0)      # submits reach the queue
                await engine._tick()

        out = await script(engine, tick)
        for _ in range(200):
            if not (engine._active or engine._queue or engine._launched):
                break
            await tick()
        await engine.stop()
        assert settled(engine)
        return out

    return asyncio.run(main()), reg


def submit(engine, backend, prompt, budget, **kw):
    """A submitted request whose tokens land in ``backend.log``."""
    return asyncio.ensure_future(engine.submit(
        prompt, budget, on_token=lambda i, t: backend.log.append(
            ("token", tuple(prompt), i, t)), **kw))


def calls(backend):
    return [ev if isinstance(ev, str) else ev[0] for ev in backend.log
            if ev == "first_ids" or ev[0] in ("join", "launch", "fetch")]


class TestAJoinDoesNotStopTheLoop:
    @pytest.mark.parametrize("clump,slots", [(1, 2), (2, 2), (7, 8), (5, 3)])
    def test_a_pass_dispatches_its_joins_then_launches_then_fetches(
            self, clump, slots):
        """join, join, ..., launch — and the first fetch only after the
        launch that carries the joins; a clump larger than the pool joins
        as slots come free."""
        backend = Ahead(slots=slots)
        out, reg = serve_scripted(
            backend, [([10 * (i + 1)], 3) for i in range(clump)])
        assert out == [[10 * (i + 1) + k for k in (1, 2, 3)]
                       for i in range(clump)]
        first = min(clump, slots)
        assert calls(backend)[:first + 3] == (
            ["join"] * first + ["launch", "launch", "fetch"])
        assert "first_ids" not in backend.log
        assert not any(ev[0] == "prefill" for ev in backend.log
                       if ev != "first_ids")
        assert counted(reg, "ai4e_decode_joins_total", kind="all") == clump
        assert counted(reg, "ai4e_decode_joins_total", kind="ahead") == clump
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 0

    def test_on_token_sees_index_0_before_index_1_with_the_same_ids(self):
        backend = Ahead(slots=2)
        (a, b), _ = serve_scripted(backend, [([1], 4), ([20], 2)])
        assert (a, b) == ([2, 3, 4, 5], [21, 22])
        tokens = [ev for ev in backend.log if ev[0] == "token"]
        assert tokens[:4] == [("token", (1,), 0, 2), ("token", (1,), 1, 3),
                              ("token", (20,), 0, 21), ("token", (20,), 1, 22)]
        # Both first ids came with the fetch of step 1, after launch 2.
        assert backend.log.index(("fetch", 1)) < backend.log.index(tokens[0])
        assert backend.log.index(("launch", 2)) < backend.log.index(
            ("fetch", 1))

    def test_a_request_for_one_token_alone_is_read_without_a_step(self):
        backend = Ahead(slots=2)
        (a, b), reg = serve_scripted(backend, [([1], 1), ([20], 1)])
        assert (a, b) == ([2], [21])
        assert calls(backend) == ["join", "join", "first_ids"]
        assert counted(reg, "ai4e_decode_step_launches_total", kind="all") == 0
        assert counted(reg, "ai4e_decode_joins_total", kind="all") == 2
        assert counted(reg, "ai4e_decode_joins_total", kind="ahead") == 0
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 0

    def test_a_request_for_one_token_among_others_rides_their_step(self):
        """It is not known finished when the step is launched: one
        discarded slot-step, no launch of its own, no read of its own."""
        backend = Ahead(slots=2)
        (a, b), reg = serve_scripted(backend, [([1], 1), ([20], 3)])
        assert (a, b) == ([2], [21, 22, 23])
        assert [slots for slots, _ in backend.launches] == [[0, 1], [1]]
        assert "first_ids" not in backend.log
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 1
        assert counted(reg, "ai4e_decode_joins_total", kind="ahead") == 2

    def test_a_first_id_that_is_eos_costs_one_discarded_slot_step(self):
        backend = Ahead(slots=2, eos_id=2)
        (a, b), reg = serve_scripted(backend, [([1], 64), ([20], 3)])
        assert (a, b) == ([2], [21, 22, 23])
        assert [slots for slots, _ in backend.launches] == [[0, 1], [0, 1]]
        # Step 1's id for it, and step 2's, launched before its EOS was read.
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") == 2

    @pytest.mark.parametrize("ended_by", ["cancel", "expiry"])
    def test_retired_between_its_join_and_the_read(self, ended_by):
        """Neither id reaches it, its slot stays parked while the step that
        carries it is unread, and the request queued behind it joins that
        slot only after the fetch (``Ahead.join`` asserts it)."""
        backend = Ahead(slots=1)

        async def script(engine, tick):
            first = submit(engine, backend, [1], 9)
            second = submit(engine, backend, [20], 2)
            await tick()                     # join + launch 1
            seq = engine._active[0]
            assert not seq.tokens and engine._launched
            if ended_by == "cancel":
                engine.cancel(seq.future)
            else:
                seq.deadline_at = 1.0
                engine._sweep()
            assert seq.done and engine._parked == {0}
            engine.pool.check_conservation()
            await tick(6)
            return await asyncio.gather(first, second,
                                        return_exceptions=True)

        (first, second), reg = by_hand(backend, script)
        assert first == [] if ended_by == "cancel" else isinstance(
            first, DeadlineExceeded)
        assert second == [21, 22]
        assert not any(ev[:2] == ("token", (1,)) for ev in backend.log)
        joins = [i for i, ev in enumerate(backend.log) if ev == ("join", 0)]
        before = backend.log[:joins[1]]
        assert (sum(ev[0] == "launch" for ev in before)
                == sum(ev[0] == "fetch" for ev in before))
        assert counted(reg, "ai4e_decode_discarded_slot_steps_total") >= 1
        assert counted(reg, "ai4e_decode_joins_total", kind="all") == 2
        assert counted(reg, "ai4e_decode_joins_total", kind="ahead") == 1

    def test_a_join_that_fails_surfaces_at_the_read(self):
        """The device's failure of a joined prefill comes up at the fetch of
        the step that carried it: its sequence fails, what was launched
        after is void, and the engine goes on."""
        backend = Ahead(slots=2)
        fetch = backend.fetch

        def poisoned(step):
            backend.fetch = fetch
            backend.unread.clear()
            raise RuntimeError("the prefill fell over")

        async def between(engine, futures):
            await asyncio.gather(*futures, return_exceptions=True)
            futures.append(asyncio.ensure_future(engine.submit([30], 3)))

        backend.fetch = poisoned
        (a, b, c), reg = serve_scripted(backend, [([1], 5), ([20], 5)],
                                        between=between)
        assert all(isinstance(exc, RuntimeError)
                   and "decode step failed" in str(exc) for exc in (a, b))
        assert c == [31, 32, 33]
        assert not any(ev[0] == "token" and ev[1] != (30,)
                       for ev in backend.log)
        assert counted(reg, "ai4e_decode_sequences_total",
                       outcome="failed") == 2

    def test_a_reload_with_a_join_unread_settles_it_with_its_step(self):
        backend = Ahead(slots=1)

        async def script(engine, tick):
            answer = submit(engine, backend, [1], 8)
            await tick()                     # join + launch 1
            assert not engine._active[0].tokens
            backend.params_version += 1
            await tick()                     # reads step 1, then resets
            assert engine._active[0].tokens == [2, 3, 4]
            await tick(8)
            return await answer

        out, reg = by_hand(backend, script)
        assert out == list(range(2, 10))
        reset = backend.log.index("reset")      # asserted nothing unread
        assert backend.log[reset - 3:reset] == [
            ("fetch", 1), ("token", (1,), 0, 2), ("token", (1,), 1, 3)]
        assert backend.log[reset + 1] == ("prefill", 0)
        assert counted(reg, "ai4e_decode_reprefills_total") == 1
        assert counted(reg, "ai4e_decode_joins_total", kind="ahead") == 1

    def test_a_drain_with_a_join_unread_reads_it_and_frees_the_slot(self):
        backend = Ahead(slots=2)

        async def script(engine, tick):
            kept = submit(engine, backend, [1], 3)
            forced = submit(engine, backend, [20], 50)
            await tick()                     # two joins + launch 1
            queued = submit(engine, backend, [30], 2)
            await asyncio.sleep(0)
            assert engine.begin_drain() == 1 and not engine.drain_complete
            await tick(4)                    # the first ids are read, A ends
            assert engine.force_drain() == 1
            await tick(3)
            assert engine.drain_complete
            return await asyncio.gather(kept, forced, queued,
                                        return_exceptions=True)

        (kept, forced, queued), _ = by_hand(backend, script)
        assert kept == [2, 3, 4]
        assert isinstance(forced, DrainingError)
        assert isinstance(queued, DrainingError)
        assert ("token", (20,), 0, 21) in backend.log


class FakeArray:
    """What a scripted program returns: knows whether it was waited for."""

    def __init__(self, waited):
        self.waited = waited

    def block_until_ready(self):
        self.waited.add(id(self))
        return self

    def __getitem__(self, index):
        self.waited.add(id(self))   # a read waits too
        return 7

    def __array__(self, dtype=None, copy=None):
        self.waited.add(id(self))   # the whole vector's read (PR 40) as well
        return np.asarray([7], dtype)


class FakePrograms(dict):
    """The ``prefill`` / ``insert`` executables of a runtime's one bucket
    over a scripted device: at every prefill's dispatch, how many earlier
    joins nobody has waited for."""

    def __init__(self, bucket):
        self.waited, self.tokens, self.unwaited = set(), [], []
        self["prefill", bucket] = self._prefill
        self["insert", bucket] = lambda *args: tuple(
            FakeArray(self.waited) for _ in range(3))

    def _prefill(self, params, padded, length):
        self.unwaited.append(sum(id(t) not in self.waited
                                 for t in self.tokens))
        self.tokens.append(FakeArray(self.waited))
        return (self.tokens[-1], *(FakeArray(self.waited) for _ in range(3)))


@pytest.mark.parametrize("joins", [1, 2, 3, 9])
def test_the_runtime_keeps_at_most_two_joins_in_flight(joins):
    """Before join ``i`` is dispatched the runtime has waited for join
    ``i - 2``: one running, one queued, whatever the clump — a rule of the
    code, not a setting."""
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    runtime = PagedDecodeRuntime(build_lm_servable(
        family="seqformer-lm", name="lm", max_len=MAX_LEN,
        **FAMILIES["seqformer-lm"]), slots=4, prompt_buckets=(8,))
    runtime._rows = runtime._state = object()
    runtime._ids = object()
    runtime._programs = {}   # built: nothing below may reach for them
    programs = runtime._executables = FakePrograms(bucket=8)
    for i in range(joins):
        assert runtime.join(i % 4, [1, 2, 3]) is None
    assert programs.unwaited == [0, 1, 1, 1, 1, 1, 1, 1, 1][:joins]
    assert runtime.prefill_into(0, [1, 2, 3]) == 7   # a join, then the read
    assert programs.unwaited[-1] == min(joins, 1)
    assert len(programs.tokens) == joins + 1


def test_insert_leaves_the_first_id_where_the_next_launch_finds_it(family):
    """On each family's runtime: a join writes its prefill's id into the
    device-resident ids at its slot — also under a step launched before it —
    and a launch that leaves the slot to the device (``fresh[slot] = None``)
    gives the ids of one that feeds that id from the host; nothing compiles
    that ``warm()`` did not build."""
    ahead, fed = family.served, family.plain
    ahead.reset_cache()
    fed.reset_cache()
    told = []
    ahead.phase_hook = lambda phase, seconds: told.append(phase)
    try:
        first = fed.prefill_into(1, list(PROMPTS["a"]))
        assert ahead.join(1, list(PROMPTS["a"])) is None
        assert ahead.first_ids()[1] == first
        positions, active = [0, 3, 0], [False, True, False]
        one = ahead.launch([None] * SLOTS, positions, active)
        # A second prompt joins under step 1, unread: slot 1 goes on feeding
        # on step 1's id, slot 2 on its own first id.
        ahead.join(2, list(PROMPTS["d"]))
        two = ahead.launch([None] * SLOTS, [0, 4, 4], [False, True, True])
        got = [ahead.fetch(one), ahead.fetch(two)]
    finally:
        ahead.phase_hook = None
    assert "compile" not in told and told.count("enqueue") == 4
    want = [fed.fetch(fed.launch([None, first, None], positions, active))]
    other = fed.prefill_into(2, list(PROMPTS["d"]))
    want.append(fed.fetch(fed.launch(
        [None, want[0].ids[1], other], [0, 4, 4], [False, True, True])))
    assert [step.ids[1] for step in got] == [step.ids[1] for step in want]
    assert got[1].ids[2] == want[1].ids[2]
    assert got[0].fed[1] == first and got[1].fed[1:] == [got[0].ids[1], other]
    assert want[1].fed[1:] == [want[0].ids[1], other]


def test_a_blocking_step_backend_rides_the_same_loop():
    """A backend with only ``step`` is adapted in one place: its launch runs
    the step, and the engine still counts every launch but a burst's first
    as ahead."""
    from test_decode import FakeBackend
    backend = FakeBackend(slots=2)
    reg = MetricsRegistry()

    async def main():
        engine = DecodeEngine(backend, metrics=reg)
        await engine.start()
        out = await asyncio.gather(engine.submit([1], 6),
                                   engine.submit([7], 3))
        await engine.stop()
        assert settled(engine)
        return out

    assert asyncio.run(main()) == [[2, 3, 4, 5, 6, 7], [8, 9, 10]]
    assert backend.steps == 5
    assert counted(reg, "ai4e_decode_step_launches_total", kind="ahead") == 4


def test_the_ids_stay_on_the_device_between_launches():
    """Two launches and then two fetches on the real runtime give what two
    blocking steps give: the second launch fed on the first's ids, which
    the host had not read."""
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    servable = build_lm_servable(family="seqformer-lm", name="lm",
                                 max_len=MAX_LEN, **FAMILIES["seqformer-lm"])
    ahead, blocking = (PagedDecodeRuntime(servable, slots=2,
                                          prompt_buckets=(8,))
                       for _ in range(2))
    prompt = [5, 9, 12]
    first = ahead.prefill_into(1, prompt)
    assert blocking.prefill_into(1, prompt) == first
    one = ahead.launch([None, first], [0, 3], [False, True])
    two = ahead.launch([None, None], [0, 4], [False, True])
    got = [ahead.fetch(one).ids[1], ahead.fetch(two).ids[1]]
    want = [blocking.step([0, first], [0, 3], [False, True])[1]]
    want.append(blocking.step([0, want[0]], [0, 4], [False, True])[1])
    assert got == want
    assert one.out is None and one.bound == two.bound == MAX_LEN
    assert ahead.step([0, got[1]], [0, 5], [False, True])[1] == (
        blocking.step([0, want[1]], [0, 5], [False, True])[1])
