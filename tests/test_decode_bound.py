"""The decode step attends only as far as its longest live sequence
(``runtime/kvcache.py`` ``step_bounds`` / ``bound_for``, the families'
``decode_step(..., bound)``, ``runtime/decode.py``'s counters).

- a bound at or above every live position gives the step of the whole
  cache: the same ids, logits and written rows, to the order of a sum; a
  bound below a live position does not (so the first can fail);
- the rung rule: ladders by ``max_len``, the rung a largest live position
  takes, stale positions of inactive slots ignored;
- ``warm()`` compiles every rung, a reload keeps them, a rung that was not
  warmed is counted as a compile;
- the engine counts as attended what its backend says the step read
  (``step_attended``: the runtime's count of the blocks its attention
  fetched), ``slots x bound`` for a backend that reports a bound alone and
  ``slots x max_len`` for one that reports nothing, and observes
  ``ai4e_decode_step_bound``.
"""

import asyncio
from types import SimpleNamespace

import numpy as np
import pytest

from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.runtime.decode import DecodeEngine
from ai4e_tpu.runtime.kvcache import (LMServable, PagedDecodeRuntime,
                                      build_lm_servable)
from test_decode_tick import series

FAMILIES = {
    "seqformer-lm": {},
    "olmoe": dict(experts=8, experts_per_token=2, expert_dim=32),
}
MAX_LEN, SLOTS = 512, 3
RUNGS = (384, 512)              # the runtime's ladder for a cache of 512
BOUNDS = (256, 384, 512)        # a family's step takes any static bound
# float32: the two programs differ in the order of a sum. bfloat16: the
# tolerance ``tests/test_olmoe.py`` holds the family to against its reference.
TOLERANCE = {4: 1e-5, 2: 0.04}


def build_runtime(family, **kw):
    servable = build_lm_servable(
        family=family, name="lm", vocab_size=64, max_len=MAX_LEN, dim=32,
        depth=2, heads=4, **FAMILIES[family])
    return PagedDecodeRuntime(servable, slots=SLOTS, prompt_buckets=(8,),
                              **kw)


@pytest.fixture(scope="module", params=list(FAMILIES))
def lm(request):
    """A family's step as the runtime builds it (ids) and its logits entry,
    over a pool of random keys and values: a step reads whatever lies
    below a slot's position."""
    import jax
    runtime = build_runtime(request.param)
    model, params = runtime.servable.model, runtime.servable.params
    shape, dtype = runtime.cache_spec()[0]
    rng = np.random.default_rng(7)
    k = rng.standard_normal(shape).astype(dtype)
    v = rng.standard_normal(shape).astype(dtype)

    def program(method, *state):
        return jax.jit(
            lambda tokens, k, v, position, bound: model.apply(
                params, tokens, k, v, *state, position, bound,
                method=method),
            static_argnums=(4,))

    return SimpleNamespace(
        runtime=runtime, ids=program("decode_step", {}),
        logits=program("decode_logits"), pool=(k, v),
        tol=TOLERANCE[np.dtype(dtype).itemsize])


def run_step(lm, positions, bound, pool=None):
    tokens = np.arange(5, 5 + SLOTS, dtype=np.int32)
    positions = np.asarray(positions, np.int32)
    k, v = pool or lm.pool
    ids = np.asarray(lm.ids(tokens, k, v, positions, bound)[0])[:SLOTS]
    logits, k, v = lm.logits(tokens, k, v, positions, bound)
    rows = [np.stack([np.asarray(pool)[:, slot, p].astype(np.float32)
                      for slot, p in enumerate(positions)])
            for pool in (k, v)]
    return ids, np.asarray(logits, np.float32), rows


# The longest live position of each case, and the slots beside it.
LIVE = {256: (256, 3, 120), 300: (41, 300, 0), 384: (384, 384, 384),
        500: (7, 19, 500)}


class TestABoundAboveEveryLivePositionChangesNothing:
    @pytest.mark.parametrize("longest,bound", [
        (longest, bound) for longest in LIVE for bound in BOUNDS
        if bound >= longest])
    def test_same_ids_logits_and_rows_as_the_whole_cache(self, lm, longest,
                                                         bound):
        ids, logits, rows = run_step(lm, LIVE[longest], bound)
        want_ids, want_logits, want_rows = run_step(lm, LIVE[longest], None)
        assert (ids == want_ids).all()
        np.testing.assert_allclose(logits, want_logits, rtol=0, atol=lm.tol)
        for got, want in zip(rows, want_rows):
            np.testing.assert_allclose(got, want, rtol=lm.tol, atol=lm.tol)

    @pytest.mark.parametrize("longest,bound", [(300, 256), (500, 384)])
    def test_a_bound_below_a_live_position_shows(self, lm, longest, bound):
        """The slot past the bound loses keys it attends (their values
        made large, so that losing them cannot pass for rounding), and its
        logits move by far more than a sum's order; the slots under the
        bound keep theirs."""
        k, v = lm.pool
        v = v.copy()
        v[:, :, bound:] *= 8
        _, logits, _ = run_step(lm, LIVE[longest], bound, (k, v))
        _, want, _ = run_step(lm, LIVE[longest], None, (k, v))
        cut = int(np.argmax(LIVE[longest]))
        assert np.abs(logits[cut] - want[cut]).max() > 10 * lm.tol
        kept = [s for s in range(SLOTS) if s != cut]
        np.testing.assert_allclose(logits[kept], want[kept], rtol=0,
                                   atol=lm.tol)

    def test_the_top_rung_is_the_program_of_the_whole_cache(self, lm):
        """A step whose longest live sequence is past the lower rung runs
        what it ran before there were rungs: ``bound = max_len`` lowers to
        the same module as no bound at all."""
        tokens = np.zeros(SLOTS, np.int32)
        k, v = lm.pool
        assert (lm.ids.lower(tokens, k, v, tokens, MAX_LEN).as_text()
                == lm.ids.lower(tokens, k, v, tokens, None).as_text())
        assert lm.runtime.bound_for(RUNGS[0] + 1) == MAX_LEN


def ladder(max_len):
    servable = LMServable(name="lm", model=None, params=None, vocab_size=8,
                          max_len=max_len)
    return PagedDecodeRuntime(servable, slots=2)


class TestTheRungRule:
    @pytest.mark.parametrize("max_len,rungs", [
        (1024, (768, 1024)),            # gpt2-medium
        (2048, (1536, 2048)),           # olmoe-1b-7b
        (1000, (768, 1000)),            # rounded up to 128s, the top clamped
        (512, (384, 512)),
        (200, (200,)),                  # 150 rounds up past the cache
        (129, (128, 129)),
        (128, (128,)),
        (24, (24,)),                    # a test model: today's one program
    ])
    def test_ladder_of_a_cache_length(self, max_len, rungs):
        runtime = ladder(max_len)
        assert runtime.step_bounds == rungs
        assert runtime.step_bounds[-1] == runtime.max_len

    @pytest.mark.parametrize("longest,bound", [
        (0, 768), (1, 768), (512, 768), (767, 768), (768, 768), (769, 1024),
        (770, 1024), (1023, 1024), (1024, 1024), (1500, 1024)])
    def test_smallest_rung_that_holds_the_longest_live_position(
            self, longest, bound):
        assert ladder(1024).bound_for(longest) == bound

    @pytest.mark.parametrize("positions,active,bound", [
        ((10, 500, 500), (True, False, False), 384),   # stale and long
        ((384, 385, 1), (True, False, True), 384),
        ((384, 385, 0), (True, True, False), 512),
        ((0, 0, 0), (False, False, False), 384),       # nobody lives
        ((385, 1, 1), (True, True, True), 512),   # an active slot is past 0
    ])
    def test_a_step_takes_the_rung_of_its_live_slots(self, lm, positions,
                                                     active, bound):
        runtime = lm.runtime
        step = runtime.fetch(runtime.launch([1] * SLOTS, list(positions),
                                            list(active)))
        assert len(step.ids) == SLOTS
        assert step.bound == bound


class TestEveryRungIsWarmed:
    @pytest.fixture(scope="class", params=list(FAMILIES))
    def warmed(self, request):
        runtime = build_runtime(request.param)
        runtime.warm()
        return runtime

    @pytest.mark.parametrize("bound", RUNGS)
    def test_a_step_at_a_warmed_rung_compiles_nothing(self, warmed, bound):
        assert warmed.step_bounds == RUNGS
        assert sorted(size for name, size in warmed._executables
                      if name == "step") == list(RUNGS)
        phases = []
        warmed.phase_hook = _compiles_and_waits(phases)
        step = warmed.fetch(warmed.launch([1] * SLOTS, [bound - 1, 0, 0],
                                          [True, False, False]))
        assert step.bound == bound
        assert phases == ["device_wait"]

    def test_a_reload_keeps_the_programs(self, warmed):
        import jax
        warmed.reload_params(jax.tree.map(lambda a: a + 0,
                                          warmed.servable.params))
        warmed.reset_cache()
        phases = []
        warmed.phase_hook = _compiles_and_waits(phases)
        for bound in RUNGS:
            warmed.step([1] * SLOTS, [bound] * SLOTS, [True] * SLOTS)
        assert phases == ["device_wait"] * len(RUNGS)

    def test_a_rung_that_was_not_warmed_is_a_compile(self, warmed):
        warmed._executables = {
            key: call for key, call in warmed._executables.items()
            if key[0] != "step"}
        phases = []
        warmed.phase_hook = _compiles_and_waits(phases)
        for position in (5, 6, 400):
            warmed.step([1] * SLOTS, [position, 0, 0], [True, False, False])
        assert phases == ["compile", "device_wait", "device_wait",
                          "compile", "device_wait"]


def _compiles_and_waits(phases):
    """A ``phase_hook`` that keeps the two phases these tests are about; the
    device thread's ledger is told more (``tests/test_device_ledger.py``)."""
    return lambda phase, seconds: (
        phases.append(phase) if phase in ("compile", "device_wait") else None)


class RungBackend:
    """A backend with ``PagedDecodeRuntime``'s ladder and report, and no
    device: counts up from the last token."""

    slots, max_len, eos_id, name, params_version = 2, 1024, None, "lm", 1
    step_bounds = (512, 768, 1024)

    def __init__(self):
        self.step_bound = self.max_len
        self.bounds = []

    def reset_cache(self):
        pass

    def bound_for(self, longest):
        return next(b for b in self.step_bounds if b >= longest)

    def prefill_into(self, slot, tokens):
        return int(tokens[-1]) + 1

    def step(self, tokens, positions, active):
        self.step_bound = self.bound_for(max(
            p for p, live in zip(positions, active) if live))
        self.bounds.append(self.step_bound)
        return [int(t) + 1 for t in tokens]


class NoBoundBackend:
    """The race tests' fakes: no ladder, no report."""

    slots, max_len, eos_id, name, params_version = 2, 1024, None, "lm", 1

    def reset_cache(self):
        pass

    def prefill_into(self, slot, tokens):
        return int(tokens[-1]) + 1

    def step(self, tokens, positions, active):
        return [int(t) + 1 for t in tokens]


def serve(backend, requests):
    reg = MetricsRegistry()

    async def main():
        engine = DecodeEngine(backend, metrics=reg)
        await engine.start()
        out = await asyncio.gather(*[engine.submit(prompt, n)
                                     for prompt, n in requests])
        await engine.stop()
        return out

    return reg, asyncio.run(main())


class TestTheEngineCountsTheBound:
    def test_attended_positions_are_slots_times_the_steps_bound(self):
        """A prompt of 510 and three more tokens: steps at positions 510,
        511 and 512 run the 512 rung, the step at 513 the 768 rung."""
        backend = RungBackend()
        reg, out = serve(backend, [(list(range(510)), 5)])
        assert len(out[0]) == 5
        assert backend.bounds == [512, 512, 512, 768]
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="attended") == 2 * (3 * 512 + 768)
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="live") == 511 + 512 + 513 + 514
        assert series(reg, "ai4e_decode_step_bound", model="lm") == (
            3 * 512 + 768, 4)
        # One bucket a rung: the histogram says how often each engaged.
        assert reg._metrics["ai4e_decode_step_bound"].buckets == (
            512, 768, 1024, float("inf"))

    def test_a_backend_that_says_what_it_read_is_counted_by_that(self):
        """``step_attended`` replaces ``slots x bound``: here a backend
        that reads its active slot alone, in blocks of 256."""
        class ReadBackend(RungBackend):
            def step(self, tokens, positions, active):
                self.step_attended = sum(
                    -(-p // 256) * 256 + 1
                    for p, live in zip(positions, active) if live)
                return super().step(tokens, positions, active)

        reg, out = serve(ReadBackend(), [(list(range(510)), 5)])
        assert len(out[0]) == 5
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="attended") == 3 * (512 + 1) + (768 + 1)
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="live") == 511 + 512 + 513 + 514

    def test_a_backend_that_reports_no_bound_counts_max_len(self):
        reg, out = serve(NoBoundBackend(), [([1, 2, 3], 4)])
        assert len(out[0]) == 4
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="attended") == 3 * 2 * 1024
        assert series(reg, "ai4e_decode_step_bound", model="lm") == (
            3 * 1024, 3)
        assert reg._metrics["ai4e_decode_step_bound"].buckets == (
            1024, float("inf"))

    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_the_runtime_reports_its_bound_to_the_engine(self, family):
        runtime = build_runtime(family)
        runtime.warm()
        reg, out = serve(runtime, [([1, 2, 3], 4), ([4, 5], 3)])
        assert [len(t) for t in out] == [4, 3]
        total, steps = series(reg, "ai4e_decode_step_bound", model="lm")
        assert steps == 3 and total == 3 * RUNGS[0]
        # Five slot-steps ({A,B}, {A,B}, {A}), each one block — this narrow
        # pool's whole 512 positions — and its own token; the idle third
        # slot reads nothing.
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="attended") == 5 * (MAX_LEN + 1)
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="live") == (3 + 1) + (2 + 1) + (4 + 1) + (3 + 1) + 6
        assert "ai4e_device_phase_seconds" not in reg._metrics
