"""Continuous-batching decode engine (runtime/decode.py + kvcache.py,
docs/streaming.md).

Three layers:

- **engine scheduling over a fake backend** (no JAX): iteration-level
  joins, backpressure, per-step deadline sweeps, cancellation,
  hot-reload re-prefill, slot conservation — plus THE acceptance
  property: a request arriving mid-decode of a long sequence receives
  its first token before that sequence finishes;
- **device path** (JAX): the KV-cache step function's correctness
  oracle — token-by-token decode must equal greedy re-prefill over the
  growing history — and the AOT-warm discipline (no serving-path
  compile);
- **metric identity**: constructing no engine registers no
  ``ai4e_decode_*`` series — the decode-engine-off worker's /metrics
  exposition is byte-identical (the PR 13 ladder discipline).
"""

import asyncio
import json
import time
from types import SimpleNamespace

import pytest

from ai4e_tpu.admission.deadline import DeadlineExceeded
from ai4e_tpu.taskstore import APITask
from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.runtime.decode import (DecodeEngine, DecodeSaturated,
                                     SlotError, SlotPool)


class FakeBackend:
    """Deterministic decode backend: token ids count up from the last
    prompt token; ``step_s`` simulates device time so latency ordering
    (TTFT vs remaining decode) is measurable."""

    def __init__(self, slots=2, max_len=64, eos_id=None, step_s=0.0,
                 name="lm"):
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.name = name
        self.step_s = step_s
        self.params_version = 1
        self.resets = 0
        self.prefills = []
        self.steps = 0

    def reset_cache(self):
        self.resets += 1

    def prefill_into(self, slot, tokens):
        if self.step_s:
            time.sleep(self.step_s)
        self.prefills.append((slot, tuple(tokens)))
        return int(tokens[-1]) + 1

    def step(self, tokens, positions, active):
        if self.step_s:
            time.sleep(self.step_s)
        self.steps += 1
        return [int(t) + 1 for t in tokens]


def run(coro):
    return asyncio.run(coro)


async def wait_until(cond, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while True:
        if cond():
            return
        assert time.perf_counter() < deadline, "condition not reached"
        await asyncio.sleep(0.001)


class TestSlotPool:
    def test_acquire_release_conservation(self):
        pool = SlotPool(3)
        a, b = pool.acquire(), pool.acquire()
        assert {a, b} == {0, 1}
        pool.release(a)
        assert pool.free_count == 2 and pool.busy_count == 1
        pool.check_conservation()

    def test_exhaustion_returns_none(self):
        pool = SlotPool(1)
        assert pool.acquire() == 0
        assert pool.acquire() is None

    def test_double_release_raises(self):
        pool = SlotPool(2)
        s = pool.acquire()
        pool.release(s)
        with pytest.raises(SlotError):
            pool.release(s)

    def test_foreign_release_raises(self):
        pool = SlotPool(2)
        with pytest.raises(SlotError):
            pool.release(1)


class TestEngineScheduling:
    def test_generates_and_streams_tokens(self):
        async def main():
            backend = FakeBackend(slots=2)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            await engine.start()
            chunks = []
            out = await engine.submit([5, 6], 4,
                                      on_token=lambda i, t: chunks.append(
                                          (i, t)))
            await engine.stop()
            return out, chunks, backend

        out, chunks, backend = run(main())
        # Prefill emits 7; each step increments the last token.
        assert out == [7, 8, 9, 10]
        assert chunks == [(0, 7), (1, 8), (2, 9), (3, 10)]
        assert backend.prefills[0] == (0, (5, 6))

    def test_eos_finishes_early_and_frees_slot(self):
        async def main():
            backend = FakeBackend(slots=1, eos_id=9)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            await engine.start()
            out = await engine.submit([6], 64)
            await engine.stop()
            return out

        assert run(main()) == [7, 8, 9]  # stops AT the eos token

    def test_backpressure_raises_decode_saturated(self):
        async def main():
            backend = FakeBackend(slots=1)
            engine = DecodeEngine(backend, max_pending=1,
                                  metrics=MetricsRegistry())
            # Engine not started: submissions stay queued.
            first = asyncio.ensure_future(engine.submit([1], 2))
            await asyncio.sleep(0)
            with pytest.raises(DecodeSaturated):
                await engine.submit([1], 2)
            first.cancel()
            return True

        assert run(main())

    def test_prompt_must_fit_kv_cache(self):
        async def main():
            engine = DecodeEngine(FakeBackend(slots=1, max_len=4),
                                  metrics=MetricsRegistry())
            with pytest.raises(ValueError):
                await engine.submit([1, 2, 3, 4], 2)

        run(main())

    def test_context_full_finishes_sequence(self):
        async def main():
            backend = FakeBackend(slots=1, max_len=5)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            await engine.start()
            # Prompt of 3 + KV length 5: prefill token (position 3) then
            # 2 steps fill the cache → 3 tokens, not the 64 requested.
            out = await engine.submit([1, 2, 3], 64)
            await engine.stop()
            return out

        assert len(run(main())) == 3

    def test_step_positions_stay_inside_the_cache(self):
        """The step program clamps a position past the last row onto it
        (``dynamic_update_slice``) and has no guard of its own: the
        engine retires a sequence before it gets there, and an inactive
        slot rides at position 0 of a row nobody reads."""
        seen = []

        class Recording(FakeBackend):
            def step(self, tokens, positions, active):
                seen.append((list(positions), list(active)))
                return super().step(tokens, positions, active)

        async def main():
            engine = DecodeEngine(Recording(slots=3, max_len=6),
                                  metrics=MetricsRegistry())
            await engine.start()
            out = await asyncio.gather(engine.submit([1, 2, 3, 4], 64),
                                       engine.submit([5], 3))
            await engine.stop()
            return out

        long, short = run(main())
        assert (len(long), len(short)) == (3, 3)
        assert seen
        for positions, active in seen:
            assert all(0 <= p < 6 for p in positions)
            assert all(p == 0 for p, a in zip(positions, active) if not a)
        assert max(max(p) for p, _ in seen) == 5   # the last row is used

    def test_late_joiner_streams_before_running_sequence_finishes(self):
        """THE acceptance property: a request arriving mid-decode of a
        long sequence gets its first chunk while that sequence is still
        decoding — its TTFT is smaller than the remaining decode time of
        the running sequence."""

        async def drive():
            backend = FakeBackend(slots=2, step_s=0.002)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            await engine.start()
            stamps = {}

            long_task = asyncio.ensure_future(engine.submit([1], 60))
            # Let the long sequence get well into its decode.
            await wait_until(lambda: backend.prefills and backend.steps >= 5)
            t_join = time.perf_counter()
            joiner = await engine.submit(
                [40], 3,
                on_token=lambda i, t: stamps.setdefault(
                    "first", time.perf_counter()))
            t_long_done_floor = time.perf_counter()
            await long_task
            t_long_done = max(time.perf_counter(), t_long_done_floor)
            await engine.stop()
            ttft = stamps["first"] - t_join
            remaining = t_long_done - t_join
            return ttft, remaining, len(joiner)

        ttft, remaining, n = run(drive())
        assert n == 3
        assert ttft < remaining, (
            f"continuous batching must stream the late joiner before the "
            f"running sequence finishes: TTFT {ttft * 1e3:.1f}ms vs "
            f"{remaining * 1e3:.1f}ms remaining")

    def test_deadline_sweep_frees_slot_mid_decode(self):
        async def main():
            # 5 ms per device call: the 10k-token budget cannot finish
            # inside the 50 ms deadline — the sweep MUST fire mid-decode.
            backend = FakeBackend(slots=1, step_s=0.005)
            reg = MetricsRegistry()
            engine = DecodeEngine(backend, metrics=reg)
            await engine.start()
            with pytest.raises(DeadlineExceeded):
                # Deadline passes mid-decode (the sequence wants 10k
                # tokens); the per-step sweep retires it and frees the
                # slot instead of completing late.
                await engine.submit([1], 10_000,
                                    deadline_at=time.time() + 0.05)
            # The step in flight still has the slot live: it comes back
            # when that step has been read, one tick on.
            await wait_until(lambda: engine.pool.free_count == 1)
            expired = reg.counter("ai4e_admission_expired_total")
            assert expired.value(hop="decode", priority="interactive") == 1
            await engine.stop()
            engine.pool.check_conservation()

        run(main())

    def test_cancelled_waiter_frees_slot(self):
        async def main():
            backend = FakeBackend(slots=1)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            await engine.start()
            fut = asyncio.ensure_future(engine.submit([1], 10_000))
            await wait_until(lambda: engine.active_count)
            fut.cancel()
            await wait_until(lambda: not engine.active_count)
            # Parked while the step in flight has it live; then freed.
            await wait_until(lambda: engine.pool.free_count == 1)
            await engine.stop()
            engine.pool.check_conservation()

        run(main())

    def test_hot_reload_invalidates_and_reprefills(self):
        async def main():
            backend = FakeBackend(slots=1, step_s=0.002)
            reg = MetricsRegistry()
            engine = DecodeEngine(backend, metrics=reg)
            await engine.start()
            fut = asyncio.ensure_future(engine.submit([1], 30))
            await wait_until(lambda: backend.steps >= 3)
            backend.params_version += 1  # hot reload lands
            out = await fut
            await engine.stop()
            return backend, reg, out

        backend, reg, out = run(main())
        assert len(out) == 30
        # The invalidation reset the pooled cache and re-prefilled the
        # active sequence from its prompt + generated history.
        assert backend.resets >= 1
        reprefill = [p for p in backend.prefills if len(p[1]) > 1]
        assert reprefill, "active sequence must re-prefill on reload"
        assert reg.counter("ai4e_decode_reprefills_total").value(
            model="lm") >= 1
        # The re-prefilled history starts with the original prompt.
        assert reprefill[0][1][0] == 1

    def test_metrics_registered_only_with_engine(self):
        reg = MetricsRegistry()
        assert not any(n.startswith("ai4e_decode_") for n in reg._metrics)
        DecodeEngine(FakeBackend(), metrics=reg)
        decode_metrics = {n for n in reg._metrics
                          if n.startswith("ai4e_decode_")}
        assert decode_metrics == {
            "ai4e_decode_ttft_seconds", "ai4e_decode_intertoken_seconds",
            "ai4e_decode_step_seconds", "ai4e_decode_slot_occupancy",
            "ai4e_decode_pending", "ai4e_decode_tokens_total",
            "ai4e_decode_sequences_total", "ai4e_decode_reprefills_total",
            "ai4e_decode_tick_seconds", "ai4e_decode_queue_wait_seconds",
            "ai4e_decode_step_active_slots", "ai4e_decode_step_bound",
            "ai4e_decode_kv_positions_total",
            "ai4e_decode_cache_bytes_total",
            "ai4e_decode_state_bytes_total", "ai4e_decode_tick_joins",
            "ai4e_decode_step_launches_total", "ai4e_decode_joins_total",
            "ai4e_decode_discarded_slot_steps_total"}

    @pytest.mark.parametrize("declared", [
        {"window_fill": ("Share of a layer's window that is live",
                         (0.25, 0.5, 1.0, float("inf")))},
        {}])
    def test_a_backend_declares_its_own_step_report(self, declared):
        """What the backend's model reports of its step is the backend's to
        name: the engine registers ``ai4e_decode_<name>`` with the declared
        help and buckets and observes it each step; a backend that
        declares nothing registers nothing beyond the engine's own."""

        class Reporting(FakeBackend):
            step_report_series = declared

            def step(self, tokens, positions, active):
                self.step_report = dict.fromkeys(declared, 0.5)
                return super().step(tokens, positions, active)

        async def main():
            reg = MetricsRegistry()
            engine = DecodeEngine(Reporting(slots=1), metrics=reg)
            await engine.start()
            await engine.submit([1], 4)
            await engine.stop()
            return reg

        reg, plain = run(main()), MetricsRegistry()
        DecodeEngine(FakeBackend(), metrics=plain)
        assert (set(reg._metrics) - set(plain._metrics)
                == {f"ai4e_decode_{name}" for name in declared})
        for name, (help_text, buckets) in declared.items():
            metric = reg._metrics[f"ai4e_decode_{name}"]
            assert (metric.help, metric.buckets) == (help_text, buckets)
            (_, _, _, value), = metric.collect()
            # 4 tokens: one from the prefill, three steps.
            assert (value["count"], value["sum"]) == (3, 1.5)

    def test_default_worker_has_no_decode_metrics(self):
        """Decode-engine-off identity (acceptance): nothing in the
        default worker construction path registers a decode series —
        same discipline as the ladder-off exposition assertions."""
        from ai4e_tpu.runtime.batcher import MicroBatcher
        from types import SimpleNamespace
        reg = MetricsRegistry()
        MicroBatcher(SimpleNamespace(models={}), metrics=reg)
        text = reg.render_prometheus()
        assert "ai4e_decode_" not in text


# -- device path (JAX) -------------------------------------------------------


# The LM families of ``runtime/families.py`` at a tiny size: every case of
# the device path below runs over each.
_LM_FAMILIES = {
    "seqformer-lm": {},
    "olmoe": dict(experts=8, experts_per_token=2, expert_dim=32),
}


@pytest.fixture(scope="module", params=list(_LM_FAMILIES))
def lm_runtime(request):
    from ai4e_tpu.runtime.kvcache import (PagedDecodeRuntime,
                                          build_lm_servable)
    servable = build_lm_servable(family=request.param, name="lm",
                                 vocab_size=64, max_len=24, dim=32, depth=2,
                                 heads=4, **_LM_FAMILIES[request.param])
    runtime = PagedDecodeRuntime(servable, slots=3, prompt_buckets=(4, 8))
    runtime.warm()
    return runtime


class TestPagedDecodeRuntime:
    def test_prompt_buckets_cover_max_len(self, lm_runtime):
        assert lm_runtime.prompt_buckets == (4, 8, 24)
        assert lm_runtime.bucket_for(3) == 4
        assert lm_runtime.bucket_for(9) == 24

    def test_decode_matches_greedy_reprefill_oracle(self, lm_runtime):
        """The KV-cache step path must produce exactly the tokens greedy
        re-prefill over the growing history produces — the correctness
        oracle for cache insert/step index arithmetic."""
        from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime
        # A prompt on which no two logits of the bfloat16 family lie within
        # its rounding of each other: there the two paths may pick either.
        prompt = [2, 4, 6]
        tok = lm_runtime.prefill_into(1, prompt)
        got = [tok]
        position = len(prompt)
        for _ in range(5):
            out = lm_runtime.step(
                [0, got[-1], 0], [0, position, 0], [False, True, False])
            got.append(out[1])
            position += 1

        oracle_rt = PagedDecodeRuntime(lm_runtime.servable, slots=1,
                                       prompt_buckets=(24,))
        history = list(prompt)
        oracle = []
        for _ in range(6):
            t = oracle_rt.prefill_into(0, history)
            oracle.append(t)
            history.append(t)
        assert got == oracle

    def test_reset_cache_frees_the_old_pool_before_it_builds_the_new(
            self, lm_runtime, monkeypatch):
        """A third pool tensor never exists: on the chip it was the
        allocator's peak (warm-up ends in a reset)."""
        import jax.numpy as jnp
        held = []
        real_zeros = jnp.zeros

        def zeros(*args, **kwargs):
            held.append(lm_runtime._rows is not None)
            return real_zeros(*args, **kwargs)

        lm_runtime._ensure()
        monkeypatch.setattr(jnp, "zeros", zeros)
        lm_runtime.reset_cache()
        assert held == [False, False]
        assert lm_runtime._rows[0].shape == lm_runtime._rows[1].shape

    def test_reload_params_bumps_version_and_checks_tree(self, lm_runtime):
        import jax
        before = lm_runtime.params_version
        new = jax.tree.map(lambda a: a, lm_runtime.servable.params)
        assert lm_runtime.reload_params(new) == before + 1
        with pytest.raises(ValueError):
            lm_runtime.reload_params({"params": {}})

    def test_engine_end_to_end_on_device(self, lm_runtime):
        async def main():
            engine = DecodeEngine(lm_runtime, metrics=MetricsRegistry())
            await engine.start()
            a, b = await asyncio.gather(engine.submit([1, 2, 3], 5),
                                        engine.submit([4, 5], 4))
            await engine.stop()
            engine.pool.check_conservation()
            return a, b

        a, b = run(main())
        assert len(a) == 5 and len(b) == 4
        assert all(0 <= t < 64 for t in a + b)


# The step's contract (PR 25): one K/V row per slot per layer, written in
# place; nothing else of the pool is touched.

_POOL = dict(depth=2, slots=5, heads=4, max_len=32, head_dim=8)
# (layers, slots, max_len, heads x hd): a position is one row
_POOL_SHAPE = (_POOL["depth"], _POOL["slots"], _POOL["max_len"],
               _POOL["heads"] * _POOL["head_dim"])


@pytest.fixture(scope="module", params=list(_LM_FAMILIES))
def tiny_lm(request):
    """The family's two programs as the runtime builds them, its pool's
    dtype (float32 for ``seqformer-lm``, bfloat16 for ``olmoe``) and the
    tolerance of one K/V row computed by prefill against the step's."""
    import jax
    import numpy as np
    from ai4e_tpu.runtime.kvcache import build_lm_servable
    servable = build_lm_servable(
        family=request.param, vocab_size=64, max_len=_POOL["max_len"],
        dim=_POOL["heads"] * _POOL["head_dim"], depth=_POOL["depth"],
        heads=_POOL["heads"], **_LM_FAMILIES[request.param])
    model, params = servable.model, servable.params
    rows, state = model.cache_spec()[:2]
    dtype = rows[0].dtype
    assert [(r.layers, r.width) for r in rows] == 2 * [
        (_POOL["depth"], _POOL["heads"] * _POOL["head_dim"])]
    assert state == ()   # these families keep K/V only

    def step(params, tokens, k, v, position):
        return model.apply(params, tokens, k, v, {}, position,
                           method="decode_step")[:3]

    def prefill(tokens, length):
        return model.apply(params, tokens, length, method="prefill")

    # In bfloat16 the two paths round the stream differently (one query
    # against the cache here, the whole prompt at once there), and the
    # second layer's row inherits it: rows of size ~2 agree to ~0.03.
    tol = 1e-5 if np.dtype(dtype).itemsize == 4 else 2.0 ** -4
    return SimpleNamespace(params=params, step=jax.jit(step),
                           prefill=jax.jit(prefill), dtype=np.dtype(dtype),
                           tol=tol)


def _garbage_pool(rng, dtype):
    # Around 100, where no K or V value lies: in bfloat16 a garbage value
    # near 0 equals the row written over it once in a few hundred elements,
    # and "every written element changed" would fail by chance.
    return ((100 + rng.standard_normal(_POOL_SHAPE)).astype(dtype),
            (100 + rng.standard_normal(_POOL_SHAPE)).astype(dtype))


def _bits(a):
    import numpy as np
    a = np.ascontiguousarray(np.asarray(a))
    return a.view({4: np.uint32, 2: np.uint16}[a.dtype.itemsize])


def _step_over_histories(lm, positions, seed=0):
    """Slot i holds a random history of ``positions[i]`` tokens (its K/V
    from the prefill program) in a pool of garbage, then every slot takes
    one step. Returns what the contract is stated over."""
    import numpy as np
    rng = np.random.default_rng(seed)
    k0, v0 = _garbage_pool(rng, lm.dtype)
    length = _POOL["max_len"]
    tokens, want_tok, want_k, want_v = [], [], [], []
    for slot, p in enumerate(positions):
        history = rng.integers(1, 64, size=p + 1)
        padded = np.zeros((1, length), np.int32)
        padded[0, :p + 1] = history
        tok, k, v, _ = lm.prefill(padded, np.asarray([p + 1], np.int32))
        k, v = np.asarray(k), np.asarray(v)   # (depth, 1, L, H x hd)
        k0[:, slot, :p] = k[:, 0, :p]
        v0[:, slot, :p] = v[:, 0, :p]
        tokens.append(int(history[-1]))
        want_tok.append(int(tok[0]))
        want_k.append(k[:, 0, p])
        want_v.append(v[:, 0, p])
    out, k1, v1 = lm.step(lm.params, np.asarray(tokens, np.int32), k0, v0,
                          np.asarray(positions, np.int32))
    return SimpleNamespace(
        # (a family may append a report to its ids: ``kvcache.step``)
        tokens=[int(t) for t in np.asarray(out)[:len(positions)]],
        want_tokens=want_tok,
        before=(k0, v0), after=(np.asarray(k1), np.asarray(v1)),
        want_rows=(want_k, want_v))


class TestStepWritesOneRowInPlace:
    @pytest.mark.parametrize("positions", [
        (0, 3, 7, 16, 31),       # every slot somewhere else, first and last row
        (5, 5, 5, 5, 5),         # every slot at the same row
        (31, 31, 0, 0, 31),      # the last row, ``max_len - 1``
        (1, 0, 30, 2, 0),
    ], ids=["spread", "same", "last-row", "mixed"])
    def test_exactly_one_row_per_slot_changes(self, tiny_lm, positions):
        import numpy as np
        got = _step_over_histories(tiny_lm, positions)
        # The same token greedy re-prefill over the history gives.
        assert got.tokens == got.want_tokens
        for before, after, rows in zip(got.before, got.after,
                                       got.want_rows):
            written = np.zeros(before.shape, bool)
            for slot, p in enumerate(positions):
                written[:, slot, p] = True
                np.testing.assert_allclose(
                    after[:, slot, p].astype(np.float32),
                    rows[slot].astype(np.float32),
                    rtol=tiny_lm.tol, atol=tiny_lm.tol)
            changed = _bits(before) != _bits(after)
            # Bit for bit: garbage the blend would have rounded (or
            # poisoned, were it not finite) stays what it was.
            assert not (changed & ~written).any()
            assert changed[written].all()
            assert int(changed.sum()) == (
                _POOL["depth"] * _POOL["slots"] * _POOL["heads"]
                * _POOL["head_dim"])

    def test_position_past_the_cache_lands_on_the_last_row(self, tiny_lm):
        """What ``dynamic_update_slice`` does with a start it cannot
        honour: it clamps. No caller sends one (the engine test above);
        the program carries no guard."""
        import numpy as np
        rng = np.random.default_rng(1)
        k0, v0 = _garbage_pool(rng, tiny_lm.dtype)
        length = _POOL["max_len"]
        positions = np.asarray([length, length + 7, 2, 2, 2], np.int32)
        _, k1, v1 = tiny_lm.step(tiny_lm.params,
                                 np.ones(_POOL["slots"], np.int32), k0, v0,
                                 positions)
        for before, after in ((k0, k1), (v0, v1)):
            changed = _bits(before) != _bits(after)
            rows = {(int(s), int(p))
                    for _, s, p, _ in zip(*np.nonzero(changed))}
            assert rows == {(0, length - 1), (1, length - 1),
                            (2, 2), (3, 2), (4, 2)}

    def test_inactive_slots_disturb_no_active_slot(self, tiny_lm):
        """Inactive slots ride at token 0, position 0 over whatever their
        rows hold — here NaN and inf, which the one-hot blend would have
        spread over the slot's whole row."""
        import numpy as np
        rng = np.random.default_rng(2)
        k0, v0 = _garbage_pool(rng, tiny_lm.dtype)
        tokens = np.asarray([9, 0, 17, 0, 0], np.int32)
        positions = np.asarray([4, 0, 11, 0, 0], np.int32)
        inactive = [1, 3, 4]
        clean = tiny_lm.step(tiny_lm.params, tokens, k0, v0, positions)
        k_bad, v_bad = k0.copy(), v0.copy()
        k_bad[:, inactive] = np.nan
        v_bad[:, inactive] = np.inf
        dirty = tiny_lm.step(tiny_lm.params, tokens, k_bad, v_bad, positions)
        active = [0, 2]
        assert (np.asarray(clean[0])[:_POOL["slots"]][active]
                == np.asarray(dirty[0])[:_POOL["slots"]][active]).all()
        for a, b, bad in ((clean[1], dirty[1], k_bad),
                          (clean[2], dirty[2], v_bad)):
            a, b = np.asarray(a), np.asarray(b)
            assert (_bits(a[:, active]) == _bits(b[:, active])).all()
            # and the inactive slots' garbage is still theirs, but for row 0
            assert (_bits(b[:, inactive, 1:])
                    == _bits(bad[:, inactive, 1:])).all()

    def test_lowered_step_makes_the_pool_only_by_row_writes(self, tiny_lm):
        """Every operation of the lowered step whose result has the
        pool's shape is a ``dynamic_update_slice``: no blend, no stack of
        rewritten layers, no scatter, no copy to write into. The step's
        own function: the attention kernel is one function beside it,
        called a layer with the pool whole (here the interpreter's loop,
        which carries its operands; on the chip a Mosaic call —
        ``tests/test_tpu_aot_compile.py``)."""
        import re
        import jax
        import jax.numpy as jnp
        pool = jax.ShapeDtypeStruct(_POOL_SHAPE, tiny_lm.dtype)
        ints = jax.ShapeDtypeStruct((_POOL["slots"],), jnp.int32)
        module = tiny_lm.step.lower(
            tiny_lm.params, ints, pool, pool, ints).as_text()
        text = re.search(r"func\.func public @main.*?\n  }\n", module,
                         re.S).group(0)
        kernel = re.findall(r"func\.func private @_pooled\(", module)
        assert len(kernel) == 1, "one traced kernel for every layer"
        assert text.count("call @_pooled(") == _POOL["depth"]
        pool_type = ("tensor<" + "x".join(map(str, _POOL_SHAPE)) + "x"
                     + {4: "f32", 2: "bf16"}[tiny_lm.dtype.itemsize] + ">")
        makers = []
        for line in text.splitlines():
            m = re.match(r"\s*%\S+ = \"?([\w.]+)\"?", line)
            if m is None:
                continue
            results = line.rsplit("->", 1)[-1] if "->" in line else (
                line.rsplit(":", 1)[-1])
            if pool_type in results:
                makers.append(m.group(1))
        assert makers == (["stablehlo.dynamic_update_slice"]
                          * (2 * _POOL["slots"])), makers


# -- worker serve_stream + SSE chunk flow ------------------------------------


class TestServeStream:
    def _worker(self, hub=None, engine=None):
        from ai4e_tpu.runtime.worker import InferenceWorker
        from ai4e_tpu.service.task_manager import LocalTaskManager
        from ai4e_tpu.taskstore import InMemoryTaskStore
        from types import SimpleNamespace
        store = InMemoryTaskStore()
        runtime = SimpleNamespace(models={})
        batcher = SimpleNamespace(pending_count=0, max_pending=8)
        worker = InferenceWorker("svc", runtime, batcher,
                                 task_manager=LocalTaskManager(store),
                                 metrics=MetricsRegistry(), store=store)
        if engine is not None:
            worker.serve_stream(engine, event_hub=hub)
        return worker, store

    def test_stream_endpoint_publishes_chunks_and_result(self):
        from ai4e_tpu.pipeline.events import TaskEventHub

        async def main():
            backend = FakeBackend(slots=2, name="lm")
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            hub = TaskEventHub(metrics=MetricsRegistry())
            worker, store = self._worker(hub=hub, engine=engine)
            await engine.start()
            store.upsert(APITask(task_id="t-1",
                                 endpoint="/lm-stream-async",
                                 body=b"", publish=False))
            handler = worker.service.endpoints["/lm-stream-async"].func
            body = json.dumps({"prompt": [5], "max_new_tokens": 3}).encode()
            await handler(taskId="t-1", body=body,
                          content_type="application/json")
            await engine.stop()
            return hub, store

        hub, store = run(main())
        events = hub.replay("t-1")
        chunks = [e for e in events if e["event"] == "chunk"]
        assert [c["data"]["data"]["token"] for c in chunks] == [6, 7, 8]
        assert all(c["data"]["stage"] == "lm" for c in chunks)
        task = store.get("t-1")
        assert task.canonical_status == "completed"
        result, _ = store.get_result("t-1")
        assert json.loads(result) == {"tokens": [6, 7, 8], "count": 3}

    def test_bad_input_fails_task_not_engine(self):
        async def main():
            backend = FakeBackend(slots=1)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            worker, store = self._worker(engine=engine)
            store.upsert(APITask(task_id="t-bad",
                                 endpoint="/lm-stream-async",
                                 body=b"", publish=False))
            handler = worker.service.endpoints["/lm-stream-async"].func
            await handler(taskId="t-bad", body=b'{"prompt": "nope"}',
                          content_type="application/json")
            return store

        store = run(main())
        assert store.get("t-bad").canonical_status == "failed"

    def test_saturated_engine_answers_503_at_admission(self):
        async def main():
            backend = FakeBackend(slots=1)
            engine = DecodeEngine(backend, max_pending=0,
                                  metrics=MetricsRegistry())
            worker, _ = self._worker(engine=engine)
            check = worker.service.endpoints[
                "/lm-stream-async"].admission_check
            return check()

        status, _, headers = run(main())
        assert status == 503
        # Every refusal names its retry horizon (docs/analysis.md AIL015).
        assert headers["Retry-After"] == "1"


# -- CLI wiring (AI4E_RUNTIME_DECODE_*) --------------------------------------


class TestCliDecodeWiring:
    MODELS = {
        "service_name": "w", "prefix": "v1/lm",
        "models": [
            {"family": "echo", "name": "echo", "size": 4, "buckets": [2]},
            {"family": "seqformer-lm", "name": "lm", "vocab_size": 32,
             "max_len": 32, "dim": 16, "depth": 1, "heads": 2,
             "eos_id": 2}]}

    def test_decode_enable_builds_engine_and_stream_endpoint(self):
        from ai4e_tpu.cli import build_worker
        from ai4e_tpu.config import FrameworkConfig
        config = FrameworkConfig()
        config.runtime.decode_enable = True
        config.runtime.kv_slots = 2
        config.runtime.decode_prompt_buckets = (4,)
        worker, _batcher, _tm = build_worker(config, dict(self.MODELS))
        assert len(worker.decode_engines) == 1
        engine = worker.decode_engines[0]
        assert engine.backend.slots == 2
        # Spec max_len wins over the kv_max_len default; the prompt
        # ladder is the knob's, with the covering top appended.
        assert engine.backend.max_len == 32
        assert engine.backend.prompt_buckets == (4, 32)
        assert engine.backend.eos_id == 2
        # The LM is NOT a batch servable…
        assert "lm" not in worker.runtime.models
        # …but IS a served streaming endpoint.
        assert "/lm-stream-async" in worker.service.endpoints
        assert worker._served["lm"]["stream_async"] == \
            "/v1/lm/lm-stream-async"

    def test_decode_off_skips_lm_specs(self):
        """Default knobs: no engine, no stream route, no LM in the batch
        registry — the decode-off worker is the pre-decode worker. (The
        /metrics byte-identity half lives in
        ``TestEngineScheduling.test_default_worker_has_no_decode_metrics``
        on an isolated registry — the cli path shares the process-default
        registry, which an earlier decode-on test legitimately used.)"""
        from ai4e_tpu.cli import build_worker
        from ai4e_tpu.config import FrameworkConfig
        worker, _batcher, _tm = build_worker(FrameworkConfig(),
                                             dict(self.MODELS))
        assert worker.decode_engines == []
        assert "/lm-stream-async" not in worker.service.endpoints
        assert "lm" not in worker.runtime.models


class TestLMHotReloadEndpoint:
    def test_reload_endpoint_reaches_decode_backend(self, tmp_path):
        """POST {prefix}/models/{lm}/reload must resolve streaming LMs
        (they never enter runtime.models) and bump params_version — the
        engine's KV-cache invalidation trigger."""
        from aiohttp.test_utils import TestClient, TestServer

        from ai4e_tpu.checkpoint import save_params
        from ai4e_tpu.runtime.kvcache import (PagedDecodeRuntime,
                                              build_lm_servable)

        async def main():
            lm = build_lm_servable(name="lm", vocab_size=16, max_len=16,
                                   dim=16, depth=1, heads=2)
            backend = PagedDecodeRuntime(lm, slots=1, prompt_buckets=(4,))
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            worker, _store = TestServeStream()._worker(engine=engine)
            ckpt = str(tmp_path / "lm-ckpt")
            save_params(ckpt, lm.params)
            client = TestClient(TestServer(worker.service.app))
            await client.start_server()
            try:
                resp = await client.post("/v1/models/lm/reload",
                                         json={"checkpoint": ckpt})
                body = await resp.json()
                missing = await client.post("/v1/models/nope/reload",
                                            json={"checkpoint": ckpt})
                return resp.status, body, missing.status, backend
            finally:
                await client.close()

        status, body, missing, backend = run(main())
        assert status == 200, body
        assert body["params_version"] == 2
        assert backend.params_version == 2
        assert body["checkpoint"].endswith("lm-ckpt")
        assert missing == 404

    def test_oversized_prompt_fails_task_as_bad_input(self):
        async def main():
            backend = FakeBackend(slots=1, max_len=4)
            engine = DecodeEngine(backend, metrics=MetricsRegistry())
            worker, store = TestServeStream()._worker(engine=engine)
            store.upsert(APITask(task_id="t-big",
                                 endpoint="/lm-stream-async",
                                 body=b"", publish=False))
            handler = worker.service.endpoints["/lm-stream-async"].func
            await handler(
                taskId="t-big",
                body=json.dumps({"prompt": [1, 2, 3, 4, 5],
                                 "max_new_tokens": 2}).encode(),
                content_type="application/json")
            return store.get("t-big")

        task = run(main())
        assert task.canonical_status == "failed"
        assert "bad input" in task.status
