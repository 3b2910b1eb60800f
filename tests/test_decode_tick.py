"""The decode tick seen from inside (runtime/decode.py, runtime/kvcache.py,
docs/observability.md "Decode-tick decomposition").

- the eight phases of ``ai4e_decode_tick_seconds`` partition the interval
  from one step's submit to the next, with and without the backend's
  ``phase_hook``;
- queue wait, active slots per step and live/attended K/V positions on a
  scripted run;
- a generate request's hop-ledger timeline and its tick range;
- compiles of the three decode programs read off the jit dispatch caches;
- named scopes are metadata: same tokens with and without them;
- ``device_trace`` annotations land on the profiler's host plane.
"""

import asyncio
import contextlib
import glob
import os
import re
import time

import pytest

from ai4e_tpu.metrics.registry import MetricsRegistry
from ai4e_tpu.observability.ledger import HopLedger
from ai4e_tpu.observability.tracing import device_trace
from ai4e_tpu.runtime.decode import TICK_PHASES, DecodeEngine


class SleepBackend:
    """Counts up from the last prompt token; sleeps where a device would
    run. No ``phase_hook``: the engine books the in-thread time of a step
    as ``device_wait``."""

    slots, max_len, eos_id, name, params_version = 2, 64, None, "lm", 1

    def __init__(self, step_s=0.004, prefill_s=0.0):
        self.step_s, self.prefill_s = step_s, prefill_s

    def reset_cache(self):
        pass

    def prefill_into(self, slot, tokens):
        time.sleep(self.prefill_s)
        return int(tokens[-1]) + 1

    def step(self, tokens, positions, active):
        time.sleep(self.step_s)
        return [int(t) + 1 for t in tokens]


class HookBackend(SleepBackend):
    """Reports its own split as ``PagedDecodeRuntime`` does: ``launch_s``
    of host work, then ``step_s`` blocked on the device."""

    launch_s = 0.003

    def __init__(self, **kw):
        super().__init__(**kw)
        self.phase_hook = None

    def step(self, tokens, positions, active):
        time.sleep(self.launch_s)
        t0 = time.perf_counter()
        time.sleep(self.step_s)
        self.phase_hook("device_wait", time.perf_counter() - t0)
        return [int(t) + 1 for t in tokens]


class AsyncBackend(SleepBackend):
    """The race tests' shape: awaited inline, no device thread."""

    async def prefill_into(self, slot, tokens):
        return int(tokens[-1]) + 1

    async def step(self, tokens, positions, active):
        await asyncio.sleep(self.step_s)
        return [int(t) + 1 for t in tokens]


def series(reg, name, **labels):
    """``(sum, count)`` of one histogram series, ``value`` of a counter."""
    want = set(labels.items())
    for kind, _, got, value in reg._metrics[name].collect():
        if want <= set(got.items()):
            return ((value["sum"], value["count"]) if kind == "histogram"
                    else value)
    return (0.0, 0)


def serve(backend, requests, reg=None, record_submits=None):
    """Run ``requests`` (``(prompt, max_new, ledger)``) to completion on a
    fresh engine; returns the registry."""
    reg = reg or MetricsRegistry()

    async def main():
        engine = DecodeEngine(backend, metrics=reg)
        if record_submits is not None:
            close = engine._close_tick

            def spy(submit, entered):
                record_submits.append((submit, engine._last_submit))
                close(submit, entered)
            engine._close_tick = spy
        await engine.start()
        out = await asyncio.gather(*[
            engine.submit(prompt, n, ledger=ledger)
            for prompt, n, ledger in requests])
        await engine.stop()
        engine.pool.check_conservation()
        return out

    return reg, asyncio.run(main())


class TestTickPhases:
    @pytest.mark.parametrize("backend_cls", [SleepBackend, HookBackend,
                                             AsyncBackend])
    def test_phases_partition_the_submit_to_submit_interval(
            self, backend_cls):
        backend = backend_cls(step_s=0.004, prefill_s=0.002)
        submits = []
        reg, out = serve(backend, [([1, 2], 12, None), ([5], 9, None)],
                         record_submits=submits)
        assert [len(t) for t in out] == [12, 9]
        # Every interval whose previous submit was known is observed once.
        intervals = [s - prev for s, prev in submits if prev is not None]
        assert len(intervals) == len(submits) - 1 == 10
        sums = {}
        for phase in TICK_PHASES:
            total, count = series(reg, "ai4e_decode_tick_seconds",
                                  phase=phase, model="lm")
            assert count == len(intervals), phase
            assert total >= 0.0
            sums[phase] = total
        assert abs(sum(sums.values()) - sum(intervals)) < 1e-3
        wait = sums["device_wait"] / len(intervals)
        assert 0.004 <= wait < 0.05
        if backend_cls is HookBackend:
            launch = sums["dispatch"] / len(intervals)
            assert 0.003 <= launch < 0.05
        else:   # no hook: nothing in the thread is told apart
            assert sums["dispatch"] == 0.0
        if backend_cls is AsyncBackend:   # no thread, so no hops
            assert sums["handoff"] == sums["return"] == 0.0

    def test_a_join_is_booked_as_admit_and_an_idle_engine_as_nothing(self):
        """The second request arrives while the first decodes: its prefill
        stalls that tick and shows as ``admit``. The idle wait between the
        two bursts is no tick at all."""
        backend = SleepBackend(step_s=0.002, prefill_s=0.02)
        reg = MetricsRegistry()

        async def main():
            engine = DecodeEngine(backend, metrics=reg)
            await engine.start()
            first = asyncio.ensure_future(engine.submit([1], 30))
            await asyncio.sleep(0.03)
            await engine.submit([2], 2)
            await first
            await asyncio.sleep(0.5)        # idle
            await engine.submit([3], 3)
            await engine.stop()

        asyncio.run(main())
        admit, n = series(reg, "ai4e_decode_tick_seconds", phase="admit",
                          model="lm")
        assert 0.02 <= admit < 0.2     # the one join among running steps
        total = sum(series(reg, "ai4e_decode_tick_seconds", phase=p,
                           model="lm")[0] for p in TICK_PHASES)
        # 29 + 1 + 2 steps at 2 ms and one 20 ms join; never the 500 ms idle.
        assert total < 0.4
        # A burst's first step closes no interval.
        steps = series(reg, "ai4e_decode_step_seconds", phase="decode",
                       model="lm")[1]
        assert n == steps - 2


class TestStepCounters:
    def test_scripted_run_arithmetic(self):
        """Two slots. A (prompt 3, 4 new) and B (prompt 2, 3 new) join in
        the first tick; C waits for B's slot, and finishes in its prefill.
        Steps: {A,B} at positions (3,2), {A,B} at (4,3), {A} at 5."""
        backend = SleepBackend(step_s=0.01)
        reg, out = serve(backend, [([1, 2, 3], 4, None), ([7, 8], 3, None),
                                   ([9], 1, None)])
        assert [len(t) for t in out] == [4, 3, 1]
        total, steps = series(reg, "ai4e_decode_step_active_slots",
                              model="lm")
        assert (total, steps) == (5.0, 3)
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="live") == (3 + 1) + (2 + 1) + (4 + 1) + (3 + 1) + 6
        assert series(reg, "ai4e_decode_kv_positions_total", model="lm",
                      kind="attended") == 3 * 2 * 64
        waited, joins = series(reg, "ai4e_decode_queue_wait_seconds",
                               model="lm")
        assert joins == 3
        assert 0.02 <= waited < 1.0     # C sat out two 10 ms steps

    def test_active_slots_buckets_resolve_every_count(self):
        reg = MetricsRegistry()
        DecodeEngine(SleepBackend(), metrics=reg)
        assert reg._metrics["ai4e_decode_step_active_slots"].buckets == (
            1, 2, float("inf"))
        assert reg._metrics["ai4e_decode_tick_seconds"].buckets[0] < 1e-4


class TestRequestTimeline:
    def test_ledger_holds_the_requests_own_timeline(self):
        ledger, other = HopLedger(), HopLedger()
        serve(SleepBackend(step_s=0.001),
              [([1, 2, 3], 6, ledger), ([4], 3, other)])
        for buf, n in ((ledger, 6), (other, 3)):
            events = buf.events()
            assert [ev["e"] for ev in events] == [
                "queued", "slot", "prefill", "chunk", "decoded"]
            assert len(events) <= 6
            assert all(ev["h"] == "decode" for ev in events)
            slot = re.fullmatch(r"slot (\d+) tick (\d+)", events[1]["r"])
            done = re.fullmatch(r"(\d+) tokens ticks (\d+)\.\.(\d+)",
                                events[4]["r"])
            assert slot and done
            tokens, first, last = map(int, done.groups())
            assert tokens == n and first == int(slot.group(2))
            # One token from the prefill, one from each tick's step.
            assert tokens == last - first + 2
            assert events[1]["ms"] >= 0 and events[2]["ms"] >= 0
            assert events[2]["r"] == f"{3 if n == 6 else 1} tokens"
            times = [ev["t"] for ev in events]
            assert times == sorted(times)

    def test_no_ledger_no_stamps_and_unfinished_has_no_decoded(self):
        ledger = HopLedger()

        async def main():
            engine = DecodeEngine(SleepBackend(step_s=0.002),
                                  metrics=MetricsRegistry())
            await engine.start()
            fut = asyncio.ensure_future(
                engine.submit([1], 1000, ledger=ledger))
            await asyncio.sleep(0.03)
            fut.cancel()
            await asyncio.sleep(0.01)
            await engine.stop()

        asyncio.run(main())
        assert [ev["e"] for ev in ledger.events()] == [
            "queued", "slot", "prefill", "chunk"]


# -- device path (JAX on the CPU) ---------------------------------------------


def tiny_runtime(**kw):
    from ai4e_tpu.runtime.kvcache import (PagedDecodeRuntime,
                                          build_lm_servable)
    servable = build_lm_servable(name="lm", vocab_size=64, max_len=24,
                                 dim=32, depth=2, heads=4)
    return PagedDecodeRuntime(servable, slots=3, prompt_buckets=(4, 8), **kw)


def decode_tokens(runtime, prompt=(3, 7, 11), steps=5):
    got = [runtime.prefill_into(1, list(prompt))]
    for position in range(len(prompt), len(prompt) + steps):
        got.append(runtime.step([0, got[-1], 0], [0, position, 0],
                                [False, True, False])[1])
    return got


class TestDecodeProgramCompiles:
    @pytest.mark.parametrize("state,expected", [
        ("warm", 0), ("cold", 3), ("step program dropped", 1)])
    def test_compile_phase_counts_grown_dispatch_caches(self, state,
                                                        expected):
        runtime = tiny_runtime()
        if state != "cold":
            runtime.warm()
        if state == "step program dropped":
            runtime._executables = {
                key: call for key, call in runtime._executables.items()
                if key[0] != "step"}
        ledger = HopLedger()
        reg, out = serve(runtime, [([1, 2, 3], 4, ledger)])
        assert len(out[0]) == 4
        if expected == 0:
            # A warm worker's exposition does not even hold the family.
            assert "ai4e_device_phase_seconds" not in reg._metrics
        else:
            seconds, count = series(reg, "ai4e_device_phase_seconds",
                                    phase="compile", model="lm")
            assert count == expected and seconds > 0
        stamps = [ev for ev in ledger.events() if ev["e"] == "compile"]
        # prefill + insert run for this request; the step serves the pool.
        assert len(stamps) == {"warm": 0, "cold": 2,
                               "step program dropped": 0}[state]
        assert all(ev["h"] == "device" and ev["ms"] > 0 for ev in stamps)

    def test_runtime_reports_the_wait_and_the_engine_books_the_rest(self):
        runtime = tiny_runtime()
        runtime.warm()
        reg, _ = serve(runtime, [([1, 2, 3], 6, None)])
        wait, n = series(reg, "ai4e_decode_tick_seconds",
                         phase="device_wait", model="lm")
        launch, _ = series(reg, "ai4e_decode_tick_seconds",
                           phase="dispatch", model="lm")
        assert n == 4 and wait > 0 and launch > 0


class TestNamedScopes:
    def test_scopes_are_metadata_only(self, monkeypatch):
        """Same weights, same prompt: the step path's tokens with the
        scopes and with ``jax.named_scope`` made a no-op are identical, and
        the scopes are in what the compiler is given."""
        import jax
        import numpy as np

        def lowered(runtime, debug_info):
            return runtime._programs["step"].lower(
                runtime.servable.params, np.zeros((3, 3), np.int32),
                np.zeros(3, np.int32), runtime._rows,
                runtime._state, runtime.max_len).as_text(
                    debug_info=debug_info)

        scoped = tiny_runtime()
        with_scopes = decode_tokens(scoped)
        text = lowered(scoped, True)
        for scope in ("cache_update", "attention", "mlp", "embedding",
                      "head"):
            assert scope in text, scope
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = tiny_runtime()
        assert decode_tokens(bare) == with_scopes
        assert "cache_update" not in lowered(bare, True)
        # Without locations the two modules are the same program.
        assert lowered(bare, False) == lowered(scoped, False)


@pytest.fixture(scope="module")
def host_events(tmp_path_factory):
    """``{name: [stats dict]}`` of the ``ai4e.*`` events of a tiny CPU
    profiler session (host tracer at 1: annotated regions only) around a
    served request."""
    import jax
    from jax.profiler import ProfileData
    out = str(tmp_path_factory.mktemp("trace"))
    runtime = tiny_runtime()
    runtime.warm()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        serve(runtime, [([1, 2, 3], 4, None)])
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ai4e."):
                    events.setdefault(ev.name, []).append(dict(ev.stats))
    return events


class TestProfilerAnnotations:
    def test_noop_without_a_session_and_keyword_stats(self):
        with device_trace("ai4e.test", tick=1, active=2):
            x = 1 + 1
        assert x == 2

    @pytest.mark.parametrize("name,stats", [
        ("ai4e.decode.tick", ("tick", "active", "bound")),
        ("ai4e.decode.prepare", ()),
        ("ai4e.decode.dispatch", ("bound",)),
        ("ai4e.decode.device_wait", ()),
        ("ai4e.decode.bookkeeping", ()),
        ("ai4e.decode.prefill", ("bucket", "slot")),
        ("ai4e.decode.insert", ("slot",)),
    ])
    def test_span_is_on_the_host_plane_with_its_stats(self, host_events,
                                                      name, stats):
        assert name in host_events, sorted(host_events)
        for key in stats:
            assert key in host_events[name][0], host_events[name][0]
        if name == "ai4e.decode.tick":
            # 4 tokens: one from the prefill, three steps, numbered.
            ticks = [int(s["tick"]) for s in host_events[name]]
            assert len(ticks) == 3 and ticks == sorted(ticks)
            assert {int(s["active"]) for s in host_events[name]} == {1}
            # ``tiny_runtime``'s cache of 24 has the one rung.
            assert {int(s["bound"]) for s in host_events[name]} == {24}
