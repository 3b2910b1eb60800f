"""Runtime tests: mesh construction, servable registration/warmup over the
8-device CPU mesh, and micro-batcher semantics (adaptive batching, padding,
failure isolation, saturation backpressure)."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from ai4e_tpu.parallel import MeshSpec, make_mesh
from ai4e_tpu.runtime import BatcherSaturated, MicroBatcher, ModelRuntime, ServableModel


def run(coro):
    return asyncio.run(coro)


def _double_servable(buckets=(1, 2, 4, 8), shape=(4,)):
    """Trivial servable: doubles its input; postprocess sums."""
    return ServableModel(
        name="double",
        apply_fn=lambda params, batch: batch * params["scale"],
        params={"scale": jnp.asarray(2.0)},
        input_shape=shape,
        preprocess=lambda body, ct: np.frombuffer(body, np.float32),
        postprocess=lambda out: {"sum": float(np.asarray(out).sum())},
        batch_buckets=buckets,
    )


class TestMesh:
    def test_default_mesh_all_dp(self):
        mesh = make_mesh()
        assert mesh.shape["dp"] == 8
        assert mesh.shape["tp"] == 1

    def test_auto_spec_tp(self):
        spec = MeshSpec.auto(8, model_parallel=2)
        assert (spec.dp, spec.tp) == (4, 2)
        mesh = make_mesh(spec)
        assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2

    def test_bad_spec_raises(self):
        with pytest.raises(ValueError):
            MeshSpec.auto(8, model_parallel=3)
        with pytest.raises(ValueError):
            make_mesh(MeshSpec(dp=3))


class TestModelRuntime:
    def test_register_warmup_run(self):
        runtime = ModelRuntime()
        servable = runtime.register(_double_servable())
        times = runtime.warmup()
        assert times["double"] > 0
        out = runtime.run_batch("double", np.ones((8, 4), np.float32))
        np.testing.assert_allclose(out, 2.0 * np.ones((8, 4)))

    def test_bucket_selection(self):
        s = _double_servable(buckets=(1, 2, 4, 8))
        assert s.bucket_for(1) == 1
        assert s.bucket_for(3) == 4
        assert s.bucket_for(8) == 8
        assert s.bucket_for(99) == 8  # clamped to max


class TestMicroBatcher:
    def test_single_request_roundtrip(self):
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable())
            batcher = MicroBatcher(runtime, max_wait_ms=1)
            await batcher.start()
            try:
                result = await batcher.submit(
                    "double", np.asarray([1, 2, 3, 4], np.float32))
                assert result == {"sum": 20.0}  # 2*(1+2+3+4)
            finally:
                await batcher.stop()

        run(main())

    def test_concurrent_requests_are_batched(self):
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable())
            batcher = MicroBatcher(runtime, max_wait_ms=20)
            await batcher.start()
            try:
                results = await asyncio.gather(*[
                    batcher.submit("double",
                                   np.full((4,), i, np.float32))
                    for i in range(8)
                ])
                for i, r in enumerate(results):
                    assert r == {"sum": 2.0 * i * 4}
                # Adaptive batching actually batched (not 8 singles).
                sizes = batcher._batch_size_hist
                assert sizes.quantile(1.0, model="double") >= 2
            finally:
                await batcher.stop()

        run(main())

    def test_pipeline_depth_overlaps_batches(self):
        """pipeline_depth N admits N batches in flight concurrently;
        results still fan back correctly and depth < 1 is rejected."""
        async def main():
            import threading

            runtime = ModelRuntime()
            s = _double_servable()
            in_flight = {"now": 0, "max": 0}
            lock = threading.Lock()
            inner = s.apply_fn

            def tracked(p, b):
                with lock:
                    in_flight["now"] += 1
                    in_flight["max"] = max(in_flight["max"], in_flight["now"])
                import time as _t
                _t.sleep(0.05)  # hold the slot so batches overlap
                with lock:
                    in_flight["now"] -= 1
                return inner(p, b)

            s.apply_fn = tracked
            runtime.register(s)
            runtime.models["double"]._compiled = tracked  # bypass jit timing
            batcher = MicroBatcher(runtime, max_wait_ms=0, pipeline_depth=3)
            await batcher.start()
            try:
                results = await asyncio.gather(*[
                    batcher.submit("double", np.full((4,), i, np.float32))
                    for i in range(12)])
                for i, r in enumerate(results):
                    assert r == {"sum": 2.0 * i * 4}
                assert in_flight["max"] >= 2, in_flight
                assert in_flight["max"] <= 3, in_flight
            finally:
                await batcher.stop()

        run(main())
        with pytest.raises(ValueError):
            MicroBatcher(ModelRuntime(), pipeline_depth=0)

    def test_interactive_priority_jumps_background_backlog(self):
        """With a background backlog deeper than one bucket, an interactive
        submit must ride the NEXT device batch, not wait for the whole
        backlog to drain (batch-API stacks submit at priority 1)."""
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable(buckets=(8,)))
            batcher = MicroBatcher(runtime, max_wait_ms=0, pipeline_depth=1)
            order: list[str] = []

            async def tagged(tag, prio, value):
                await batcher.submit("double",
                                     np.full((4,), value, np.float32),
                                     priority=prio)
                order.append(tag)

            await batcher.start()
            try:
                jobs = [asyncio.create_task(tagged(f"bg{i}", 1, float(i)))
                        for i in range(24)]  # 3 full buckets of background
                await asyncio.sleep(0)  # let them enqueue
                vip = asyncio.create_task(tagged("vip", 0, 99.0))
                await asyncio.gather(vip, *jobs)
                # The interactive request finished within the first two
                # batches' worth of completions, never behind all 24.
                assert "vip" in order[:16], order
            finally:
                await batcher.stop()

        run(main())

    def test_background_admission_headroom_keeps_interactive_alive(self):
        """Background submits saturate at (1 - reserve) of max_pending, so a
        flood of stack items can never 503 interactive traffic out of the
        batcher; aged background items still win a slot eventually."""
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable(buckets=(8,)))
            batcher = MicroBatcher(runtime, max_wait_ms=0, pipeline_depth=1,
                                   max_pending=16, interactive_reserve=0.25)
            # Don't start the flusher: queue state must stay put.
            bg = []
            for i in range(12):  # background cap = 12 of 16
                fut = asyncio.ensure_future(batcher.submit(
                    "double", np.full((4,), float(i), np.float32),
                    priority=1))
                await asyncio.sleep(0)
                bg.append(fut)
            with pytest.raises(BatcherSaturated):
                await batcher.submit("double", np.zeros((4,), np.float32),
                                     priority=1)
            # Interactive still admitted in the reserved headroom.
            vip = asyncio.ensure_future(batcher.submit(
                "double", np.full((4,), 9.0, np.float32)))
            await asyncio.sleep(0)
            assert batcher.pending_count == 13
            await batcher.start()
            results = await asyncio.gather(vip, *bg)
            assert results[0] == {"sum": 72.0}
            await batcher.stop()

        run(main())

    def test_aged_background_item_beats_fresh_interactive(self):
        """Strict priority would starve background under sustained
        interactive load; after priority_aging_s of waiting a background
        item outranks a just-arrived interactive one in the cut."""
        import time as _t

        from ai4e_tpu.runtime.batcher import _Pending

        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable(buckets=(8,)))
            batcher = MicroBatcher(runtime, max_wait_ms=0,
                                   priority_aging_s=0.5)
            loop = asyncio.get_running_loop()
            old_bg = _Pending(np.zeros((4,), np.float32),
                              loop.create_future(), priority=1)
            old_bg.enqueued = _t.perf_counter() - 1.0  # waited 2 classes
            fresh = [
                _Pending(np.zeros((4,), np.float32), loop.create_future())
                for _ in range(9)]
            batcher._pending["double"] = [old_bg, *fresh]
            cut, _bucket = batcher._take_batch("double")
            assert old_bg in cut, "aged background item was starved"

        run(main())

    def test_device_failure_fails_batch_but_not_batcher(self):
        """A device-level execution failure (run_batch raising) must fail
        every request in THAT batch and release the pipeline-window slot —
        later batches run normally on the same batcher."""
        async def main():
            runtime = ModelRuntime()
            s = _double_servable()
            runtime.register(s)
            inner = runtime.models["double"]._compiled

            def flaky(p, b):
                if float(np.asarray(b)[0][0]) < 0:  # poisoned batch marker
                    raise RuntimeError("device exploded")
                return inner(p, b)

            runtime.models["double"]._compiled = flaky
            batcher = MicroBatcher(runtime, max_wait_ms=0, pipeline_depth=2)
            await batcher.start()
            try:
                with pytest.raises(RuntimeError, match="device exploded"):
                    await batcher.submit(
                        "double", np.full((4,), -1.0, np.float32))
                # The window slot came back: a healthy batch still runs.
                ok = await batcher.submit(
                    "double", np.full((4,), 2.0, np.float32))
                assert ok == {"sum": 16.0}
            finally:
                await batcher.stop()

        run(main())

    def test_bad_shape_rejected_immediately(self):
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable())
            batcher = MicroBatcher(runtime, max_wait_ms=1)
            await batcher.start()
            try:
                with pytest.raises(ValueError):
                    await batcher.submit("double", np.zeros((5,), np.float32))
            finally:
                await batcher.stop()

        run(main())

    def test_per_example_postprocess_failure_isolated(self):
        async def main():
            runtime = ModelRuntime()
            s = _double_servable()

            def post(out):
                arr = np.asarray(out)
                if arr[0] < 0:
                    raise ValueError("negative!")
                return {"sum": float(arr.sum())}

            s.postprocess = post
            runtime.register(s)
            batcher = MicroBatcher(runtime, max_wait_ms=20)
            await batcher.start()
            try:
                goods = [batcher.submit("double", np.ones((4,), np.float32))
                         for _ in range(3)]
                bad = batcher.submit("double", -np.ones((4,), np.float32))
                results = await asyncio.gather(*goods, bad,
                                               return_exceptions=True)
                assert [r for r in results[:3]] == [{"sum": 8.0}] * 3
                assert isinstance(results[3], ValueError)  # only the bad one
            finally:
                await batcher.stop()

        run(main())

    def test_saturation_raises(self):
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable())
            batcher = MicroBatcher(runtime, max_wait_ms=1000, max_pending=2)
            # NOT started: requests pile up in pending
            f1 = asyncio.ensure_future(
                batcher.submit("double", np.ones((4,), np.float32)))
            f2 = asyncio.ensure_future(
                batcher.submit("double", np.ones((4,), np.float32)))
            await asyncio.sleep(0.01)
            with pytest.raises(BatcherSaturated):
                await batcher.submit("double", np.ones((4,), np.float32))
            f1.cancel(); f2.cancel()

        run(main())

    def test_padding_not_leaked_into_results(self):
        # 3 requests on buckets (1,2,4,8) → bucket 4, one padded row; padded
        # row must never surface as a result.
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable())
            batcher = MicroBatcher(runtime, max_wait_ms=20)
            await batcher.start()
            try:
                results = await asyncio.gather(*[
                    batcher.submit("double", np.full((4,), 5, np.float32))
                    for _ in range(3)
                ])
                assert results == [{"sum": 40.0}] * 3
            finally:
                await batcher.stop()

        run(main())


class TestPoisonedRows:
    """VERDICT r2 #5 (batcher leg): rows a degraded host invalidated must
    FAIL their tasks while the batch's other rows complete normally."""

    def test_poisoned_rows_fail_only_those_tasks(self):
        async def main():
            runtime = ModelRuntime()
            runtime.register(_double_servable())
            orig = runtime.run_batch_report

            def report(name, batch):
                out, _ = orig(name, batch)
                return out, frozenset({1})  # row 1's host degraded

            runtime.run_batch_report = report
            batcher = MicroBatcher(runtime, max_wait_ms=30)
            await batcher.start()
            try:
                futs = [asyncio.ensure_future(batcher.submit(
                            "double", np.full((4,), float(i + 1), np.float32)))
                        for i in range(3)]
                results = await asyncio.gather(*futs, return_exceptions=True)
                assert results[0] == {"sum": 8.0}
                assert isinstance(results[1], RuntimeError)
                assert "invalidated" in str(results[1])
                assert results[2] == {"sum": 24.0}
            finally:
                await batcher.stop()

        run(main())

    def test_single_runtime_report_is_clean(self):
        runtime = ModelRuntime()
        runtime.register(_double_servable())
        out, poisoned = runtime.run_batch_report(
            "double", np.ones((8, 4), np.float32))
        assert poisoned == frozenset()
        np.testing.assert_allclose(np.asarray(out), 2.0)
