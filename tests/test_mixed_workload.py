"""Mixed multi-API serving (VERDICT r3 #7): several model families share ONE
worker/batcher/device, and the priority classes keep interactive latency
flat while a background batch stack saturates the queue — the isolation the
reference only gets from separate container pools
(``APIs/Charts/camera-trap/`` side-by-side deployments). This test pins
the serving-level isolation property on CPU."""

import asyncio
import io
import time

import numpy as np
from aiohttp.test_utils import TestClient, TestServer

from ai4e_tpu.metrics import MetricsRegistry
from ai4e_tpu.platform_assembly import LocalPlatform, PlatformConfig
from ai4e_tpu.runtime import (
    InferenceWorker,
    MicroBatcher,
    ModelRuntime,
    ServableModel,
)

SIZE = 8


def run(coro):
    return asyncio.run(coro)


async def serve(app):
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


def npy_bytes(arr):
    buf = io.BytesIO()
    np.save(buf, arr)
    return buf.getvalue()


def make_servable(name):
    import jax.numpy as jnp

    def apply_fn(params, batch):
        return jnp.asarray(batch) * 2.0

    return ServableModel(
        name=name, apply_fn=apply_fn, params={},
        input_shape=(SIZE,), preprocess=lambda b, c: np.load(io.BytesIO(b)),
        postprocess=lambda out: {"sum": float(np.asarray(out).sum())},
        batch_buckets=(4,))


class TestMixedWorkloadIsolation:
    def test_interactive_model_unaffected_by_background_stack(self):
        """Two models on one worker: while a 400-item background stack for
        the 'stack' model drains (priority 1, ~100 sequential device
        batches at bucket 4), interactive requests for the 'vip' model must
        cut into the next batches and complete in a small fraction of the
        stack's wall time — per-model queues + interactive-first cuts are
        the mechanism."""
        async def main():
            platform = LocalPlatform(PlatformConfig(retry_delay=0.05))
            runtime = ModelRuntime()
            vip = make_servable("vip")
            stack_model = make_servable("stack")
            runtime.register(vip)
            runtime.register(stack_model)
            runtime.warmup()
            metrics = MetricsRegistry()
            batcher = MicroBatcher(runtime, max_wait_ms=1, max_pending=2048,
                                   pipeline_depth=1, metrics=metrics)
            worker = InferenceWorker("mixed-svc", runtime, batcher,
                                     task_manager=platform.task_manager,
                                     prefix="v1/models",
                                     store=platform.store,
                                     metrics=MetricsRegistry())
            worker.serve_model(vip, sync_path="/vip")
            worker.serve_batch(stack_model, max_items=1024,
                               progress_every=0.0)
            await batcher.start()
            client = await serve(worker.service.app)
            try:
                stack = np.ones((400, SIZE), np.float32)

                async def run_stack():
                    t0 = time.perf_counter()
                    resp = await client.post("/v1/models/stack-batch",
                                             data=npy_bytes(stack))
                    body = await resp.json()
                    return time.perf_counter() - t0, resp.status, body

                stack_task = asyncio.create_task(run_stack())
                # Let the stack flood the queue before interactive arrives
                # (serve_batch keeps submit_concurrency=64 items in flight,
                # so the queue holds at most that many at once).
                while batcher.pending_count < 48:  # noqa: ASYNC110  # polling an in-process counter is the test's readiness gate
                    await asyncio.sleep(0.005)

                vip_lat = []
                for _ in range(10):
                    t0 = time.perf_counter()
                    resp = await client.post(
                        "/v1/models/vip", data=npy_bytes(
                            np.ones((SIZE,), np.float32)))
                    assert resp.status == 200, await resp.text()
                    assert (await resp.json())["sum"] == 2.0 * SIZE
                    vip_lat.append(time.perf_counter() - t0)
                assert not stack_task.done(), (
                    "stack drained before interactive ran — the test lost "
                    "its contention window; raise the stack size")

                stack_s, status, body = await stack_task
                assert status == 200 and body["count"] == 400, body
                assert body["failed"] == 0, body
                # Isolation: every interactive request beat the stack by a
                # wide margin (it cut into the next device batch instead of
                # queueing behind ~100 background batches).
                worst_vip = max(vip_lat)
                assert worst_vip < stack_s / 4, (
                    f"interactive p100 {worst_vip:.3f}s vs stack "
                    f"{stack_s:.3f}s — priority isolation failed")

                # Per-model breakdown exists in the shared batcher metrics
                # (the mixed bench's per-model histogram source).
                seen = {labels.get("model")
                        for _, _, labels, _ in metrics.histogram(
                            "ai4e_batch_size", "").collect()}
                assert {"vip", "stack"} <= seen, seen
            finally:
                await batcher.stop()
                await client.close()

        run(main())
