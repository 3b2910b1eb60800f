"""The closed-loop measurement client (utils/loadclient.py — used by
examples/loadgen.py) against a live aiohttp app that exhibits
the production failure modes it must survive: 503 backpressure, error
responses, non-JSON bodies, vanished (404) tasks, and tasks stuck
non-terminal. A load tool pointed at a deployment must record these as
failures and keep running, never crash or hang."""

import asyncio
import itertools

import pytest
from aiohttp import ClientSession, TCPConnector, web

from ai4e_tpu.utils.loadclient import run_closed_loop


def run(coro):
    return asyncio.run(coro)


async def _serve(app):
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, runner.addresses[0][1]


class TestSyncMode:
    def test_mixed_outcomes_counted_not_raised(self):
        """200s count completed; 500s and non-JSON error bodies count
        failed; 503 is backpressure (retried, never a failure)."""
        outcomes = itertools.cycle([200, 500, 503, 200])

        async def main():
            async def handler(request):
                status = next(outcomes)
                if status == 503:
                    return web.Response(status=503, text="busy")
                if status == 500:
                    return web.Response(status=500, text="boom not json")
                return web.json_response({"ok": True})

            app = web.Application()
            app.router.add_post("/api", handler)
            runner, port = await _serve(app)
            try:
                async with ClientSession(
                        connector=TCPConnector(limit=0)) as session:
                    window = await run_closed_loop(
                        session, post_url=f"http://127.0.0.1:{port}/api",
                        payload=b"x", headers={}, mode="sync",
                        concurrency=4, duration=0.8, ramp=0.2)
            finally:
                await runner.cleanup()
            return window

        window = run(main())
        assert window["completed"] > 0
        assert window["failed"] > 0
        assert window["p50_latency_ms"] >= 0

    def test_connection_error_is_a_failure_not_a_crash(self):
        async def main():
            async with ClientSession(
                    connector=TCPConnector(limit=0)) as session:
                # Nothing listens on this port: every request errors.
                return await run_closed_loop(
                    session, post_url="http://127.0.0.1:9/never",
                    payload=b"x", headers={}, mode="sync",
                    concurrency=2, duration=0.5, ramp=0.1)

        window = run(main())
        assert window["completed"] == 0
        assert window["failed"] > 0


class TestAsyncMode:
    def _app(self, *, task_status):
        """Task API: POST creates a task, GET reports ``task_status``."""
        counter = itertools.count()

        async def post(request):
            return web.json_response({"TaskId": str(next(counter))})

        async def status(request):
            st = task_status(request.match_info["tid"])
            if st is None:
                return web.Response(status=404, text="Task not found.")
            return web.json_response({"TaskId": request.match_info["tid"],
                                      "Status": st})

        app = web.Application()
        app.router.add_post("/api", post)
        app.router.add_get("/task/{tid}", status)
        return app

    def _drive(self, app, **kw):
        async def main():
            runner, port = await _serve(app)
            try:
                async with ClientSession(
                        connector=TCPConnector(limit=0)) as session:
                    return await run_closed_loop(
                        session, post_url=f"http://127.0.0.1:{port}/api",
                        payload=b"x", headers={}, mode="async",
                        status_url_for=lambda tid:
                            f"http://127.0.0.1:{port}/task/{tid}",
                        concurrency=3, duration=0.8, ramp=0.2, **kw)
            finally:
                await runner.cleanup()

        return run(main())

    def test_completed_and_failed_tasks_counted(self):
        window = self._drive(self._app(
            task_status=lambda tid: "completed - done" if int(tid) % 2
            else "failed - bad"))
        assert window["completed"] > 0
        assert window["failed"] > 0

    def test_vanished_task_404_is_a_failure_not_a_crash(self):
        window = self._drive(self._app(task_status=lambda tid: None))
        assert window["completed"] == 0
        assert window["failed"] > 0

    def test_stuck_task_hits_deadline_instead_of_hanging(self):
        window = self._drive(
            self._app(task_status=lambda tid: "running - forever"),
            task_timeout=0.3, poll_wait=0.1)
        assert window["completed"] == 0
        assert window["failed"] > 0

    def test_requires_status_url(self):
        async def main():
            async with ClientSession() as session:
                with pytest.raises(ValueError):
                    await run_closed_loop(session, post_url="http://x",
                                          payload=b"", headers={},
                                          mode="async")

        run(main())


class TestThrottleBackpressure:
    def test_429_is_backpressure_not_failure(self):
        """A rate-limited deployment throttles the load tool; throttled
        requests re-enter (honoring a capped Retry-After), never counting
        as failures."""
        import itertools as _it

        outcomes = _it.cycle([429, 200, 200])

        async def main():
            async def handler(request):
                if next(outcomes) == 429:
                    return web.Response(status=429, text="slow down",
                                        headers={"Retry-After": "1"})
                return web.json_response({"ok": True})

            app = web.Application()
            app.router.add_post("/api", handler)
            runner, port = await _serve(app)
            try:
                async with ClientSession(
                        connector=TCPConnector(limit=0)) as session:
                    return await run_closed_loop(
                        session, post_url=f"http://127.0.0.1:{port}/api",
                        payload=b"x", headers={}, mode="sync",
                        concurrency=4, duration=1.0, ramp=0.2)
            finally:
                await runner.cleanup()

        window = run(main())
        assert window["completed"] > 0
        assert window["failed"] == 0  # throttling is not failure


class TestLoadgenHonesty:
    """ISSUE 11 satellite: the window JSON must record OFFERED vs ACHIEVED
    rate and a client-side error taxonomy, so a CPU-bound run can't
    silently report a lower rate as if it were the target."""

    def test_closed_loop_reports_offered_and_error_taxonomy(self):
        outcomes = itertools.cycle([200, 500])

        async def main():
            async def handler(request):
                status = next(outcomes)
                if status == 500:
                    return web.Response(status=500, text="boom")
                return web.json_response({"ok": True})

            app = web.Application()
            app.router.add_post("/v1/echo", handler)
            runner, port = await _serve(app)
            try:
                async with ClientSession(
                        connector=TCPConnector(limit=0)) as session:
                    window = await run_closed_loop(
                        session, post_url=f"http://127.0.0.1:{port}/v1/echo",
                        payload=b"x", headers={}, mode="sync",
                        concurrency=4, duration=0.6, ramp=0.2)
            finally:
                await runner.cleanup()
            # Offered counts every attempt; achieved only completions —
            # with every other request a 500, offered ≈ 2× completed.
            assert window["offered"] >= window["completed"]
            assert window["offered_rate"] >= window["achieved_rate"]
            assert window["client_errors"].get("http_500", 0) > 0
            assert window["achieved_rate"] == window["value"]

        run(main())

    def test_open_loop_offers_the_target_rate_and_reports_saturation(self):
        """The open loop schedules starts by the clock: a slow platform
        still sees the target offered rate, and starts the client could
        not even launch (max_inflight) are recorded as client_saturated
        — never silently dropped."""
        from ai4e_tpu.utils.loadclient import run_open_loop

        async def main():
            accepted, terminal = [], []

            async def post(request):
                return web.json_response({"TaskId": "t-%d" % len(accepted)})

            async def poll(request):
                # Answer terminal instantly — the pacing under test is
                # the POST schedule, not the platform.
                return web.json_response({"Status": "completed"})

            app = web.Application()
            app.router.add_post("/v1/echo", post)
            app.router.add_get("/v1/task/{tid}", poll)
            runner, port = await _serve(app)
            base = f"http://127.0.0.1:{port}"
            try:
                async with ClientSession(
                        connector=TCPConnector(limit=0)) as session:
                    window = await run_open_loop(
                        session, post_url=f"{base}/v1/echo", payload=b"x",
                        headers={}, rate=200.0,
                        status_url_for=lambda t: f"{base}/v1/task/{t}",
                        duration=1.0, ramp=0.3, max_inflight=64,
                        on_accepted=accepted.append,
                        on_terminal=lambda t, s: terminal.append((t, s)))
            finally:
                await runner.cleanup()
            # The offered rate tracks the target (clock-scheduled), within
            # scheduler slack on a busy box.
            assert window["offered_rate"] > 100.0
            assert window["target_rate"] == 200.0
            assert window["total_offered"] >= window["total_launched"]
            assert len(accepted) == window["total_launched"]
            assert len(terminal) >= window["total_completed"]

        run(main())

    def test_open_loop_client_saturation_is_taxonomized(self):
        from ai4e_tpu.utils.loadclient import run_open_loop

        async def main():
            async def post(request):
                return web.json_response({"TaskId": "t"})

            async def poll(request):
                await asyncio.sleep(2.0)  # tasks outlive the client budget
                return web.json_response({"Status": "created"})

            app = web.Application()
            app.router.add_post("/v1/echo", post)
            app.router.add_get("/v1/task/{tid}", poll)
            runner, port = await _serve(app)
            base = f"http://127.0.0.1:{port}"
            try:
                async with ClientSession(
                        connector=TCPConnector(limit=0)) as session:
                    window = await run_open_loop(
                        session, post_url=f"{base}/v1/echo", payload=b"x",
                        headers={}, rate=300.0,
                        status_url_for=lambda t: f"{base}/v1/task/{t}",
                        duration=0.8, ramp=0.2, max_inflight=4,
                        task_timeout=0.5)
            finally:
                await runner.cleanup()
            # 4 pollers wedge instantly; every further offered start is
            # recorded against the CLIENT, not hidden.
            assert window["total_errors"].get("client_saturated", 0) > 0
            assert window["total_offered"] > window["total_launched"]

        run(main())
