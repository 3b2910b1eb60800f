"""Multi-process integration: control plane and worker as SEPARATE OS
processes wired only by HTTP — the multi-host topology SURVEY.md §4 says the
reference never had a test for (its components only ever met in production
Azure). Worker task state flows through HttpTaskManager → task-store HTTP
surface; results through HttpResultStore."""

import io
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_http(url: str, timeout: float = 30.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except Exception:
            time.sleep(0.2)
    raise TimeoutError(f"{url} never came up")


def http_json(url: str, data: bytes | None = None) -> dict:
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


@pytest.fixture
def spec_dir(tmp_path):
    return tmp_path


class TestMultiProcess:
    def test_task_flows_across_processes(self, spec_dir):
        cp_port, wk_port = free_port(), free_port()
        cp_base = f"http://127.0.0.1:{cp_port}"
        wk_base = f"http://127.0.0.1:{wk_port}"

        models = {
            "service_name": "echo-worker",
            "prefix": "v1/echo",
            "taskstore": cp_base,
            "models": [{"family": "echo", "name": "echo", "size": 16,
                        "buckets": [4], "sync_path": "/run",
                        "async_path": "/run-async"}],
        }
        routes = {"apis": [
            {"prefix": "/v1/echo/run-async",
             "backend": f"{wk_base}/v1/echo/run-async",
             "concurrency": 2, "retry_delay": 0.1},
            {"prefix": "/v1/echo/run",
             "backend": f"{wk_base}/v1/echo/run", "mode": "sync"},
        ]}
        (spec_dir / "models.json").write_text(json.dumps(models))
        (spec_dir / "routes.json").write_text(json.dumps(routes))

        env = dict(os.environ,
                   AI4E_RUNTIME_PLATFORM="cpu",
                   AI4E_PLATFORM_RETRY_DELAY="0.1",
                   PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
        procs = []
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "ai4e_tpu", "control-plane",
                 "--routes", str(spec_dir / "routes.json"),
                 "--port", str(cp_port)],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.STDOUT))
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "ai4e_tpu", "worker",
                 "--models", str(spec_dir / "models.json"),
                 "--port", str(wk_port)],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.STDOUT))

            wait_http(f"{cp_base}/healthz", timeout=60)
            # Generous: worker start pays jit warmup, and a loaded CI host
            # (parallel compile jobs) can stretch it well past 60s.
            wait_http(f"{wk_base}/v1/echo/", timeout=150)

            payload = io.BytesIO()
            np.save(payload, np.arange(16, dtype=np.float32))
            payload = payload.getvalue()

            # The worker says which device it holds (the one it was asked
            # for: AI4E_RUNTIME_PLATFORM=cpu).
            device = http_json(f"{wk_base}/v1/echo/models")["device"]
            assert device["platform"] == "cpu"
            assert device["device_kind"] and device["device_count"] >= 1
            assert device["mesh"]["dp"] == device["device_count"]

            # Sync across the gateway proxy → worker process.
            sync = http_json(f"{cp_base}/v1/echo/run", data=payload)
            assert sync["echo"][:3] == [0.0, 1.0, 2.0]

            # Async: gateway creates the task; dispatcher POSTs to the other
            # process; worker updates status over HTTP; result lands on the
            # control plane's store.
            task = http_json(f"{cp_base}/v1/echo/run-async", data=payload)
            task_id = task["TaskId"]
            final = http_json(
                f"{cp_base}/v1/taskmanagement/task/{task_id}?wait=30")
            assert "completed" in final["Status"], final

            with urllib.request.urlopen(
                    f"{cp_base}/v1/taskstore/result?taskId={task_id}",
                    timeout=10) as resp:
                result = json.loads(resp.read())
            assert result["echo"][:3] == [0.0, 1.0, 2.0]

            # Worker draining: SIGTERM → exits cleanly.
            procs[1].send_signal(signal.SIGTERM)
            assert procs[1].wait(timeout=15) == 0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)


class TestLiveSplitBrainFencing:
    def test_partitioned_primary_is_fenced_across_processes(self, tmp_path):
        """Live 3-OS-process split-brain drive (VERDICT r4 #3): primary and
        standby control planes as real ``python -m ai4e_tpu control-plane``
        processes; this driver process holds the 'network' between them (a
        togglable proxy the standby replicates through). The primary is
        PARTITIONED — alive and serving — while the standby promotes; a
        write carrying the new epoch is rejected by the old primary
        (503 + X-Not-Primary) and it demotes; on heal it rejoins the new
        primary as a follower automatically."""
        import asyncio

        import aiohttp
        from aiohttp import web

        pri_port, stb_port, net_port = free_port(), free_port(), free_port()
        pri_base = f"http://127.0.0.1:{pri_port}"
        stb_base = f"http://127.0.0.1:{stb_port}"
        net_base = f"http://127.0.0.1:{net_port}"

        routes = {"apis": []}
        (tmp_path / "routes.json").write_text(json.dumps(routes))
        base_env = dict(os.environ,
                        AI4E_PLATFORM_RETRY_DELAY="0.1",
                        AI4E_PLATFORM_FAILOVER_INTERVAL="0.3",
                        AI4E_PLATFORM_FAILOVER_DOWN_AFTER="2",
                        PYTHONPATH=REPO + os.pathsep
                        + os.environ.get("PYTHONPATH", ""))
        pri_env = dict(base_env,
                       AI4E_PLATFORM_JOURNAL_PATH=str(tmp_path / "pri.jsonl"),
                       AI4E_PLATFORM_ADVERTISE_URL=pri_base)
        stb_env = dict(base_env,
                       AI4E_PLATFORM_JOURNAL_PATH=str(tmp_path / "stb.jsonl"),
                       AI4E_PLATFORM_REPLICATE_FROM=net_base,
                       AI4E_PLATFORM_ADVERTISE_URL=stb_base)

        async def main():
            procs = []
            net = {"up": True}
            session = aiohttp.ClientSession()

            async def forward(request: web.Request) -> web.Response:
                if not net["up"]:
                    return web.Response(status=503, text="partitioned")
                async with session.request(
                        request.method, pri_base + request.path_qs,
                        data=await request.read(),
                        headers={k: v for k, v in request.headers.items()
                                 if k.startswith("X-")}) as resp:
                    body = await resp.read()
                    headers = {k: v for k, v in resp.headers.items()
                               if k.startswith("X-")}
                    return web.Response(status=resp.status, body=body,
                                        headers=headers,
                                        content_type=resp.content_type)

            proxy = web.Application()
            proxy.router.add_route("*", "/{tail:.*}", forward)
            runner = web.AppRunner(proxy)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", net_port)
            await site.start()

            async def get_json(url, **kw):
                async with session.get(url, **kw) as resp:
                    return await resp.json()

            async def wait_until(pred_coro, timeout=30.0):
                deadline = time.time() + timeout
                while time.time() < deadline:
                    try:
                        if await pred_coro():
                            return True
                    except Exception:
                        pass
                    await asyncio.sleep(0.2)
                return False

            try:
                for env in (pri_env, stb_env):
                    port = pri_port if env is pri_env else stb_port
                    procs.append(subprocess.Popen(  # noqa: ASYNC220  # test launches real control-plane processes
                        [sys.executable, "-m", "ai4e_tpu", "control-plane",
                         "--routes", str(tmp_path / "routes.json"),
                         "--port", str(port)],
                        env=env, stdout=subprocess.DEVNULL,
                        stderr=subprocess.STDOUT))
                await asyncio.to_thread(wait_http, f"{pri_base}/healthz", 60)
                await asyncio.to_thread(wait_http, f"{stb_base}/healthz", 60)

                # Seed a task on the primary; wait until the standby
                # mirrors it (replication through the proxy).
                async with session.post(
                        f"{pri_base}/v1/taskstore/upsert",
                        json={"Endpoint": "http://e/v1/x",
                              "Body": "tile"}) as resp:
                    assert resp.status == 200
                    task_id = (await resp.json())["TaskId"]

                async def mirrored():
                    async with session.get(
                            f"{stb_base}/v1/taskstore/task",
                            params={"taskId": task_id}) as resp:
                        return resp.status == 200
                assert await wait_until(mirrored)

                # Partition. The standby promotes; the primary stays up and
                # still believes it is primary — the dangerous window.
                net["up"] = False

                async def stb_promoted():
                    data = await get_json(f"{stb_base}/v1/taskstore/role")
                    return data["role"] == "primary" and data["epoch"] == 1
                assert await wait_until(stb_promoted)
                pri_role = await get_json(f"{pri_base}/v1/taskstore/role")
                assert pri_role["role"] == "primary"
                assert pri_role["epoch"] == 0

                # A write carrying the new epoch reaches the old primary:
                # REJECTED (fenced on contact), not silently accepted.
                async with session.post(
                        f"{pri_base}/v1/taskstore/upsert",
                        json={"Endpoint": "http://e/v1/x",
                              "Body": "doomed"},
                        headers={"X-Store-Epoch": "1"}) as resp:
                    assert resp.status == 503
                    assert resp.headers.get("X-Not-Primary") == "1"
                pri_role = await get_json(f"{pri_base}/v1/taskstore/role")
                assert pri_role["role"] == "follower"
                assert pri_role["epoch"] == 1

                # New-primary writes flow meanwhile.
                async with session.post(
                        f"{stb_base}/v1/taskstore/upsert",
                        json={"Endpoint": "http://e/v1/x",
                              "Body": "post-failover"}) as resp:
                    assert resp.status == 200
                    new_id = (await resp.json())["TaskId"]

                # Heal: the standby's fencing prober nudges the deposed
                # node to rejoin; it mirrors the new primary's lineage.
                net["up"] = True

                async def rejoined():
                    data = await get_json(f"{pri_base}/v1/taskstore/role")
                    if not (data["role"] == "follower"
                            and data.get("replicating")):
                        return False
                    async with session.get(
                            f"{pri_base}/v1/taskstore/task",
                            params={"taskId": new_id}) as resp:
                        return resp.status == 200
                assert await wait_until(rejoined)
            finally:
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                        p.wait(timeout=10)
                await runner.cleanup()
                await session.close()

        asyncio.run(main())


class TestRedriveCLI:
    def test_redrive_verb_against_live_control_plane(self, spec_dir):
        """`python -m ai4e_tpu redrive` (the Service Bus Explorer resubmit
        workflow as a CLI verb) against a real control-plane process: a
        task dead-letters against a dead backend, the CLI sweeps it back
        to created, and the exact-match filter leaves it alone."""
        cp_port, dead_port = free_port(), free_port()
        cp_base = f"http://127.0.0.1:{cp_port}"
        routes = {"apis": [
            {"prefix": "/v1/echo/run-async",
             "backend": f"http://127.0.0.1:{dead_port}/v1/echo/run-async",
             "concurrency": 1, "retry_delay": 0.1},
        ]}
        (spec_dir / "routes.json").write_text(json.dumps(routes))
        env = dict(os.environ,
                   AI4E_RUNTIME_PLATFORM="cpu",
                   AI4E_PLATFORM_RETRY_DELAY="0.1",
                   AI4E_PLATFORM_MAX_DELIVERY_COUNT="1",
                   PYTHONPATH=REPO + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""))
        proc = subprocess.Popen(
            [sys.executable, "-m", "ai4e_tpu", "control-plane",
             "--routes", str(spec_dir / "routes.json"),
             "--port", str(cp_port)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
        try:
            wait_http(f"{cp_base}/healthz", timeout=60)
            task = http_json(f"{cp_base}/v1/echo/run-async", data=b"BODY")
            tid = task["TaskId"]
            deadline = time.time() + 30
            while time.time() < deadline:
                status = http_json(
                    f"{cp_base}/v1/taskmanagement/task/{tid}")["Status"]
                if "failed" in status:
                    break
                time.sleep(0.2)
            assert "delivery attempts exhausted" in status

            failed_at = http_json(
                f"{cp_base}/v1/taskmanagement/task/{tid}")["Timestamp"]

            # A non-matching filter redrives nothing.
            out = subprocess.run(
                [sys.executable, "-m", "ai4e_tpu", "redrive",
                 "--store", cp_base, "--contains", "no such prose"],
                env=env, capture_output=True, text=True, timeout=60)
            assert out.returncode == 0, out.stderr
            assert json.loads(out.stdout.splitlines()[-1])["redriven"] == 0

            # The default filter sweeps the dead-lettered task.
            out = subprocess.run(
                [sys.executable, "-m", "ai4e_tpu", "redrive",
                 "--store", cp_base],
                env=env, capture_output=True, text=True, timeout=60)
            assert out.returncode == 0, out.stderr
            swept = json.loads(out.stdout.splitlines()[-1])
            assert swept == {"redriven": 1, "task_ids": [tid]}
            # The republished task really re-entered the delivery loop:
            # the record's Timestamp moved past the pre-redrive failure
            # (its Status may read created, mid-backpressure-retry, or —
            # backend still dead at budget 1 — dead-lettered AGAIN).
            record = http_json(f"{cp_base}/v1/taskmanagement/task/{tid}")
            assert record["Timestamp"] > failed_at, record
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
