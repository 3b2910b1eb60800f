"""Shared closed-loop load-measurement client.

Used by ``examples/loadgen.py`` against any live deployment: N clients each
keep exactly one request in flight against an async task route (POST →
long-poll ``/task/{id}``) or a sync route (POST → response), with an untimed
steady-state ramp before the measured window opens.

Error tolerance is the point of sharing this: a non-503 error response, an
undecodable body, a vanished task (404 after the reaper), or a transport
error counts as one failed request and the run continues — a load tool
pointed at a production topology must survive exactly the conditions it
creates.
"""

from __future__ import annotations

import asyncio
import time


def _latency_percentiles(window_lat: list[float]) -> dict:
    """p50/p95/p99 (ms) over a sorted window-latency list — ONE convention
    shared by the closed and open loops so their reported numbers stay
    comparable."""
    def pctl(q: float) -> float:
        return round(
            window_lat[max(0, int(len(window_lat) * q) - 1)] * 1000, 1)
    return {
        "p50_latency_ms": round(window_lat[len(window_lat) // 2] * 1000, 1),
        "p95_latency_ms": pctl(0.95),
        "p99_latency_ms": pctl(0.99),
    }


def _window_error_delta(close: dict, mark: dict) -> dict:
    """Per-kind client-error counts inside the measured window (close
    snapshot minus mark snapshot, zero-delta kinds dropped)."""
    return {k: close["errors"].get(k, 0) - mark["errors"].get(k, 0)
            for k in close["errors"]
            if close["errors"].get(k, 0) - mark["errors"].get(k, 0) > 0}


def _backoff(resp) -> float:
    """Sleep for a backpressure response: Retry-After when the server sent
    one (capped at 2 s — a closed-loop client that idles longer just
    under-measures), else a short yield."""
    retry_after = resp.headers.get("Retry-After")
    try:
        return min(float(retry_after), 2.0) if retry_after else 0.05
    except ValueError:
        return 0.05


async def run_closed_loop(
    session,
    *,
    post_url: str,
    payload: bytes,
    headers: dict,
    mode: str = "async",
    status_url_for=None,
    concurrency: int = 64,
    duration: float = 20.0,
    ramp: float = 5.0,
    task_timeout: float = 120.0,
    poll_wait: float = 30.0,
) -> dict:
    """Drive ``post_url`` closed-loop; returns window stats.

    ``status_url_for(task_id) -> url`` is required in async mode.
    Tasks the platform shed on their deadline (terminal ``expired``
    status / 504) count as ``expired``, not failed.
    Returns ``{"value", "p50_latency_ms", "p95_latency_ms", "completed",
    "failed", "expired", "duration_s", ...}`` where value is
    completions/second inside the measurement window that opens after
    ``ramp`` seconds.
    """
    import aiohttp

    if mode == "async" and status_url_for is None:
        raise ValueError("async mode needs status_url_for")

    latencies: list[float] = []
    completed = 0
    failed = 0
    expired = 0
    # Loadgen honesty (ISSUE 11): every POST the client actually attempted
    # (backpressure re-entries included) and a client-side error taxonomy,
    # so the window JSON records OFFERED vs ACHIEVED rate — a CPU-bound
    # run cannot silently report a lower rate as if it were the target.
    offered = 0
    errors: dict[str, int] = {}

    def _err(kind: str) -> None:
        errors[kind] = errors.get(kind, 0) + 1

    def _score_completion(elapsed: float) -> None:
        nonlocal completed
        latencies.append(elapsed)
        completed += 1

    def _score_failed() -> None:
        nonlocal failed
        failed += 1

    def _score_expired() -> None:
        nonlocal expired
        expired += 1

    def _score_backpressure(resp) -> None:
        # A tenant-quota 429 is the tenant's OWN contract (shed, carries
        # Retry-After); other 429/503s are platform pressure.
        reason = resp.headers.get("X-Shed-Reason", "")
        if "tenant-quota" in reason:
            _err("tenant_quota_429")
        else:
            _err(f"backpressure_{resp.status}")

    async def one_async() -> None:
        nonlocal offered
        t0 = time.perf_counter()
        offered += 1
        try:
            async with session.post(post_url, data=payload,
                                    headers=headers) as resp:
                if resp.status in (503, 429):
                    # Backpressure (admission 503 / per-key throttle 429 /
                    # tenant quota 429): not a failure — yield briefly and
                    # re-enter. The client honors Retry-After when present,
                    # capped so one long hint can't idle the closed loop
                    # past the window.
                    _score_backpressure(resp)
                    await asyncio.sleep(_backoff(resp))
                    return
                if resp.status == 504:  # shed: budget spent at the edge
                    _err("shed_504")
                    _score_expired()
                    return
                if resp.status >= 400:
                    _err(f"http_{resp.status}")
                    _score_failed()
                    return
                task = await resp.json()
            task_id = task["TaskId"]
        except asyncio.TimeoutError:
            _err("timeout")
            _score_failed()
            return
        except aiohttp.ClientError as exc:
            _err("connect_error"
                 if isinstance(exc, aiohttp.ClientConnectorError)
                 else "transport_error")
            _score_failed()
            return
        except (ValueError, KeyError, TypeError):
            _err("bad_response")
            _score_failed()
            return
        deadline = t0 + task_timeout
        while True:
            try:
                async with session.get(status_url_for(task_id),
                                       params={"wait": str(int(poll_wait))},
                                       headers=headers) as resp:
                    if resp.status == 404:  # reaped/evicted task
                        _err("task_poll_404")
                        _score_failed()
                        return
                    record = await resp.json()
                status = record["Status"]
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                    KeyError, TypeError):
                _err("poll_transport")
                _score_failed()
                return
            # "failed" FIRST — the platform's canonical bucketing
            # (TaskStatus.canonical) tests it first, so a status carrying
            # both words counts the same here as in the store's sets.
            if "failed" in status:
                _score_failed()
                return
            if "completed" in status:
                _score_completion(time.perf_counter() - t0)
                return
            if "expired" in status:
                # Admission shed the task on its deadline (terminal) —
                # shed work, not a platform failure.
                _score_expired()
                return
            if time.perf_counter() > deadline:  # stuck task: don't hang the run
                _err("stuck_timeout")
                _score_failed()
                return

    async def one_sync() -> None:
        # 503 backpressure: sleep briefly and return (neither completed nor
        # failed) — client_loop re-enters until the run deadline, same as
        # one_async, so sustained backpressure can never outlive the run.
        nonlocal offered
        t0 = time.perf_counter()
        offered += 1
        try:
            async with session.post(post_url, data=payload,
                                    headers=headers) as resp:
                if resp.status in (503, 429):
                    _score_backpressure(resp)
                    await asyncio.sleep(_backoff(resp))
                    return
                if resp.status == 504:  # admission shed on deadline
                    _err("shed_504")
                    _score_expired()
                    return
                await resp.read()
                ok = resp.status == 200
                if not ok:
                    _err(f"http_{resp.status}")
        except asyncio.TimeoutError:
            _err("timeout")
            ok = False
        except aiohttp.ClientError as exc:
            _err("connect_error"
                 if isinstance(exc, aiohttp.ClientConnectorError)
                 else "transport_error")
            ok = False
        if ok:
            _score_completion(time.perf_counter() - t0)
        else:
            _score_failed()

    one = one_sync if mode == "sync" else one_async

    async def client_loop(stop_at: float) -> None:
        while time.perf_counter() < stop_at:
            await one()

    # Ramp: run load untimed until the pipeline is in steady state (cold
    # start — empty queues, small batches, cache touches — would otherwise
    # land inside the measured window). In-flight work at the open and
    # close of the window cancels to first order.
    mark: dict = {}
    close: dict = {}

    def _snapshot() -> dict:
        return dict(t=time.perf_counter(), completed=completed,
                    failed=failed, expired=expired, offered=offered,
                    errors=dict(errors), n_lat=len(latencies))

    async def open_window() -> None:
        await asyncio.sleep(ramp)
        mark.update(_snapshot())

    async def close_window() -> None:
        # Snapshot AT stop_at, not after the drain: gather() returns only
        # once every in-flight request resolves, and a single stuck task
        # would stretch the denominator by up to task_timeout with no
        # completions — deflating throughput several-fold.
        await asyncio.sleep(ramp + duration)
        close.update(_snapshot())

    stop_at = time.perf_counter() + ramp + duration
    await asyncio.gather(open_window(), close_window(),
                         *[client_loop(stop_at) for _ in range(concurrency)])
    elapsed = close["t"] - mark["t"]

    window_lat = sorted(latencies[mark["n_lat"]:close["n_lat"]]) or [0.0]
    n = close["completed"] - mark["completed"]

    n_offered = close["offered"] - mark["offered"]
    return {
        "value": round(n / elapsed, 2),
        **_latency_percentiles(window_lat),
        "completed": n,
        "failed": close["failed"] - mark["failed"],
        "expired": close["expired"] - mark["expired"],
        "duration_s": round(elapsed, 1),
        # Honesty block (ISSUE 11): what the client actually ATTEMPTED vs
        # what completed, plus the client-side error taxonomy — a
        # CPU-bound run reports its shortfall instead of silently
        # presenting the achieved rate as the target.
        "offered": n_offered,
        "offered_rate": round(n_offered / elapsed, 2),
        "achieved_rate": round(n / elapsed, 2),
        "client_errors": _window_error_delta(close, mark),
    }


async def run_open_loop(
    session,
    *,
    post_url: str,
    payload: bytes,
    headers: dict,
    rate: float,
    status_url_for,
    duration: float = 20.0,
    ramp: float = 2.0,
    max_inflight: int = 512,
    task_timeout: float = 120.0,
    poll_wait: float = 30.0,
    post_url_for=None,
    on_accepted=None,
    on_terminal=None,
) -> dict:
    """Drive ``post_url`` OPEN-loop at an offered ``rate`` (request starts
    per second) — the rig's load shape (ISSUE 11): unlike the closed loop,
    arrival times are scheduled by the clock, not by completions, so a
    slow platform faces the same offered rate as a fast one and the gap
    shows up as queueing/errors instead of silently lowering the load.

    Honesty contract: ``offered`` counts every scheduled start — including
    starts the CLIENT could not launch because ``max_inflight`` requests
    were already outstanding (taxonomy ``client_saturated``: the loadgen
    itself was the bottleneck; the platform never saw those). ``achieved``
    counts requests that reached a terminal outcome. The window JSON
    reports ``offered_rate`` vs ``achieved_rate`` plus the same client
    error taxonomy as the closed loop.

    ``on_accepted(task_id)`` / ``on_terminal(task_id, status)`` feed the
    rig's cross-process invariant verdict (every accepted task terminal).
    """
    import aiohttp

    offered = 0
    launched = 0
    completed = 0
    failed = 0
    expired = 0
    latencies: list[float] = []
    errors: dict[str, int] = {}
    inflight: set = set()

    def _err(kind: str) -> None:
        errors[kind] = errors.get(kind, 0) + 1

    async def one() -> None:
        t0 = time.perf_counter()
        url = post_url if post_url_for is None else post_url_for()
        nonlocal completed, failed, expired
        try:
            async with session.post(url, data=payload,
                                    headers=headers) as resp:
                if resp.status in (503, 429):
                    # Tenant-quota 429s get their own taxonomy line: the
                    # rig runs one open loop per tenant, so this count IS
                    # that tenant's shed tally in the verdict.
                    if "tenant-quota" in resp.headers.get(
                            "X-Shed-Reason", ""):
                        _err("tenant_quota_429")
                    else:
                        _err(f"backpressure_{resp.status}")
                    return
                if resp.status == 504:
                    _err("shed_504")
                    expired += 1
                    return
                if resp.status >= 400:
                    _err(f"http_{resp.status}")
                    failed += 1
                    return
                task = await resp.json()
            task_id = task["TaskId"]
        except asyncio.TimeoutError:
            _err("timeout")
            failed += 1
            return
        except aiohttp.ClientError as exc:
            _err("connect_error"
                 if isinstance(exc, aiohttp.ClientConnectorError)
                 else "transport_error")
            failed += 1
            return
        except (ValueError, KeyError, TypeError):
            _err("bad_response")
            failed += 1
            return
        if on_accepted is not None:
            on_accepted(task_id)
        deadline = t0 + task_timeout
        while True:
            try:
                async with session.get(status_url_for(task_id),
                                       params={"wait": str(int(poll_wait))},
                                       headers=headers) as resp:
                    if resp.status == 404:
                        _err("task_poll_404")
                        failed += 1
                        return
                    if resp.status >= 400:
                        # Transient poll refusal (a gateway mid-kill, a
                        # store mid-failover): back off and re-poll — the
                        # task is accepted, its verdict matters.
                        await asyncio.sleep(0.2)
                    else:
                        record = await resp.json()
                        status = record["Status"]
                        if ("failed" in status or "completed" in status
                                or "expired" in status):
                            if on_terminal is not None:
                                on_terminal(task_id, status)
                            if "failed" in status:
                                failed += 1
                            elif "completed" in status:
                                completed += 1
                                latencies.append(time.perf_counter() - t0)
                            else:
                                expired += 1
                            return
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                    KeyError, TypeError):
                # A kill mid-poll is expected chaos: reconnect via the
                # balancer and keep polling until the task's own budget
                # runs out.
                _err("poll_transport")
                await asyncio.sleep(0.2)
            if time.perf_counter() > deadline:
                _err("stuck_timeout")
                failed += 1
                return

    def _reap(task: asyncio.Task) -> None:
        inflight.discard(task)

    mark: dict = {}
    close: dict = {}

    async def open_window() -> None:
        await asyncio.sleep(ramp)
        mark.update(t=time.perf_counter(), offered=offered,
                    completed=completed, failed=failed, expired=expired,
                    errors=dict(errors), n_lat=len(latencies))

    async def close_window() -> None:
        await asyncio.sleep(ramp + duration)
        close.update(t=time.perf_counter(), offered=offered,
                     completed=completed, failed=failed, expired=expired,
                     errors=dict(errors), n_lat=len(latencies))

    async def pacer() -> None:
        nonlocal offered, launched
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        stop_at = t0 + ramp + duration
        while True:
            now = loop.time()
            if now >= stop_at:
                return
            due = int(rate * (now - t0)) - offered
            for _ in range(due):
                offered += 1
                if len(inflight) >= max_inflight:
                    # The CLIENT is the bottleneck: record it as such —
                    # this offered start never reached the platform.
                    _err("client_saturated")
                    continue
                task = loop.create_task(one())
                inflight.add(task)
                task.add_done_callback(_reap)
                launched += 1
            await asyncio.sleep(0.005)

    await asyncio.gather(pacer(), open_window(), close_window())
    if inflight:
        # Bounded drain so accepted tasks get their verdict; the window
        # stats were snapshotted at close time already.
        await asyncio.wait(inflight, timeout=task_timeout)
        for task in list(inflight):
            task.cancel()
        await asyncio.gather(*inflight, return_exceptions=True)

    elapsed = close["t"] - mark["t"]
    n = close["completed"] - mark["completed"]
    n_offered = close["offered"] - mark["offered"]
    window_lat = sorted(latencies[mark["n_lat"]:close["n_lat"]]) or [0.0]

    window_errors = _window_error_delta(close, mark)
    return {
        "mode": "open",
        "target_rate": rate,
        "offered": n_offered,
        "offered_rate": round(n_offered / elapsed, 2),
        "achieved_rate": round(n / elapsed, 2),
        "value": round(n / elapsed, 2),
        "completed": n,
        "failed": close["failed"] - mark["failed"],
        "expired": close["expired"] - mark["expired"],
        **_latency_percentiles(window_lat),
        "client_errors": window_errors,
        "duration_s": round(elapsed, 1),
        # Totals over the WHOLE run (ramp + window + drain) — what the
        # rig's invariant verdict reconciles against accepted TaskIds.
        "total_offered": offered,
        "total_launched": launched,
        "total_completed": completed,
        "total_failed": failed,
        "total_expired": expired,
        "total_errors": dict(errors),
    }
