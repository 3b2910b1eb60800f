"""The deployed client's three calls, over HTTP to the gateway: submit an
async API, long-poll the task to a terminal status, fetch the stored result —
and the one call of a sync API. asyncio + aiohttp, one process, one loop."""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

POLL_WAIT_S = 30


def session() -> aiohttp.ClientSession:
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0, keepalive_timeout=120),
        timeout=aiohttp.ClientTimeout(total=None, sock_connect=30))


def _outcome() -> dict:
    return {"ok": False, "task_id": None, "status": None, "result": None,
            "error": None}


def _bucket(status: str) -> str | None:
    """The platform's own status bucketing (``failed`` tested first)."""
    low = status.lower()
    for word in ("failed", "completed", "expired"):
        if word in low:
            return word
    return None


async def async_task(sess, cp_base: str, path: str, body: bytes,
                     content_type: str, deadline: float) -> dict:
    """Submit → long-poll → result, as the caller SDK does it. Returns
    ``{"ok", "task_id", "status", "result", "error"}``; never raises for an
    outcome the platform produced (a refusal is a failed request)."""
    out = _outcome()
    try:
        async with sess.post(cp_base + path, data=body,
                             headers={"Content-Type": content_type}) as resp:
            if resp.status >= 400:
                out["error"] = f"submit HTTP {resp.status}"
                return out
            out["task_id"] = (await resp.json())["TaskId"]
        url = f"{cp_base}/v1/taskmanagement/task/{out['task_id']}"
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                out["error"] = "timed out"
                return out
            async with sess.get(url, params={
                    "wait": f"{max(1.0, min(POLL_WAIT_S, left)):.1f}"}) as resp:
                if resp.status >= 400:
                    out["error"] = f"status HTTP {resp.status}"
                    return out
                record = await resp.json()
            out["status"] = record.get("Status", "")
            kind = _bucket(out["status"])
            if kind == "completed":
                break
            if kind is not None:
                out["error"] = out["status"]
                return out
        async with sess.get(f"{cp_base}/v1/taskstore/result",
                            params={"taskId": out["task_id"]}) as resp:
            if resp.status != 200:
                out["error"] = f"result HTTP {resp.status}"
                return out
            out["result"] = json.loads(await resp.read())
        out["ok"] = True
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            KeyError) as exc:
        out["error"] = repr(exc)
    return out


async def sync_call(sess, cp_base: str, path: str, body: bytes,
                    content_type: str, deadline: float) -> dict:
    out = _outcome()
    try:
        async with sess.post(
                cp_base + path, data=body,
                headers={"Content-Type": content_type},
                timeout=aiohttp.ClientTimeout(
                    total=max(0.1, deadline - time.monotonic()))) as resp:
            if resp.status != 200:
                out["error"] = f"HTTP {resp.status}"
                return out
            out["result"] = json.loads(await resp.read())
        out["ok"] = True
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as exc:
        out["error"] = repr(exc)
    return out


async def task_ledger(sess, cp_base: str, task_id: str) -> list[dict]:
    async with sess.get(f"{cp_base}/v1/taskmanagement/task/{task_id}",
                        params={"ledger": "1"}) as resp:
        if resp.status != 200:
            return []
        return (await resp.json()).get("Ledger") or []
