"""Closed loop: one job keeps ``outstanding`` async tasks in flight, each
completion submitting the next (a raster job fanning tiles out).

Traffic parameters: ``path``, ``outstanding``, ``ramp_s`` (the loop runs this
long before the window opens, so the window sees the steady state and every
bucket has been ridden), ``task_timeout_s``, ``payload`` (kind + parameters).

What counts. A task whose result body was read inside the window is attempted,
and completed if the result is valid: the rate is taken over those. A task
SUBMITTED inside the window that ends failed, invalid or timed out is
attempted and failed whenever it ends — in the window, in the drain after it,
or never (given up when the drain's ``task_timeout_s`` is over) — so a lost
task cannot leave the run ``correct``.
"""

from __future__ import annotations

import asyncio
import itertools
import random

from benchmark.lib import client


async def run(ctx) -> dict:
    t = ctx.traffic
    payloads = ctx.payloads()
    counter = itertools.count()
    window = {"t0": None, "t1": None}
    done: list[dict] = []
    resubmit_gaps: list[float] = []
    in_flight: dict[int, float] = {}     # counter -> instant it was submitted

    def record(c: int, t_sub: float, t_end: float, ok: bool, out: dict,
               error: str | None) -> None:
        done.append({"counter": c, "due": t_sub, "end": t_end, "ok": ok,
                     "latency_s": t_end - t_sub, "task_id": out["task_id"],
                     "result": out["result"] if ok else None, "error": error})

    async def slot(sess) -> None:
        last_end = None
        while window["t1"] is None:
            c = next(counter)
            body = payloads.body(c)
            t_sub = in_flight[c] = ctx.now()
            if last_end is not None and window["t0"] is not None:
                resubmit_gaps.append(t_sub - last_end)
            out = await client.async_task(
                sess, ctx.cp_base, t["path"], body, payloads.content_type,
                deadline=t_sub + t["task_timeout_s"])
            last_end = t_end = ctx.now()
            del in_flight[c]
            if window["t0"] is None:
                continue                     # ended in the ramp
            ok = out["ok"] and ctx.valid(out["result"])
            ended_inside = t_end >= window["t0"] and (
                window["t1"] is None or t_end <= window["t1"])
            if ended_inside or (not ok and t_sub >= window["t0"]):
                record(c, t_sub, t_end, ok, out, None if ok else (
                    out["error"] or "invalid result"))

    async with client.session() as sess:
        slots = [asyncio.ensure_future(slot(sess))
                 for _ in range(t["outstanding"])]
        try:
            await asyncio.sleep(t["ramp_s"])
            window["t0"] = await ctx.window_start()
            await asyncio.sleep(ctx.seconds)
            window["t1"] = ctx.now()
            await ctx.window_end()
            # Let the tasks in flight end (none is submitted after the
            # window), so that no long-poll is left hanging on the gateway
            # when the processes are stopped.
            await asyncio.wait(slots, timeout=t["task_timeout_s"])
        finally:
            for s in slots:
                s.cancel()
            await asyncio.gather(*slots, return_exceptions=True)
        for c, t_sub in in_flight.items():   # never ended: given up, failed
            if t_sub >= window["t0"]:
                record(c, t_sub, ctx.now(), False,
                       {"task_id": None, "result": None},
                       "not ended within task_timeout_s of the window's end")
        ok = [r for r in done if r["ok"]]
        rng = random.Random(ctx.seed)
        pool = [r for r in ok if ctx.reference_eligible(r)]
        sample = rng.sample(pool, min(len(pool), ctx.reference_sample))
        ledgers = await ctx.ledgers(sess, [r["task_id"] for r in rng.sample(
            ok, min(len(ok), t.get("ledger_sample", 0)))])
    return {"requests": done, "window_s": window["t1"] - window["t0"],
            "attempted": len(done), "failed": len(done) - len(ok),
            "lateness_s": resubmit_gaps, "ledgers": ledgers,
            "check_jobs": [{"counter": r["counter"], "result": r["result"]}
                           for r in sample]}
