"""Open loop: requests are due on a fixed schedule at a FIXED rate, whether
or not earlier ones have ended (independent users).

Traffic parameters: ``path``, ``mode`` (``async`` = submit → long-poll →
result, ``sync`` = one call), ``rate_per_s``, ``ramp_s`` (arrivals at the
cell's rate before the window, until occupancy has settled), ``drain_cap_s``
(how long after the window requests due inside it are followed; what has not
ended by then has failed), ``payload``, ``schedule_seed``, ``rotation``
(optional: the point of the cycle at which EVERY seed's window starts, for a
mix where the starting point itself changes the work; without it ``--seed``
chooses the point), and for prompts
``prompt_len`` / ``max_new_tokens`` (log-normal ``median``, ``sigma``,
``lo``, ``hi``).

The schedule is NOT a Poisson draw per run. It is one fixed sequence: the
gaps are the mid-quantiles of the exponential distribution at the rate (what a
Poisson process's gaps are distributed as) and the lengths the mid-quantiles
of their log-normals (``lib.stats.stratified``), each shuffled once by the
mix's own ``schedule_seed`` — so the bursts and lulls are those of that one
order. ``--seed`` makes the request bodies and ROTATES the window's arrivals:
every seed offers the same requests with the same neighbours, starting at
another point of the cycle. A tail reported here is therefore the tail of
this one order under every rotation, not of Poisson traffic at large.
(Measured, PR 23: with a free shuffle per seed the p95 of the normalised
latency swung by 20 % between seeds at one rate, because each order builds its
own bursts; that is the seed changing the work.) Latency runs from the instant
a request was DUE, not from when the generator got round to sending it; how
late sends were is reported beside it.
"""

from __future__ import annotations

import asyncio
import random

from benchmark.lib import client, stats


def schedule(traffic: dict, seconds: float, seed: int) -> list[dict]:
    """Arrivals of the ramp then the window: ``due`` (seconds from the ramp's
    start), ``in_window``, and the lengths where the payload has them."""
    rng = random.Random(traffic.get("schedule_seed", 0))
    rate = traffic["rate_per_s"]
    out, start = [], 0.0
    for phase, span in (("ramp", traffic["ramp_s"]), ("window", seconds)):
        n = max(1, round(rate * span)) if span > 0 else 0
        gaps = stats.exponential_gaps(n, rate) if n else []
        rng.shuffle(gaps)
        cols = {}
        for key in ("prompt_len", "max_new_tokens"):
            if key in traffic:
                p = traffic[key]
                cols[key] = stats.lognormal_lengths(
                    n, p["median"], p["sigma"], p["lo"], p["hi"])
                rng.shuffle(cols[key])
        # Scale so the phase's arrivals span exactly its seconds.
        scale = span / sum(gaps) * (n / (n + 0.5)) if n else 1.0
        order = list(range(n))
        if phase == "window" and n:
            k = traffic.get("rotation", seed) % n
            order = order[k:] + order[:k]
        for i, due in zip(order, stats.due_times(
                [gaps[i] * scale for i in order], start)):
            out.append({"due": due, "in_window": phase == "window",
                        **{k: v[i] for k, v in cols.items()}})
        start += span
    for i, a in enumerate(out):
        a["counter"] = i
    return out


async def run(ctx) -> dict:
    t = ctx.traffic
    payloads = ctx.payloads()
    arrivals = schedule(t, ctx.seconds, ctx.seed)
    call = client.async_task if t.get("mode", "async") == "async" \
        else client.sync_call
    records: list[dict] = []

    async def one(sess, a: dict, due_abs: float, hard_stop: float) -> None:
        if "prompt_len" in a:
            body = payloads.body(a["counter"], a["prompt_len"],
                                 a["max_new_tokens"])
        else:
            body = payloads.body(a["counter"])
        a["late_s"] = ctx.now() - due_abs
        out = await call(sess, ctx.cp_base, t["path"], body,
                         payloads.content_type, deadline=hard_stop)
        t_end = ctx.now()
        ok = out["ok"] and ctx.valid(out["result"], a)
        records.append({**a, "end": t_end, "ok": ok,
                        "latency_s": t_end - due_abs,
                        "task_id": out["task_id"],
                        "result": out["result"] if ok else None,
                        "error": None if ok else (out["error"]
                                                  or "invalid result")})

    async with client.session() as sess:
        origin = ctx.now()
        t0 = origin + t["ramp_s"]
        hard_stop = t0 + ctx.seconds + t["drain_cap_s"]
        tasks, opened = {}, False
        try:
            for a in arrivals:
                due_abs = origin + a["due"]
                if a["in_window"] and not opened:
                    await asyncio.sleep(max(0.0, t0 - ctx.now()))
                    await ctx.window_start([
                        b["due"] - t["ramp_s"] for b in arrivals
                        if b["in_window"]])
                    opened = True
                await asyncio.sleep(max(0.0, due_abs - ctx.now()))
                tasks[a["counter"]] = asyncio.ensure_future(
                    one(sess, a, due_abs, hard_stop))
            await asyncio.sleep(max(0.0, t0 + ctx.seconds - ctx.now()))
            await ctx.window_end()
            pending = [f for f in tasks.values() if not f.done()]
            if pending:
                await asyncio.wait(pending, timeout=max(
                    0.0, hard_stop - ctx.now()) + 1.0)
        finally:
            for f in tasks.values():
                f.cancel()
            await asyncio.gather(*tasks.values(), return_exceptions=True)
        ended = {r["counter"] for r in records}
        end_abs = ctx.now()
        for a in arrivals:   # never ended: failed, censored at the drain's end
            if a["counter"] not in ended:
                records.append({**a, "end": end_abs, "ok": False,
                                "latency_s": end_abs - (origin + a["due"]),
                                "task_id": None, "result": None,
                                "error": "not ended within drain_cap_s"})
        inside = [r for r in records if r["in_window"]]
        ok = [r for r in inside if r["ok"]]
        rng = random.Random(ctx.seed)
        pool = [r for r in ok if ctx.reference_eligible(r)]
        sample = rng.sample(pool, min(len(pool), ctx.reference_sample))
        ledgers = await ctx.ledgers(sess, [r["task_id"] for r in rng.sample(
            ok, min(len(ok), t.get("ledger_sample", 0)))])
    t1 = t0 + ctx.seconds

    def in_flight(at: float) -> int:
        return sum(1 for r in records
                   if origin + r["due"] <= at < r["end"])

    return {"requests": inside, "window_s": ctx.seconds,
            "in_flight": {"at_open": in_flight(t0), "at_close": in_flight(t1)},
            "attempted": len(inside), "failed": len(inside) - len(ok),
            "lateness_s": [r["late_s"] for r in inside if "late_s" in r],
            "ledgers": ledgers,
            "all_requests": records,
            "check_jobs": [{"counter": r["counter"],
                            "prompt_len": r.get("prompt_len"),
                            "result": r["result"]} for r in sample]}
