"""Traffic kind ``serve-open``: requests arrive on a schedule whatever the
system does (independent users).  Parameters (traffic file): ``rate_rps``
(fixed; found once by tools/sweep.py), ``schedule_seed`` (the one order of
gaps and sizes every run of the mix times: with the order left to ``--seed``
the 95th percentiles moved by 12 % from seed to seed and by 2 % between two
runs of one seed, PERF.md PR 24; ``--seed`` still makes the tokens and the
weights), ``prompt_tokens``,
``output_tokens``, ``distinct_prompt_lengths``, ``drain_s`` (how long after
the window's end requests due inside it may still finish)."""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Dict

from perfbench import stats
from perfbench.kinds import serve_common


def schedule(traffic: Dict[str, Any], config: Dict[str, Any], seed: int,
             seconds: float, rate: float):
    offsets = stats.poisson_arrivals(
        rate, seconds,
        random.Random(traffic.get("schedule_seed", seed ^ 0x5EED)))
    return serve_common.make_requests(traffic, config, seed, offsets)


async def drive_open(client, requests, t_open: float, t_close: float,
                     drain_s: float):
    """Send each request at t_open + its offset, never earlier and as
    little later as the loop allows; wait for them up to ``drain_s`` past
    the window's end."""
    tasks = []
    for r in requests:
        r.due = t_open + r.due
        wait = r.due - time.time()
        if wait > 0:
            await asyncio.sleep(wait)
        tasks.append(asyncio.ensure_future(
            client.request(r, t_close + drain_s)))
    if tasks:
        _, late = await asyncio.wait(
            tasks, timeout=max(0.0, t_close + drain_s + 5.0 - time.time()))
        for task in late:
            task.cancel()
        for r in requests:
            if r.done is None and r.error is None:
                r.error = "not finished when the drain ended"
    return requests


def _sweep_row(rate: float, seconds: float, reqs, t0: float
               ) -> Dict[str, Any]:
    """One rate of tools/sweep.py, reduced."""
    waits = serve_common.first_token_waits(reqs, seconds)
    third = max(1, len(reqs) // 3)
    done = [r.done for r in reqs if r.done is not None]
    return {"rate_rps": rate, "requests": len(reqs),
            "failed": serve_common.failed(reqs),
            "ttft_p50_ms": 1e3 * stats.percentile(waits, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(waits, 95),
            "ttft_p50_first_third_ms":
                1e3 * stats.percentile(waits[:third], 50),
            "ttft_p50_last_third_ms":
                1e3 * stats.percentile(waits[-third:], 50),
            "gap_p95_ms": 1e3 * stats.percentile(
                serve_common.token_waits(reqs) or [0.0], 95),
            "tokens_per_s": serve_common.tokens_in(
                reqs, t0, t0 + seconds) / seconds,
            "drained_after_s": (max(done) - (t0 + seconds)) if done
            else None}


def run(ctx) -> Dict[str, Any]:
    t = ctx.traffic
    sweep = ctx.extra.get("sweep")
    plan = schedule(t, ctx.config, ctx.seed, ctx.seconds, t["rate_rps"])
    rows = []

    async def drive(client, t_open, t_close):
        if not sweep:
            return await drive_open(client, plan, t_open, t_close,
                                    t["drain_s"])
        for rate in sweep["rates"]:
            t0 = time.time()
            reqs = await drive_open(
                client, schedule(t, ctx.config, ctx.seed, sweep["seconds"],
                                 rate),
                t0, t0 + sweep["seconds"], t["drain_s"])
            rows.append(_sweep_row(rate, sweep["seconds"], reqs, t0))
            await asyncio.sleep(1.0)
        return []

    out = serve_common.serve_cell(ctx, drive)
    if "rows" in out:
        return out
    if sweep:
        return dict(out, sweep=rows)
    out["attempted"] = len(out["requests"])
    out["failed"] = serve_common.failed(out["requests"])
    return out
