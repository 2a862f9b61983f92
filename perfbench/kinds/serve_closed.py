"""Traffic kind ``serve-closed``: a fixed number of callers, each sending
its next request when its last one is done.  Parameters (traffic file):
``clients``, ``requests_per_client`` (more than a window can use),
``prompt_tokens``, ``output_tokens``, ``distinct_prompt_lengths``.

Caller i owns every ``clients``-th of the mix's prompt lengths and goes
through them again and again in an order the seed shuffles.  With as many
lengths per caller as a caller finishes requests in a window, every seed's
window holds the same requests, in another order, up to the one each caller
is cut off in (dealing all lengths to all callers at random made
``serve_tok_s`` differ by 2 % from seed to seed and by 0.2 % between two
runs of one seed: PERF.md, PR 24)."""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Dict, List

from perfbench import manifest, stats
from perfbench.kinds import serve_common


def plan_for(traffic: Dict[str, Any], config: Dict[str, Any], seed: int
             ) -> List[List[serve_common.Request]]:
    """One list of requests per caller."""
    rng = random.Random(seed)
    lengths = serve_common.prompt_lengths(traffic)
    vocab = manifest.family_of(config).shapes.vocab(config)
    per, n = traffic["requests_per_client"], traffic["clients"]
    out = []
    for i in range(n):
        mine = lengths[i::n]
        rng.shuffle(mine)
        outs = stats.sizes(traffic["output_tokens"], per, rng)
        out.append([serve_common.Request(
            0.0, stats.prompt(rng, mine[j % len(mine)], vocab), outs[j])
            for j in range(per)])
    return out


def run(ctx) -> Dict[str, Any]:
    t = ctx.traffic
    plan = plan_for(t, ctx.config, ctx.seed)

    async def drive(client, t_open, t_close):
        made = []

        async def caller(mine):
            for r in mine:
                if time.time() >= t_close:
                    return
                r.due = time.time()
                made.append(r)
                await client.request(r, t_close)

        await asyncio.gather(*(caller(mine) for mine in plan))
        return made

    out = serve_common.serve_cell(ctx, drive)
    if "rows" in out:
        return out
    reqs = out["requests"]
    # a request the window's end cut short is not a failure
    out["attempted"] = len(reqs)
    out["failed"] = sum(1 for r in reqs if r.error is not None)
    return out
