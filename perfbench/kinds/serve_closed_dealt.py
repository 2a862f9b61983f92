"""Traffic kind ``serve-closed-dealt``: `serve-closed` (a fixed number of
callers, each sending its next request when its last one is done) with the
order of every caller's prompt lengths dealt by the mix's ``schedule_seed``
and not by the run's seed.  Every seed then times the SAME sequence of sizes
on every caller; the tokens, like the weights, are the seed's.

`serve-closed` lets the seed shuffle each caller's lengths, which is even
enough where a caller's lengths are near each other.  In a mix whose
callers own one short and one four times longer prompt, and finish under
four requests in a window, the seed decides whether the window opens on
70 k or on 116 k tokens of prefill and which of a caller's two sizes the
window's end cuts: ``serve_tok_s`` then spreads by 3.5 % over seeds with
nothing else changed (PERF.md, PR 32).  Parameters: those of
`serve-closed`, and ``schedule_seed``."""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Dict, List

from perfbench import manifest, stats
from perfbench.kinds import serve_common


def plan_for(traffic: Dict[str, Any], config: Dict[str, Any], seed: int
             ) -> List[List[serve_common.Request]]:
    """One list of requests per caller: sizes by ``schedule_seed``, tokens
    by ``seed``."""
    order = random.Random(traffic["schedule_seed"])
    rng = random.Random(seed)
    lengths = serve_common.prompt_lengths(traffic)
    vocab = manifest.family_of(config).shapes.vocab(config)
    per, n = traffic["requests_per_client"], traffic["clients"]
    out = []
    for i in range(n):
        mine = lengths[i::n]
        order.shuffle(mine)
        outs = stats.sizes(traffic["output_tokens"], per, order)
        out.append([serve_common.Request(
            0.0, stats.prompt(rng, mine[j % len(mine)], vocab), outs[j])
            for j in range(per)])
    return out


def run(ctx) -> Dict[str, Any]:
    """`serve_closed.run` over this kind's plan (that one takes no plan:
    a file the benchmark has)."""
    plan = plan_for(ctx.traffic, ctx.config, ctx.seed)

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
