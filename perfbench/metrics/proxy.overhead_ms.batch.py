"""proxy.overhead_ms.batch: Median over the window's next_chunk calls of the
client's time for the call minus the time the replica spent in core.handle
for it: proxy, router, transport and queueing in the replica.
"""

import statistics


def read(run):
    reqs = run.raw.get("requests")
    if not reqs:
        return None
    over = [c - s for r in reqs for op, c, s in r.calls
            if op == "next_chunk" and s is not None]
    return 1e3 * statistics.median(over) if over else None
