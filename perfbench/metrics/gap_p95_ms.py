"""gap_p95_ms: 95th percentile, over every output token after a request's
first, of the wait since the previous token of the same request as the
client saw it; tokens that arrive in one chunk share that chunk's wait
equally.  Read per layer, beside ttft_p95_ms: between two runs of one seed
it moves by 4-7 %, more than half of the widest bound an end-to-end metric
may have.
"""

from perfbench import stats
from perfbench.kinds import serve_common


def read(run):
    waits = serve_common.token_waits(run.raw.get("requests") or [])
    if not waits:
        return None
    return 1e3 * stats.percentile(waits, 95)
