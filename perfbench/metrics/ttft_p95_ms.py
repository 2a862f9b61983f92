"""ttft_p95_ms: 95th percentile, over the requests due in the window, of the
first token's arrival at the client minus the time the request was DUE; a
request that failed or got no token counts as the window's length.
"""

from perfbench import stats
from perfbench.kinds import serve_common


def read(run):
    reqs = run.raw.get("requests")
    if not reqs:
        return None
    return 1e3 * stats.percentile(
        serve_common.first_token_waits(reqs, run.window_s), 95)
