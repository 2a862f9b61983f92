"""loadgen.late_ms.chat: 95th percentile of how late the open loop sent a
request after it was due.
"""

from perfbench import stats
from perfbench.kinds import serve_common


def read(run):
    late = serve_common.loadgen_late(run.raw.get("requests") or [])
    return 1e3 * stats.percentile(late, 95) if late else None
