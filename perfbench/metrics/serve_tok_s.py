"""serve_tok_s: Output tokens the clients received over HTTP inside the window
over the window's length.
"""

from perfbench.kinds import serve_common


def read(run):
    reqs = run.raw.get("requests")
    if reqs is None:
        return None
    s = run.stamps
    return serve_common.tokens_in(reqs, s["open"], s["close"]) / run.window_s
