"""proxy.reply_ms.batch: Median over the window's next_chunk calls still in the span files of
the proxy's ``proxy:request`` end less the replica's ``serve_exec::`` end, joined
on the request id: the result's way back and the response written.
"""

from perfbench import spans


def read(run):
    return spans.hop_median_ms(run, "reply")
