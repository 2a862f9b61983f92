"""proxy.route_ms.batch: Median over the window's next_chunk calls still in the span files of
the proxy's ``proxy:route`` span: request arrived -> call handed to the replica.
"""

from perfbench import spans


def read(run):
    return spans.hop_median_ms(run, "route")
