"""replica.queue_ms.batch: Median over the window's next_chunk calls still in the span files of
the replica's ``serve_queue::`` span: call handed over by the router -> the
replica starts on it (transport, the actor's queue).
"""

from perfbench import spans


def read(run):
    return spans.hop_median_ms(run, "queue")
