"""engine.batch_mean.batch: engine.stats(): tokens emitted over decode steps
run, between the window's opening and its end.
"""

from perfbench import readers


def read(run):
    steps = readers.counters_delta(run, "steps")
    if not steps:
        return None
    return readers.counters_delta(run, "tokens") / steps
