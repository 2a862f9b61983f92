"""train.host_gap_ms: Mean device-idle time between consecutive executions of
the train step program, from the trace's module line.
"""

from perfbench import readers, xplane


def read(run):
    if run.trace is None:
        return None
    gap = xplane.program(run.trace, readers.TRAIN_STEP)["mean_gap_s"]
    return None if gap is None else 1e3 * gap
