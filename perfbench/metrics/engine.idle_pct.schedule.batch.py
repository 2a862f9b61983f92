"""engine.idle_pct.schedule.batch: Device 0's idle holes of 50 us or more under the engine thread's
``engine:schedule`` span (reap, metrics push, admit and collect under the
lock, the batch's token and mask rows), % of the traced window.
"""

from perfbench import spans


def read(run):
    return spans.engine_idle_pct(run, "schedule")
