"""engine.idle_pct.readback.batch: Device 0's idle holes of 50 us or more under the engine thread's
``engine:readback`` span (the step's tokens copied to the host), % of the
traced window.
"""

from perfbench import spans


def read(run):
    return spans.engine_idle_pct(run, "readback")
