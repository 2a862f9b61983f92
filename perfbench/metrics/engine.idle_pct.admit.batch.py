"""engine.idle_pct.admit.batch: Device 0's idle holes of 50 us or more under the engine thread's
``engine:admit`` span (slot inserts, one chunk program per joining session,
the first-token read), % of the traced window.
"""

from perfbench import spans


def read(run):
    return spans.engine_idle_pct(run, "admit")
