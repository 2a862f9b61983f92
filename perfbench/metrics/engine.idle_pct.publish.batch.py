"""engine.idle_pct.publish.batch: Device 0's idle holes of 50 us or more under the engine thread's
``engine:publish`` span (counters, tokens onto the sessions' queues under the
lock, the wake-up of waiting callers), % of the traced window.
"""

from perfbench import spans


def read(run):
    return spans.engine_idle_pct(run, "publish")
