"""engine.idle_pct.dispatch.chat: Device 0's idle holes of 50 us or more under the engine thread's
``engine:dispatch`` span (upload of token and mask rows when membership
changed, the call of the step program), % of the traced window.
"""

from perfbench import spans


def read(run):
    return spans.engine_idle_pct(run, "dispatch")
