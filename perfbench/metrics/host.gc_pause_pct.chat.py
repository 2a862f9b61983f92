"""host.gc_pause_pct.chat: The chip holder's garbage collections, every generation,
% of the window: 100 x Δ``phase_totals["gc"]`` over the seconds between the
two readings (`ray_tpu/util/tracing.py` `_on_gc`, a ``gc.callbacks`` hook:
the interpreter, and with it the engine loop and every caller's thread,
stands still for a collection).  A collection of 1 ms or more is a ring span
``host:gc`` too, and an annotation in a traced run.  A program without the
counter gives None.
"""

from perfbench import host_waits


def read(run):
    return host_waits.window_pct(run, "gc")
