"""engine.long_read_ms.batch: Milliseconds of the window in reads of a decode
step's tokens that took 250 ms or more (an ordinary turn is under 100):
1e3 x Δ``phase_totals["long_read"]``.  0 in a run that did not stall.  Each
such read is a ring span ``engine:long_read`` that says how many late
wake-ups of the watch thread stood beside it: none = the device or the
transfer, some = the interpreter or the host
(`python3 -m perfbench.tools.stalls`).  A program without the counter gives
None.
"""

from perfbench import host_waits


def read(run):
    return host_waits.window_ms(run, "long_read")
