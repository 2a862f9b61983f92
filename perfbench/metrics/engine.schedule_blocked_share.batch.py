"""engine.schedule_blocked_share.batch: Of the wall seconds of the engine thread's
``engine:schedule`` phase in the window, the % that were NOT the thread's own
CPU seconds: 100 x (Δ``schedule`` - Δ``schedule_cpu``) / Δ``schedule`` of
``phase_totals``.  The phase holds the lock throughout and dispatches
nothing: near 0 it is the loop's own work (cure: hide it behind a second
queued turn, or make it less); near 100 the loop stands runnable behind the
callers' threads for the interpreter, or off the CPU (cure: the callers).
A program without ``schedule_cpu`` gives None.
"""

from perfbench import host_waits


def read(run):
    return host_waits.schedule_blocked_share(run)
