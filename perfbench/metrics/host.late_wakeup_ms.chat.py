"""host.late_wakeup_ms.chat: Milliseconds by which the chip holder's watch thread,
which sleeps 20 ms, woke 50 ms or more late, summed over the window: ``late_ms``
of the ``host:late_wakeup`` ring spans of that process (``tid`` = the chip
holder's pid) that end in it.  The interpreter was held (a full collection, a
long C call) or the host did not run the process.  0 in a quiet window; a
program without the watch gives None.
"""

from perfbench import host_waits


def read(run):
    return host_waits.holder_late_wakeup_ms(run)
