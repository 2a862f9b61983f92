"""loadgen.late_wakeup_ms.chat: Milliseconds by which the watch thread of the
benchmark's OWN process (the runtime's driver, whose asyncio loop is the open
loop's load generator) woke 50 ms or more late, summed over the window:
``late_ms`` of that process's ``host:late_wakeup`` ring spans that end in it.
Beside ``loadgen.late_ms.chat``: a request that left late while this reads
about as much was held by the load generator's process or its host, not by
the system under test.  A program without the watch gives None.
"""

from perfbench import host_waits


def read(run):
    return host_waits.driver_late_wakeup_ms(run)
