"""What the host's turn waited for, as the ``engine.lock_wait_ms_per_step``,
``engine.schedule_blocked_share``, ``engine.long_read_ms``,
``host.gc_pause_pct``, ``host.late_wakeup_ms`` and ``loadgen.late_wakeup_ms``
metric readers use it.

Counters: keys of the engine's ``phase_totals`` (cumulative seconds), which
the benchmark takes at both edges of the window beside ``steps`` and its
clock ``t`` (`kinds/serve_common.py` `_op_counters`)::

    lock_wait      the engine thread's waits for the engine's one lock
                   (`ray_tpu/serve/decode_session.py` `_LoopLock`: only an
                   acquisition that had to block is timed)
    schedule       wall seconds of its ``engine:schedule`` phase, and
    schedule_cpu   the thread's own CPU seconds of that phase
    long_read      reads of a step's tokens that took 250 ms or more
    gc             the replica PROCESS's garbage collections, every
                   generation (`ray_tpu/util/tracing.py` `_on_gc`)
    late_wakeup    its watch thread's late wake-ups (read as the sign that
                   the program has the watch at all)

Ring spans: ``host:late_wakeup`` (category ``host``, argument ``late_ms``),
one for each time a process's watch thread, which sleeps 20 ms, woke 50 ms
or more late: the interpreter was held (a collection, a long C call) or the
host did not run the process.  Every process of the session has a watch and
a span file; ``tid`` is the recording process's pid as text.

A program without the counters (the parent of the PR that added them) gives
None from every function here, and the readers leave their metrics out.
"""

from __future__ import annotations

import os
from typing import Optional

from perfbench import readers, spans


def ms_per_step(run, key: str) -> Optional[float]:
    """Milliseconds of ``phase_totals[key]`` a decode step of the window."""
    steps = readers.counters_delta(run, "steps")
    secs = spans.phase_delta(run, key)
    if not steps or secs is None:
        return None
    return 1e3 * secs / steps


def window_ms(run, key: str) -> Optional[float]:
    """Milliseconds of ``phase_totals[key]`` in the window."""
    secs = spans.phase_delta(run, key)
    return None if secs is None else 1e3 * secs


def window_pct(run, key: str) -> Optional[float]:
    """Seconds of ``phase_totals[key]`` in the window, % of the seconds
    between the two readings of the counters."""
    secs = spans.phase_delta(run, key)
    if secs is None:
        return None
    return 100.0 * secs / readers.counters_delta(run, "t")


def schedule_blocked_share(run) -> Optional[float]:
    """Of the wall seconds of ``engine:schedule``, the % that were not the
    engine thread's own CPU seconds.  The phase holds the engine's lock
    throughout and dispatches nothing, so the rest is time the thread was
    runnable and not running: it waited for the interpreter against the
    callers' threads, or the host did not run it."""
    wall = spans.phase_delta(run, "schedule")
    cpu = spans.phase_delta(run, "schedule_cpu")
    if not wall or cpu is None:
        return None
    return 100.0 * (wall - cpu) / wall


def late_wakeup_ms(run, pid) -> Optional[float]:
    """``late_ms`` summed over process ``pid``'s ``host:late_wakeup`` spans
    that end in the window; 0 for a quiet process of a program that has
    the watch."""
    if spans.phase_delta(run, "late_wakeup") is None:
        return None
    t0, t1 = run.stamps["open"], run.stamps["close"]
    return sum(e.get("args", {}).get("late_ms", 0.0)
               for e in spans.ring_spans(run)
               if e.get("name") == "host:late_wakeup"
               and e.get("tid") == str(pid)
               and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1)


def holder_late_wakeup_ms(run) -> Optional[float]:
    """... of the chip holder: the replica's interpreter or its host."""
    return late_wakeup_ms(run, run.worker["pid"])


def driver_late_wakeup_ms(run) -> Optional[float]:
    """... of the benchmark's own process, which is the runtime's driver
    and the load generator (the readers run in it)."""
    return late_wakeup_ms(run, os.getpid())
