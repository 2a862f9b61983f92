"""engine.lock_wait_ms_per_step.batch: Milliseconds a decode step of the window that the
engine thread WAITED for the engine's one lock against its callers' threads:
Δ``phase_totals["lock_wait"]`` over Δ``steps`` (``engine.stats()`` at both
edges).  Only an acquisition that had to block is timed
(`ray_tpu/serve/decode_session.py` `_LoopLock`); the seconds lie inside
whichever ``engine:`` phase was open (and at a turn's top under none), a
host annotation ``wait:lock`` in a traced run.  What the CALLERS wait for
the lock is not in it.  A program without the counter gives None.
"""

from perfbench import host_waits


def read(run):
    return host_waits.ms_per_step(run, "lock_wait")
