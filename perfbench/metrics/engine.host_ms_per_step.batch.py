"""engine.host_ms_per_step.batch: Engine-thread milliseconds per decode step in the phases no device
work hides: schedule + dispatch + publish of ``phase_totals`` over the steps of
the window (``engine.stats()`` deltas).
"""

from perfbench import readers, spans


def read(run):
    steps = readers.counters_delta(run, "steps")
    host = spans.phase_delta(run, "schedule", "dispatch", "publish")
    if not steps or host is None:
        return None
    return 1e3 * host / steps
