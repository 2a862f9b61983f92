"""engine.admit_wait_ms.chat: Mean over the window's requests of the engine's
own queue phase: a start enqueued until its first prefill chunk was
dispatched (engine.stats() phase_totals.queue over the starts handled).
"""

from perfbench import readers


def read(run):
    starts = readers.counters_delta(run, "starts")
    if not starts:
        return None
    c = run.raw["counters"]
    queue = c["after"]["phase_totals"]["queue"] \
        - c["before"]["phase_totals"]["queue"]
    return 1e3 * queue / starts
