"""engine.first_token_ms.chat: Mean over the window's requests of the time inside the engine from a
start enqueued to its first token existing (``phase_totals.first_token`` per
start handled): the engine's part of the time to first token.
"""

from perfbench import readers, spans


def read(run):
    starts = readers.counters_delta(run, "starts")
    first = spans.phase_delta(run, "first_token")
    if not starts or first is None:
        return None
    return 1e3 * first / starts
