"""engine.prefill_tail_share.chat: Share of the engine thread's prefill seconds spent in chunk programs
of ONE token, a prompt's tail (``phase_totals.prefill_tail`` over
``phase_totals.prefill``, window deltas), %.
"""

from perfbench import spans


def read(run):
    tail = spans.phase_delta(run, "prefill_tail")
    prefill = spans.phase_delta(run, "prefill")
    if tail is None or not prefill:
        return None
    return 100.0 * tail / prefill
