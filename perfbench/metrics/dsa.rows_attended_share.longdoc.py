"""dsa.rows_attended_share.longdoc: Latent rows the decode steps' live slots
ATTENDED over the rows full attention would have: ``rows_read`` over
``rows_if_full`` of the window's ``cache:rows`` ring spans, summed
(`ray_tpu/serve/decode_session.py` `_rows_of`: a layer under an indexer's
choice attends ``min(t + 1, index_topk)`` rows of a slot at position ``t``).
6-25 % by the cell's traffic (2048 chosen of 8-33 k), 100 % where the
selection is lost.  Only the spans of a model with an indexer carry
``index_rows_read``; a program whose spans lack the key (a model without an
indexer, the parent of the PR that added it) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    indexed, rows, full = False, 0, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            indexed = indexed or "index_rows_read" in args
            rows += args.get("rows_read", 0)
            full += args.get("rows_if_full", 0)
    if not indexed or not full:
        return None
    return 100.0 * rows / full
