"""cache.bytes_read_share.longreason: The bytes of cache the decode steps'
live slots attended over what they would attend were every layer a full
layer at the model's WIDEST key-value heads: ``bytes_read`` over
``bytes_if_uniform`` of the window's ``cache:rows`` ring spans, summed
(`ray_tpu/serve/decode_session.py` `_rows_of`: a full layer's rows at what
a full layer holds a position, a window layer's, no more than its window,
at what a ring holds a row).  What the window AND fewer key-value heads on
the full layers save a decode step; 100 for a model of one kind of row.
A program whose spans lack the keys (the parent of the PR that added them)
gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    read_, uniform = 0, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            read_ += args.get("bytes_read", 0)
            uniform += args.get("bytes_if_uniform", 0)
    if not uniform:
        return None
    return 100.0 * read_ / uniform
