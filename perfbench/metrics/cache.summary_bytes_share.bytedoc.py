"""cache.summary_bytes_share.bytedoc: Of the bytes of cache the decode steps'
live slots attended, the share that was SUMMARY rows (a pooled key and value
a chunk of 16 positions, one for every chunk of the windows before a slot's
own) and not ring rows (the slot's own window, exact): ``summary_bytes_read``
over ``bytes_read`` of the window's ``cache:rows`` ring spans, summed
(`ray_tpu/serve/decode_session.py` `_rows_of`).  It grows with the context:
what a full layer would read sixteen rows for.  A program whose spans lack
the key (the parent of the PR that added it) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    pooled, read_ = None, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            if "summary_bytes_read" in args:
                pooled = (pooled or 0) + args["summary_bytes_read"]
            read_ += args.get("bytes_read", 0)
    if pooled is None or not read_:
        return None
    return 100.0 * pooled / read_
