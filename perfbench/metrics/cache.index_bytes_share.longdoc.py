"""cache.index_bytes_share.longdoc: Of the bytes of cache the decode steps'
live slots must read, the share that is INDEX KEYS (one key of
``index_head_dim`` values for every position at or before the query, on the
indexing layers alone: what the choice costs) and not the chosen latents:
``index_bytes_read`` over ``bytes_read`` of the window's ``cache:rows`` ring
spans, summed (`ray_tpu/serve/decode_session.py` `_rows_of`).  It grows with
the context: the latents read stop at ``index_topk`` rows a layer, the index
keys do not.  A program whose spans lack the key (a model without an indexer,
the parent of the PR that added it) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    keys, read_ = None, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            if "index_bytes_read" in args:
                keys = (keys or 0) + args["index_bytes_read"]
            read_ += args.get("bytes_read", 0)
    if keys is None or not read_:
        return None
    return 100.0 * keys / read_
