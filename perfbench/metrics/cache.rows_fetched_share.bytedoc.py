"""cache.rows_fetched_share.bytedoc: Of the cache rows the decode steps'
attention MOVED from memory, the share their live slots ATTENDED:
``rows_read`` over ``rows_fetched`` of the window's ``cache:rows`` ring
spans, summed (`ray_tpu/serve/decode_session.py` `_rows_of`,
`ray_tpu/models/generate.py` `rows_fetched`).  Dense dots under a mask move
every row of every slot's arrays whatever the slots' depths (3,840 a slot a
layer where a slot at 5-25 k attends about 1,700); a step that walks the
blocks a slot sees (`ray_tpu/ops/cache_attention.py`) moves those blocks
alone, and what is left under 100 is the blocks' rounding.  A program whose
spans lack the key (the parent of the PR that added it) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    fetched, read_ = None, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            if "rows_fetched" in args:
                fetched = (fetched or 0) + args["rows_fetched"]
            read_ += args.get("rows_read", 0)
    if not fetched:
        return None
    return 100.0 * read_ / fetched
