"""kda.state_fetched_share.think: Of the bytes of delta state the decode
steps' programs MOVED, the share the rule REQUIRES: each live slot's states
once read and once written, ``state_bytes_moved`` over ``state_bytes_fetched``
of the window's ``cache:rows`` ring spans, summed
(`ray_tpu/serve/decode_session.py` `_state_rows_of`,
`ray_tpu/models/generate.py` `state_fetched`).  XLA's form of the step reads
every slot's states twice and writes them once, live or not (about 63 at a
batch of 30 of 32); a step that advances a live slot's states where they lie
in one pass (`ray_tpu/ops/delta_rule.py` `step_in_place`) moves those alone,
100.  A program whose spans lack the key (a model without KDA layers, the
parent of the PR that added it) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    fetched, moved = None, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            if "state_bytes_fetched" in args:
                fetched = (fetched or 0) + args["state_bytes_fetched"]
            moved += args.get("state_bytes_moved", 0)
    if not fetched:
        return None
    return 100.0 * moved / fetched
