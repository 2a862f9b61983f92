"""kda.state_bytes_share.think: Of the cache bytes the decode steps' live
slots must MOVE, the share that is DELTA STATE (a KDA layer's float32 matrix
a head and its convolutions' last inputs, read whole and written whole every
step whatever the slot's depth) and not cached latents of the full layers:
``state_bytes_moved`` over ``state_bytes_moved + bytes_read`` of the window's
``cache:rows`` ring spans, summed (`ray_tpu/serve/decode_session.py`
`_state_rows_of`, `_rows_of`).  It falls as contexts grow: the state is
constant, the latents are not.  A program whose spans lack the key (a model
without KDA layers, the parent of the PR that added it: a zero argument is
absent from its span) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    state, rows = None, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            if "state_bytes_moved" in args:
                state = (state or 0) + args["state_bytes_moved"]
            rows += args.get("bytes_read", 0)
    if not state:
        return None
    return 100.0 * state / (state + rows)
