"""ssm.state_bytes_share.chat: Of the cache bytes the decode steps' live
slots must MOVE, the share that is STATE-SPACE STATE (a mixer's float32
matrix a head and its convolution's last inputs, read whole and written
whole every step whatever the slot's depth) and not the keys and values the
SAME layers attend: ``state_bytes_moved`` over ``state_bytes_moved +
bytes_read`` of the window's ``cache:rows`` ring spans, summed
(`ray_tpu/serve/decode_session.py` `_state_rows_of`, `_rows_of`).  It falls
as contexts grow: the state is constant, the rows are not (at 18 KB a
position against 76 MB a slot moved, they meet past 4 k).  A program whose
spans carry no ``bytes_ssm`` (a model without such layers, the parent of the
PR that added the kind) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    state, rows = None, 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            if args.get("bytes_ssm") and "state_bytes_moved" in args:
                state = (state or 0) + args["state_bytes_moved"]
            rows += args.get("bytes_read", 0)
    if not state:
        return None
    return 100.0 * state / (state + rows)
