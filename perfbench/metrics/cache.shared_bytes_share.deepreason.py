"""cache.shared_bytes_share.deepreason: Of the cache bytes the decode steps'
live slots must move, the share that is ONE SHARED ARRAY: the rows of a full
layer that MORE LAYERS READ THAN HOLD (the layer itself and every ``"cross"``
layer behind it, each its own pass over the slot's depth), and not the window
layers' rings nor the selective scans' states: ``shared_bytes_read`` over
``bytes_read + state_bytes_moved`` of the window's ``cache:rows`` ring spans,
summed (`ray_tpu/models/generate.py` `CacheTraffic.step`: a row set's readers
apart from its holders).  The array is an eighth of that in memory: what a
step reads of it grows with the readers, what a slot holds does not.  A
program whose spans lack the key (the parent of the PR that added it) gives
None, and so does one that counted no such byte (a model whose every row set
is read by the layers that hold it).
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    shared = moved = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            shared += args.get("shared_bytes_read", 0)
            moved += args.get("bytes_read", 0) \
                + args.get("state_bytes_moved", 0)
    if not shared or not moved:
        return None
    return 100.0 * shared / moved
