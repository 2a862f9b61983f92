"""engine.ahead_share.batch: Fused decode steps the engine dispatched while
the step before them had not been read, % of the fused steps it dispatched:
100 x ``steps_ahead`` / ``steps`` summed over the ``engine:ahead`` ring
spans that end in the window (`ray_tpu/serve/decode_session.py`
`_dispatch`: ONE span every 2 s with the sums since the last, as
``cache:rows``; a span argument that was zero is absent from its span).
Near 100 the chip always has its next step queued while the host reads,
publishes and schedules; near 0 the loop runs in turn.  A program that
writes no such span (the parent of the PR that added it) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    steps = ahead = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "engine:ahead" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            steps += args.get("steps", 0)
            ahead += args.get("steps_ahead", 0)
    return 100.0 * ahead / steps if steps else None
