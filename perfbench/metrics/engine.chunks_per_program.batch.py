"""engine.chunks_per_program.batch: Chunks of prompts the engine consumed over
the chunk programs that consumed them: ``chunks`` / ``programs`` summed over
the ``engine:lanes`` ring spans that end in the window
(`ray_tpu/serve/decode_session.py` `_count_chunks`: ONE span every 2 s with
the sums since the last, as ``engine:ahead``; a span argument that was zero
is absent from its span).  1.0: every program advanced one session, the
traffic never had two prompts prefilling at once (or the engine speculates);
near the engine's lanes (``stats()["prefill_lanes"]``): the lanes program ran
full, each weight read once for that many sessions' chunks.  A program that
writes no such span (the parent of the PR that added it) gives None, and so
does a window in which no prompt was prefilled.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    programs = chunks = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "engine:lanes" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            programs += args.get("programs", 0)
            chunks += args.get("chunks", 0)
    return chunks / programs if programs else None
