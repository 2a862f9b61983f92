"""prefill.tail_rows_share.deepreason: Rows the STATELESS TAIL of the chunk
programs ran over the rows those programs fed: the layers behind the last one
that holds state (gated memory units and cross layers: they write nothing a
later token reads) run on ONE row a lane, the row whose logits the program
hands out, where the layers before them run on all of a chunk's rows
(`ray_tpu/models/generate.py` `_tail_on_one_row`): ``tail_rows`` over
``rows_fed`` summed over the ``engine:lanes`` ring spans that end in the
window (`ray_tpu/serve/decode_session.py` `_count_chunks`).  1 / 128 (0.78)
where the tail is cut at a chunk of 128; 100 is the uncut form.  A program
that writes no such argument (a model without such a tail, the parent of the
PR that added it) gives None, and so does a window in which no prompt was
prefilled.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    tail = fed = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "engine:lanes" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            tail += args.get("tail_rows", 0)
            fed += args.get("rows_fed", 0)
    if not tail or not fed:
        return None
    return 100.0 * tail / fed
