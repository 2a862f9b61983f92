"""cache.latent_rows_fetched_share.batch: Of the cache rows a latent model's
attention MOVED from memory, in its decode steps AND its chunk programs, the
share a real query of theirs SAW: ``rows_read + chunk_rows_read`` over
``rows_fetched + chunk_rows_fetched`` of the window's ``cache:rows`` and
``engine:lanes`` ring spans, summed (`ray_tpu/serve/decode_session.py`
`_rows_of`, `_count_chunks`; `ray_tpu/models/generate.py` `rows_fetched`,
`chunk_rows_fetched`).  Dense dots under a mask move every row of every
slot's (of a lane's) layer whatever stands in it: 5632 rows a slot where a
thinking slot holds about 2.7 k, and a slot that stands as many.  A program
that walks the blocks a slot's or a lane's queries see
(`ray_tpu/ops/latent_attention.py` `attend_cache`) moves those blocks alone;
what is left under 100 is the blocks' rounding, a padded chunk's rows, the
chunk programs of a model whose heads fill no head tile, which stay dense,
and, under an indexer's choice, every row before a query that it did NOT
choose (the kernel walks up to the last chosen row).  A program whose spans
lack the keys (the parent of the PR that counted a step's rows) gives None.
"""

from perfbench import spans

_SUMS = {"cache:rows": ("rows_fetched", "rows_read"),
         "engine:lanes": ("chunk_rows_fetched", "chunk_rows_read")}


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    fetched = seen = 0
    for e in spans.ring_spans(run):
        keys = _SUMS.get(e.get("name"))
        if keys and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            fetched += args.get(keys[0], 0)
            seen += args.get(keys[1], 0)
    return 100.0 * seen / fetched if fetched else None
