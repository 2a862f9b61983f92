"""cache.chunk_rows_fetched_share.batch: Of the cache rows the CHUNK
programs' attention MOVED from memory, the share that a real query of
theirs SAW: ``chunk_rows_read`` over ``chunk_rows_fetched`` of the window's
``engine:lanes`` ring spans, summed (`ray_tpu/serve/decode_session.py`
`_count_chunks`, `ray_tpu/models/generate.py` `chunk_rows_fetched`).  Dense
dots under a mask move every row of a lane's arrays a layer whatever the
lane's position (a full layer's 16,896 where the mean prompt is 5.5 k); a
program that walks the blocks some query of a lane's chunk sees
(`ray_tpu/ops/cache_attention.py` `attend_chunk_blocks`) moves those blocks
alone, and what is left under 100 is the blocks' rounding, a padded
chunk's rows and the layers that stay dense (a kind with an attention
sink).  A program whose spans lack the keys (the parent of the PR that
added them, a model of latent layers) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    fetched = seen = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "engine:lanes" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            fetched += args.get("chunk_rows_fetched", 0)
            seen += args.get("chunk_rows_read", 0)
    return 100.0 * seen / fetched if fetched else None
