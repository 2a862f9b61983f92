"""cache.ring_latent_bytes_share.notes: Of the bytes of cache the decode
steps' live slots must read, the share that is a window layer's RING OF
LATENTS (a live slot reads ``min(pos + 1, window)`` rows of ``kv_lora + rope``
values on each window layer, at the ring's own row width) and not the full
layers' chosen latents nor their index keys: ``ring_latent_bytes_read`` over
``bytes_read`` of the window's ``cache:rows`` ring spans, summed
(`ray_tpu/serve/decode_session.py` `_ring_latent_bytes`, `_chosen_rows_of`).
It falls as the context grows: the ring's rows stop at the window, the index
keys do not.  A program whose spans lack the key (the parent of the PR that
added it) gives None, and so does one that counted no such byte (a model
without window layers over a latent cache).
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    ring = read_ = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            ring += args.get("ring_latent_bytes_read", 0)
            read_ += args.get("bytes_read", 0)
    if not ring or not read_:
        return None
    return 100.0 * ring / read_
