"""cache.state_bytes_share.reason: The conv layers' states' share of the
slot cache's bytes: ``bytes_state`` over the bytes of all three state kinds
(``bytes_full``, ``bytes_ring``, ``bytes_state``) of the window's last
``cache:rows`` ring span (`ray_tpu/serve/decode_session.py` `_count_rows`).
A state is the last ``conv_kernel - 1`` inputs of a convolution a slot
whatever the context, so the share says how little of the slot cache the
conv layers need, and it falls as ``max_len`` grows.  A program whose span
has no ``bytes_state`` (the parent of the PR that added it, a model without
conv layers: a zero argument is absent from its span) gives None.
"""

from perfbench import spans

_KINDS = ("bytes_full", "bytes_ring", "bytes_state")


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    last = None
    for e in spans.ring_spans(run):
        if e.get("name") == "cache:rows" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            last = e.get("args", {})
    if not last or not last.get("bytes_state"):
        return None
    return 100.0 * last["bytes_state"] / sum(last.get(k, 0) for k in _KINDS)
