"""moe.zero_pairs_share.assist: Of the token-output pairs the window's decode
steps CHOSE under a router with identity experts (``chosen``: live rows x
experts a token x layers that route, summed over steps), the share that chose
an IDENTITY expert, which computes nothing (``zero_pairs``): the two sums of
the engine's ``moe:load`` ring spans that ended inside the window
(`ray_tpu/serve/decode_session.py` `_count_moe`; `ray_tpu/ops/moe.py`
`routed_ffn`'s ``identity_from``).  About a third where the routers hold the
published mean of 8 real experts of 12 a token: a guard on the family's
`make` (the balanced bias) and on the counters.  A program whose spans lack
the keys (a router without identity outputs; the parent of the PR that added
them) gives None.
"""

from perfbench import spans


def read(run):
    t0, t1 = run.stamps["open"], run.stamps["close"]
    zero = chosen = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "moe:load" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            args = e.get("args", {})
            zero += args.get("zero_pairs", 0)
            chosen += args.get("chosen", 0)
    if not chosen:
        return None
    return 100.0 * zero / chosen
