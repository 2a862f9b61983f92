"""The serve engine's ``moe:load`` ring spans, as the ``.agent`` metric
readers use them.

A model whose expert layers drop no token has its decode step return, behind
the tokens, the experts its live rows touched and the largest expert's load,
each summed over the expert layers; the engine sums them over steps and
every two seconds writes ONE ring span ``moe:load`` whose arguments are the
sums since the last (`ray_tpu/serve/decode_session.py` `_count_moe`)::

    steps            decode steps
    experts_touched  experts that got a pair, summed over layers and steps
    pairs            token-expert pairs routed: live rows x experts a token
                     x expert layers, summed over steps
    load_max         pairs of the fullest expert, summed over layers, steps
    layers, experts  the model's expert layers and experts a layer

A program that writes no such span (a dense model, the parent of the PR
that added it) gives None here and the readers leave their metrics out.
"""

from __future__ import annotations

from typing import Dict, Optional

from perfbench import spans

_SUMS = ("steps", "experts_touched", "pairs", "load_max")


def window_sums(run) -> Optional[Dict[str, float]]:
    """The ``moe:load`` spans that ended inside the window, summed; a
    span argument that was zero is absent from its span."""
    if "_moe_load" not in run.__dict__:
        run._moe_load = _window_sums(run)
    return run._moe_load


def _window_sums(run) -> Optional[Dict[str, float]]:
    t0, t1 = run.stamps["open"], run.stamps["close"]
    out = dict.fromkeys(_SUMS, 0.0)
    for e in spans.ring_spans(run):
        if e.get("name") != "moe:load":
            continue
        if not t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            continue
        args = e.get("args", {})
        for k in _SUMS:
            out[k] += args.get(k, 0)
        out["layers"], out["experts"] = args["layers"], args["experts"]
    return out if out["steps"] else None


def experts_touched_per_layer_step(run) -> Optional[float]:
    s = window_sums(run)
    if s is None:
        return None
    return s["experts_touched"] / (s["steps"] * s["layers"])
