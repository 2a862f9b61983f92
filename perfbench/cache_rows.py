"""The serve engine's ``cache:rows`` ring spans, as the ``.mixed`` metric
readers use them.

A served cache holds two kinds of state where a model mixes window and full
layers: arrays that hold the whole context, and rings of the window's rows.
The engine counts, for every decode step, the cache rows its live slots
attended, and every two seconds writes ONE ring span ``cache:rows`` whose
arguments are the sums since the last
(`ray_tpu/serve/decode_session.py` `_count_rows`)::

    steps          decode steps
    rows_read      rows attended, summed over live slots, layers and steps:
                   a full layer's the slot's depth, a window layer's no
                   more than the window
    rows_if_full   the same were every layer a full one
    bytes_full     bytes of the slot cache's arrays that hold every position
    bytes_ring     bytes of its rings

A program that writes no such span (the parent of the PR that added it)
gives None here and the readers leave their metrics out.
"""

from __future__ import annotations

from typing import Dict, Optional

from perfbench import spans

_SUMS = ("steps", "rows_read", "rows_if_full")
_LAST = ("bytes_full", "bytes_ring")


def window_sums(run) -> Optional[Dict[str, float]]:
    """The ``cache:rows`` spans that ended inside the window: the counts
    summed, the bytes as the last span had them; a span argument that was
    zero is absent from its span."""
    if "_cache_rows" not in run.__dict__:
        run._cache_rows = _window_sums(run)
    return run._cache_rows


def _window_sums(run) -> Optional[Dict[str, float]]:
    t0, t1 = run.stamps["open"], run.stamps["close"]
    out = dict.fromkeys(_SUMS + _LAST, 0.0)
    for e in spans.ring_spans(run):
        if e.get("name") != "cache:rows":
            continue
        if not t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            continue
        args = e.get("args", {})
        for k in _SUMS:
            out[k] += args.get(k, 0)
        for k in _LAST:
            out[k] = args.get(k, 0)
    return out if out["steps"] else None
