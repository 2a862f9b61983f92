"""The program's own spans, as the metric readers use them.

Two sources, both written by `ray_tpu.util.tracing.span`:

* **Engine-thread phases** (``engine:schedule``, ``engine:admit``,
  ``engine:dispatch``, ``engine:readback``, ``engine:publish``) are host
  annotations in the run's profiler trace, on the device lines' clock.
  `engine_idle` cuts device 0's idle holes of 50 us or more at the edges of
  those spans and sums the pieces by the one phase open in each (the phases
  are flat: at most one is open at any instant), ``none`` where the engine
  thread had no phase open (it waited with nothing to do, or was between
  two phases).

* **Ring spans** (``proxy:request``, ``proxy:route``, ``serve_queue::``,
  ``serve_exec::``, ``setup:*``) are wall-clock Chrome-trace events that
  each process of the session leaves in ``<session_dir>/spans/*.json`` when
  it exits; the readers run after the runtime is down, so that is where
  they find them.  A ring keeps the newest 1024 spans of a category, so of
  a long window the files hold the last few hundred calls.

A program that records none of these (the parent of the PR that added
them) gives empty lists and no files: every function here then returns
None or an empty result, and the reader leaves its metric out.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
from typing import Any, Dict, List, Optional, Tuple

from perfbench import xplane

Interval = Tuple[float, float]
Span = Tuple[float, float, str]

ENGINE_PHASES = ("schedule", "admit", "dispatch", "readback", "publish")
_ENGINE = "engine:"


# ------------------------------------------------------- engine idle share

def cut(holes: List[Interval], spans: List[Span]) -> List[Interval]:
    """``holes`` cut into pieces at every edge of ``spans`` that falls
    inside one, so that no piece straddles the start or end of a span."""
    edges = sorted({t for s in spans for t in s[:2]})
    out: List[Interval] = []
    i = 0
    for a, b in sorted(holes):
        while i < len(edges) and edges[i] <= a:
            i += 1
        j, cur = i, a
        while j < len(edges) and edges[j] < b:
            out.append((cur, edges[j]))
            cur = edges[j]
            j += 1
        out.append((cur, b))
    return out


def idle_by_phase(holes: List[Interval], spans: List[Span],
                  floor: float = xplane.GAP_FLOOR_S) -> Dict[str, float]:
    """Seconds of the ``holes`` of at least ``floor`` by the engine phase
    open in them: one key per phase of `ENGINE_PHASES`, ``none`` for the
    rest, ``total`` for their sum (so the phases plus ``none`` equal
    ``total``).  Spans of other names (the benchmark's own ``handle:``
    spans on the RPC threads) are not looked at."""
    engine = [s for s in spans if s[2].startswith(_ENGINE)]
    long_holes = [h for h in holes if h[1] - h[0] >= floor]
    by_label = xplane.attribute_gaps(cut(long_holes, engine), engine,
                                     floor=0.0)
    out = {p: 0.0 for p in ENGINE_PHASES}
    out["none"] = 0.0
    for label, secs in by_label.items():
        # flat spans: a piece lies under one phase; should two ever
        # overlap, the piece is credited to the first by name
        name = label.split("+")[0]
        key = name[len(_ENGINE):] if name.startswith(_ENGINE) else "none"
        out[key if key in out else "none"] += secs
    out["total"] = xplane.total(long_holes)
    return out


def engine_idle(run) -> Optional[Dict[str, float]]:
    """`idle_by_phase` of the run's trace with ``window_s`` beside it, or
    None where there is no trace or the trace holds no engine span.  Read
    once per run and kept on it."""
    if "_engine_idle" not in run.__dict__:
        run._engine_idle = _engine_idle(run)
    return run._engine_idle


def _engine_idle(run) -> Optional[Dict[str, float]]:
    if run.trace is None:
        return None
    raw = xplane.read(xplane.find(run.raw["trace"]["dir"]))
    if not any(s[2].startswith(_ENGINE) for s in raw["spans"]):
        return None
    first = sorted(raw["devices"])[0]
    merged = xplane.union((a, b) for a, b, _ in raw["devices"][first]["ops"])
    out = idle_by_phase(xplane.gaps(merged), raw["spans"])
    out["window_s"] = run.trace["window_s"]
    return out


def engine_idle_pct(run, phase: str) -> Optional[float]:
    """Idle seconds under ``phase`` (or ``none``) as a percentage of the
    traced window: the unit of ``device.idle_share``."""
    idle = engine_idle(run)
    if idle is None:
        return None
    return 100.0 * idle[phase] / idle["window_s"]


# ---------------------------------------------------------- engine counters

def phase_delta(run, *keys: str) -> Optional[float]:
    """Sum over ``keys`` of the engine's ``phase_totals`` at the window's
    end less at its start; None where the program has no such key."""
    c = run.raw.get("counters", {})
    if "before" not in c:
        return None
    before = c["before"].get("phase_totals", {})
    after = c["after"].get("phase_totals", {})
    if not all(k in before and k in after for k in keys):
        return None
    return sum(after[k] - before[k] for k in keys)


# --------------------------------------------------------------- ring spans

def session_dir(run) -> Optional[str]:
    """The session directory of this run's runtime: of the ``session_*``
    made since the run's process started, the one in which the chip holder
    left its span file (other runtimes may share the machine: the test
    suite's), else the newest."""
    try:
        from ray_tpu.core.node import sessions_base
    except ImportError:
        return None
    found = [d for d in glob.glob(os.path.join(sessions_base(), "session_*"))
             if os.path.getmtime(d) >= run.stamps["start"] - 1.0]
    found.sort(key=os.path.getmtime, reverse=True)
    holder = f"worker-{run.worker['pid']}.json"
    for d in found:
        if os.path.exists(os.path.join(d, "spans", holder)):
            return d
    return found[0] if found else None


def ring_spans(run) -> List[Dict[str, Any]]:
    """Every span the session's processes left in their span files."""
    if "_ring_spans" not in run.__dict__:
        run._ring_spans = _ring_spans(session_dir(run))
    return run._ring_spans


def _ring_spans(directory: Optional[str]) -> List[Dict[str, Any]]:
    events: List[Dict[str, Any]] = []
    if not directory:
        return events
    for path in sorted(glob.glob(os.path.join(directory, "spans",
                                              "*.json"))):
        try:
            with open(path) as f:
                events.extend(json.load(f))
        except (OSError, ValueError):
            continue
    return events


def setup_span_s(run, name: str) -> Optional[float]:
    """Seconds of the chip holder's ``setup:`` span ``name`` (the span of
    that name whose ``worker_pid`` is the chip holder's)."""
    pid = run.worker["pid"]
    for e in ring_spans(run):
        if e.get("name") == name and \
                e.get("args", {}).get("worker_pid") == pid:
            return e["dur"] * 1e-6
    return None


def hops(events: List[Dict[str, Any]], op: str, t0: float, t1: float
         ) -> Dict[str, List[float]]:
    """Per-hop milliseconds of the proxied calls of protocol operation
    ``op`` that the proxy began inside [t0, t1] (epoch seconds), joined on
    the request id the proxy minted:

    route   ``proxy:route``: request arrived -> handed to the replica
    queue   ``serve_queue::``: handed over -> the replica starts on it
    reply   ``proxy:request`` end less ``serve_exec::`` end: the result's
            way back, the response written
    A call enters only with all four of its spans present."""
    by_rid: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for e in events:
        rid = e.get("args", {}).get("rid")
        if not rid:
            continue
        name = e["name"]
        kind = (name if name in ("proxy:request", "proxy:route")
                else name.split("::")[0])
        if kind in ("proxy:request", "proxy:route", "serve_queue",
                    "serve_exec"):
            by_rid.setdefault(rid, {})[kind] = e     # a retry: last wins
    out: Dict[str, List[float]] = {"route": [], "queue": [], "reply": []}
    for call in by_rid.values():
        if len(call) < 4 or call["serve_exec"]["args"].get("op") != op:
            continue
        req, ex = call["proxy:request"], call["serve_exec"]
        if not t0 <= req["ts"] * 1e-6 <= t1:
            continue
        out["route"].append(call["proxy:route"]["dur"] * 1e-3)
        out["queue"].append(call["serve_queue"]["dur"] * 1e-3)
        out["reply"].append(((req["ts"] + req["dur"])
                             - (ex["ts"] + ex["dur"])) * 1e-3)
    return out


def hop_median_ms(run, hop: str, op: str = "next_chunk") -> Optional[float]:
    if "_hops" not in run.__dict__:
        run._hops = hops(ring_spans(run), op, run.stamps["open"],
                         run.stamps["close"])
    values = run._hops[hop]
    return statistics.median(values) if values else None
