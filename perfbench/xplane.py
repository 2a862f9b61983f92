"""From a profiler trace (`.xplane.pb`) to numbers.

`jax.profiler.ProfileData` reads the file with nothing but JAX.  A TPU
trace has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops``
holds one event per executed HLO operation (start, duration, in
nanoseconds of one clock) and whose line ``XLA Modules`` holds one event per
executed program (``jit_<function>(<id>)``); the plane ``/host:CPU`` has
one line per host thread, and the benchmark's `TraceAnnotation` spans
(``handle:start``, ``train:block`` ...) are events there, on the same clock.

What is computed, per trace:

busy_s       seconds in which an operation ran on a device: the union of the
             intervals of its ``XLA Ops`` line, averaged over the devices;
window_s     from the first to the last instant of anything recorded on a
             device line or in a benchmark span;
top_ops      device seconds by operation name (mean over devices), largest
             first, each operation's own time: what runs nested in it (the
             body of a ``while``) is counted under its own name;
programs     count and device seconds by program name, and the mean idle gap
             between consecutive executions of the same program;
idle_gaps    device 0's idle gaps of 50 us or more, summed by the set of
             benchmark spans open on the host while the device was idle;
collective_exposed_s   seconds (mean over devices) in which a collective
             operation ran and nothing else did on that device.

Everything here is plain Python over (start, end) pairs, so the arithmetic
is tested on hand-made intervals as well as on the recorded trace beside the
tests.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SPAN = re.compile(r"^[a-z_]+:[a-z_0-9]+$")
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
GAP_FLOOR_S = 50e-6


class NoDevicePlane(ValueError):
    """Nothing ran on a device while the trace was taken."""


# --------------------------------------------------------------- intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(merged: List[Interval]) -> List[Interval]:
    """The holes between consecutive merged intervals."""
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def subtract(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """The parts of merged ``xs`` that merged ``ys`` do not cover."""
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def attribute_gaps(holes: List[Interval],
                   spans: List[Tuple[float, float, str]],
                   floor: float = GAP_FLOOR_S) -> Dict[str, float]:
    """Seconds of ``holes`` by the names of the ``spans`` open in them.  A
    hole shorter than ``floor`` goes under ``under_50us``; one with no span
    open under ``none``.  The label joins the distinct names, sorted."""
    out: Dict[str, float] = {}
    spans = sorted(spans)
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in sorted(holes):
        if b - a < floor:
            out["under_50us"] = out.get("under_50us", 0.0) + (b - a)
            continue
        while i < len(spans) and spans[i][0] < b:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > a]
        names = sorted({s[2] for s in active if s[0] < b and s[1] > a})
        label = "+".join(names) if names else "none"
        out[label] = out.get(label, 0.0) + (b - a)
    return out


# ------------------------------------------------------------------- trace

def _program_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


_HLO = re.compile(r"^%?([A-Za-z0-9_.\-]+) = ")


def _op_name(event_name: str) -> str:
    """``%fusion.147 = bf16[...] fusion(...)`` -> ``fusion.147``; a Pallas
    kernel, whose instruction is named after the computation it sits in,
    -> ``tpu_custom_call:checkpoint.19``."""
    m = _HLO.match(event_name)
    name = m.group(1) if m else event_name[:80]
    if "tpu_custom_call" in event_name:
        name = "tpu_custom_call:" + name
    return name


def self_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, str, bool]]:
    """(seconds, name, is a leaf) per event, the seconds less the time of
    the events nested in it: a ``while`` holds its body's operations on the
    same line, and counting both would count the body twice.  A leaf holds
    no other event."""
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []      # (end, index into out)
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][0] -= (b - a)
            out[stack[-1][1]][2] = False
        out.append([b - a, name, True])
        stack.append((b, len(out) - 1))
    return [(max(0.0, t), n, leaf) for t, n, leaf in out]


def leaves(events: List[Tuple[float, float, str]]
           ) -> List[Tuple[float, float, str]]:
    """The events that hold no other event (same order as `self_times`)."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for e, (_, _, leaf) in zip(ordered, self_times(events))
            if leaf]


def read(path: str) -> Dict[str, Any]:
    """The lines this module needs, as plain lists, seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            lines = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    a = e.start_ns * 1e-9
                    name = _op_name(e.name) if key == "ops" else e.name
                    lines[key].append((a, a + e.duration_ns * 1e-9, name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if _SPAN.match(e.name):
                        a = e.start_ns * 1e-9
                        spans.append((a, a + e.duration_ns * 1e-9, e.name))
    return {"devices": devices, "spans": spans}


def reduce(raw: Dict[str, Any]) -> Dict[str, Any]:
    devices, spans = raw["devices"], raw["spans"]
    if not devices:
        raise NoDevicePlane("the trace has no /device:TPU:<n> plane: nothing "
                         "ran on a device while it was taken")
    n = len(devices)
    edges = [t for d in devices.values() for k in ("ops", "modules")
             for e in d[k] for t in e[:2]] + [t for s in spans for t in s[:2]]
    window = max(edges) - min(edges)
    busy = 0.0
    ops: Dict[str, float] = {}
    programs: Dict[str, Dict[str, float]] = {}
    exposed = 0.0
    first = sorted(devices)[0]
    holes: List[Interval] = []
    for name, d in devices.items():
        merged = union((a, b) for a, b, _ in d["ops"])
        busy += total(merged)
        if name == first:
            holes = gaps(merged)
        for secs, op, _leaf in self_times(d["ops"]):
            ops[op] = ops.get(op, 0.0) + secs
        # leaves only: a ``while`` that holds the collective is not compute
        # that hides it
        inner = leaves(d["ops"])
        coll = union((a, b) for a, b, op in inner if _COLLECTIVE.search(op))
        rest = union((a, b) for a, b, op in inner
                     if not _COLLECTIVE.search(op))
        exposed += total(subtract(coll, rest))
        by_prog: Dict[str, List[Interval]] = {}
        for a, b, mod in d["modules"]:
            by_prog.setdefault(_program_name(mod), []).append((a, b))
        for prog, runs in by_prog.items():
            runs.sort()
            p = programs.setdefault(
                prog, {"count": 0.0, "device_s": 0.0, "gap_s": 0.0,
                       "gaps": 0.0})
            p["count"] += len(runs) / n
            p["device_s"] += total(runs) / n
            between = [y[0] - x[1] for x, y in zip(runs, runs[1:])]
            p["gap_s"] += sum(between)
            p["gaps"] += len(between)
    for p in programs.values():
        p["mean_gap_s"] = p.pop("gap_s") / p["gaps"] if p["gaps"] else None
        del p["gaps"]
    top = sorted(((k, v / n) for k, v in ops.items()),
                 key=lambda kv: -kv[1])
    idle = sorted(attribute_gaps(holes, spans).items(),
                  key=lambda kv: -kv[1])
    return {"n_devices": n, "busy_s": busy / n, "window_s": window,
            "ops": dict(top), "top_ops": [list(kv) for kv in top[:10]],
            "programs": programs,
            "idle_gaps": [list(kv) for kv in idle[:10]],
            "collective_exposed_s": exposed / n}


def find(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    return reduce(read(find(trace_dir)))


def op_seconds(reduced: Dict[str, Any], pattern: str) -> float:
    """Device seconds (mean over devices) of the operations whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["ops"].items() if rx.search(k))


def program(reduced: Dict[str, Any], pattern: str) -> Dict[str, float]:
    """Count, device seconds and mean gap of the programs whose name
    matches ``pattern``, together."""
    rx = re.compile(pattern)
    hits = [p for k, p in reduced["programs"].items() if rx.search(k)]
    count = sum(p["count"] for p in hits)
    device_s = sum(p["device_s"] for p in hits)
    gaps_ = [p["mean_gap_s"] for p in hits if p["mean_gap_s"] is not None]
    return {"count": count, "device_s": device_s,
            "mean_gap_s": sum(gaps_) / len(gaps_) if gaps_ else None}
