"""Device time by the part of the model an operation belongs to.

A trace names a device operation by the compiler's numbering (``fusion.401``)
and the numbers change with every compile.  The program says what each one
is: its compile ledger (`ray_tpu/util/device_profile.py`) leaves, for every
program it compiled, ``<session_dir>/programs/<kind>-<pid>.<program>.json``::

    {"program": "decode_step",
     "maps": [{"module": "jit_fused_step",          one a compiled shape
               "instructions": {"fusion.142": "jit(fused_step)/while/body/
                                closed_call/attention/bskgt,bkdt->bskgd/
                                dot_general", ...}}]}

the ``op_name`` path of each instruction a trace can show, in which the
`jax.named_scope` names of the model programs stand.  Here every ``XLA Ops``
event of a device goes to the ``XLA Modules`` event that encloses it (the
program: two programs may both own a ``fusion.3``), is looked up in that
module's map, and its own seconds (`own_times`: a ``while`` is not counted
over its body) go to (part, direction):

part       the LAST component of the path that is one of `PARTS`, bare or
           inside the transformations JAX wrapped it in
           (``transpose(jvp(norm))``): the innermost scope wins;
direction  ``recompute`` under a ``rematted_computation`` component, else
           ``backward`` with ``transpose(`` anywhere, else ``forward``.

An operation with no map, no ``op_name`` or no part in it is ``unnamed``, and
so is the time of a program in which no operation ran: shares are of the
``XLA Modules`` line's seconds, mean over devices, and the ten parts and
``unnamed`` add up to 100.

A program that leaves no map files (the parent of the PR that added them)
gives None, and so does an untraced run: the readers leave their metrics out.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from perfbench import spans, xplane

PARTS = ("embed", "norm", "projections", "attention", "cache_write", "ffn",
         "experts", "conv", "head", "optimizer")
UNNAMED = "unnamed"
_SCOPE = re.compile(r"^(?:(?:jvp|transpose|vmap)\()*(\w*)\)*$")
_KERNEL = "tpu_custom_call:"        # `xplane._op_name`'s mark on a kernel

Key = Tuple[str, str, str]          # (program, part, direction)
Maps = Dict[str, List[Dict[str, str]]]      # HLO module -> its maps


def place(op_name: str) -> Tuple[str, str]:
    """``op_name`` path -> (part or `UNNAMED`, direction)."""
    steps = op_name.split("/")
    part = next((m.group(1) for m in map(_SCOPE.match, reversed(steps))
                 if m and m.group(1) in PARTS), UNNAMED)
    if "rematted_computation" in steps:
        return part, "recompute"
    return part, "backward" if "transpose(" in op_name else "forward"


def own_times(events: List[Tuple[float, float, str]]) -> List[float]:
    """Seconds of each event (in the order given) in which it was the
    INNERMOST one running: every instant of the events' union goes to the
    event that started last among those open, so a ``while`` is not counted
    over its body and the times add up to the union whatever overlaps.
    (`xplane.self_times` takes a child's whole length from its parent and
    holds the result above zero: right for nested events, but an
    asynchronous copy that a later operation outlasts is counted twice.)"""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out = [0.0] * len(events)
    stack: List[int] = []
    t = 0.0

    def run_until(to: float) -> None:
        nonlocal t
        while stack and t < to:
            end = events[stack[-1]][1]
            if end > t:
                upto = min(end, to)
                out[stack[-1]] += upto - t
                t = upto
            if end <= t:
                stack.pop()
        t = max(t, to)

    for i in order:
        run_until(events[i][0])
        stack.append(i)
    run_until(float("inf"))
    return out


def load_maps(session_dir: Optional[str]) -> Maps:
    """Every op map the session's processes left, by HLO module name."""
    out: Maps = {}
    if not session_dir:
        return out
    for path in sorted(glob.glob(os.path.join(session_dir, "programs",
                                              "*.json"))):
        try:
            with open(path) as f:
                body = json.load(f)
            for m in body["maps"]:
                out.setdefault(m["module"], []).append(m["instructions"])
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return out


def _map_for(module: str, ops: Dict[str, float], maps: Maps
             ) -> Dict[str, str]:
    """The map of the program ``module`` (an ``XLA Modules`` event's name)
    whose operations are ``ops``: of the maps its HLO module's name has (one
    a compiled shape), the one that knows most of them."""
    found = maps.get(xplane._program_name(module), [])
    if len(found) < 2:
        return found[0] if found else {}
    return max(found, key=lambda m: sum(1 for op in ops if op in m))


def by_part(devices: Dict[str, Dict[str, list]], maps: Maps
            ) -> Dict[str, Any]:
    """``devices`` as `xplane.read` gives them -> ``{"total_s": the modules'
    seconds, "seconds": {(program, part, direction): s}, "ops": {(program,
    part, direction): {operation: s}}, "idle_s": seconds of the programs in
    which no operation ran}``, each the mean over devices."""
    n = len(devices)
    total = 0.0
    seconds: Dict[Key, float] = {}
    ops: Dict[Key, Dict[str, float]] = {}
    for d in devices.values():
        modules = sorted(d["modules"])
        starts = [m[0] for m in modules]
        total += sum(b - a for a, b, _ in modules)
        per_module: Dict[str, Dict[str, float]] = {}
        for (a, _b, op), secs in zip(d["ops"], own_times(d["ops"])):
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= modules[i][1]:
                continue            # outside every program: not in the total
            mine = per_module.setdefault(modules[i][2], {})
            mine[op] = mine.get(op, 0.0) + secs
        for module, mine in per_module.items():
            program = xplane._program_name(module)
            names = {op: op[len(_KERNEL):] if op.startswith(_KERNEL) else op
                     for op in mine}
            m = _map_for(module, {names[op]: s for op, s in mine.items()},
                         maps)
            for op, secs in mine.items():
                key = (program,) + place(m.get(names[op], ""))
                seconds[key] = seconds.get(key, 0.0) + secs / n
                into = ops.setdefault(key, {})
                into[names[op]] = into.get(names[op], 0.0) + secs / n
    total /= n
    return {"total_s": total, "seconds": seconds, "ops": ops,
            "idle_s": max(0.0, total - sum(seconds.values()))}


def shares(run) -> Optional[Dict[str, float]]:
    """Percent of the traced programs' device seconds by part (every
    direction together), ``recompute`` (every part's operations under
    ``rematted_computation``) and `UNNAMED` (what is left of 100 beside the
    ten parts); None in an untraced run and where the program left no map.
    Read once per run and kept on it."""
    if "_part_shares" not in run.__dict__:
        run._part_shares = _shares(run)
    return run._part_shares


def _shares(run) -> Optional[Dict[str, float]]:
    if run.trace is None:
        return None
    maps = load_maps(spans.session_dir(run))
    if not maps:
        return None
    r = by_part(xplane.read(xplane.find(run.raw["trace"]["dir"]))["devices"],
                maps)
    if not r["total_s"]:
        return None
    out = dict.fromkeys(PARTS + ("recompute",), 0.0)
    for (_, part, direction), secs in r["seconds"].items():
        if part != UNNAMED:
            out[part] += 100.0 * secs / r["total_s"]
            if direction == "recompute":
                out["recompute"] += 100.0 * secs / r["total_s"]
    out[UNNAMED] = 100.0 - sum(out[p] for p in PARTS)
    return out


def share(run, name: str) -> Optional[float]:
    """One entry of `shares`: a part, ``recompute`` or ``unnamed``."""
    s = shares(run)
    return None if s is None else s[name]
