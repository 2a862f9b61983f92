"""Incident flight recorder — postmortems stop depending on having
scraped at the right moment.

On a SUSPECT transition, controller failover, drain deadline overrun,
elastic gang repair, or OOM kill (and on demand via ``ray-tpu debug
capture``), the controller captures one **bundle** — a directory of
JSON files under ``flight_recorder_dir``:

* ``meta.json``    — trigger, reason, wall/monotonic stamps, epoch
* ``spans.json``   — the last-N lifecycle spans of EVERY process
  (merged from the ``trace`` KV namespace, which retains the final
  flush of processes that have since died, plus the controller's own
  unflushed buffer)
* ``metrics.json`` — the controller's metrics-history window around
  the trigger, the WAL/RPC-dispatch attribution tables, and
  best-effort metrics-history rings pulled from reachable nodelets
* ``events.json``  — the structured cluster event ring
* ``nodes.json``   — the ``state.nodes()`` snapshot (health knobs,
  suspect/drain progress, reachability, clock offsets)

Reference model: ``ray timeline`` dumps + the dashboard's incident
artifacts (arXiv:1712.05889's state stack); the TPU serving-economics
argument (arXiv:2605.25645) makes preemption/failover routine events
that must be explainable after the fact.

Automatic captures are rate-limited per trigger
(``flight_recorder_min_interval_s``) and the directory is pruned to
``flight_recorder_keep`` bundles.  Capture failures are swallowed: the
recorder observes incidents, it must never cause one.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from .config import GlobalConfig

#: triggers the controller fires automatically (manual grabs use "manual")
AUTO_TRIGGERS = ("node_suspect", "node_dead", "controller_failover",
                 "drain_deadline", "elastic_repair", "oom_kill",
                 "compile_storm", "slo_breach", "overload",
                 "disk_pressure", "crash_loop")

FLIGHT_WRITE_SITE = "flight.write"


def recorder_dir() -> str:
    return GlobalConfig.flight_recorder_dir or os.path.join(
        tempfile.gettempdir(), "ray_tpu_incidents")


def list_bundles(base: Optional[str] = None) -> List[str]:
    base = base or recorder_dir()
    try:
        # dot-prefixed dirs are in-flight staging (bundles publish by
        # rename, so a listed bundle always holds all five files)
        return sorted(p for p in os.listdir(base)
                      if not p.startswith(".")
                      and os.path.isdir(os.path.join(base, p)))
    except OSError:
        return []


class FlightRecorder:
    def __init__(self, controller):
        self.c = controller
        self._last: Dict[str, float] = {}   # trigger -> monotonic
        self._captures = 0

    # ------------------------------------------------------------- trigger
    def trigger(self, trigger: str, reason: str = "",
                **meta: Any) -> None:
        """Fire-and-forget capture from controller hot paths (rate-
        limited per trigger; never blocks or raises)."""
        if not GlobalConfig.flight_recorder_enabled:
            return
        now = time.monotonic()
        min_gap = GlobalConfig.flight_recorder_min_interval_s
        if now - self._last.get(trigger, -1e9) < min_gap:
            return
        self._last[trigger] = now
        try:
            asyncio.ensure_future(self._capture_safe(trigger, reason,
                                                     meta))
        except RuntimeError:
            pass  # no running loop (teardown): drop the capture

    async def _capture_safe(self, trigger, reason, meta) -> Optional[str]:
        try:
            return await self.capture(trigger, reason, meta)
        except Exception:
            return None

    # ------------------------------------------------------------- capture
    async def capture(self, trigger: str, reason: str = "",
                      meta: Optional[dict] = None) -> str:
        """Capture one bundle NOW; returns the bundle directory path."""
        t_wall = time.time()
        bundle = {
            "meta": {
                "trigger": trigger, "reason": reason,
                "ts": t_wall, "ts_iso": time.strftime(
                    "%Y-%m-%dT%H:%M:%S", time.gmtime(t_wall)),
                "controller": self.c.address,
                "epoch": getattr(self.c.ha, "epoch", 0),
                "capture_seq": self._captures,
                **(meta or {}),
            },
            "spans": self._spans(),
            "metrics": await self._metrics(t_wall),
            "events": list(self.c.events),
            "nodes": self.c.node_rows(),
        }
        self._captures += 1
        name = f"{int(t_wall * 1000)}_{trigger}"
        path = await asyncio.to_thread(self._write, name, bundle)
        self.c._emit_event(
            "INFO", "flight_recorder",
            f"incident bundle captured ({trigger}: {reason or '-'}) -> "
            f"{path}", trigger=trigger, path=path)
        return path

    # ------------------------------------------------------------- sources
    def _spans(self) -> List[dict]:
        """Every process's flushed lifecycle spans — including the
        retained spans of processes that have since exited — plus the
        controller's own not-yet-flushed ring."""
        from ..util import tracing
        events: List[dict] = []
        for ring in self.c.trace_log.values():
            events.extend(ring.events())
        if tracing.proc_key() not in self.c.trace_log:
            events.extend(tracing.span_events())
        events.sort(key=lambda e: e.get("ts", 0))
        return events

    async def _metrics(self, t_wall: float) -> dict:
        from . import rpc
        out: Dict[str, Any] = {
            "rpc_attribution": rpc.attribution_rows(),
            "loop_lag": {
                "ewma_ms": getattr(self.c, "_lag_ewma", 0.0) * 1e3,
                "max_ms": getattr(self.c, "_lag_max", 0.0) * 1e3},
        }
        if self.c.pstore is not None:
            out["wal"] = dict(self.c.pstore.timing)
        ring = getattr(self.c, "metrics_ring", None)
        if ring is not None:
            out["history"] = {
                "interval_s": ring.interval_s,
                "controller": ring.window_around(t_wall)}
        # best-effort nodelet rings: a dead/partitioned node simply
        # contributes nothing (its last state is in spans/nodes.json)
        nodes = {}
        for nid, rec in list(self.c.nodes.items()):
            if not rec.view.alive or rec.conn.closed:
                continue
            try:
                r = await asyncio.wait_for(
                    rec.conn.call("metrics_history", {"last": 120}),
                    timeout=1.0)
                if isinstance(r, dict):
                    nodes[nid[:12]] = r
            except Exception:
                continue
        if nodes:
            out.setdefault("history", {})["nodes"] = nodes
        return out

    # --------------------------------------------------------------- disk
    def _write(self, name: str, bundle: dict) -> str:
        """Bundle write is BEST-EFFORT: an incident capture hitting a
        full/broken disk is shed with a counter (the recorder observes
        incidents, it must never cause one) — raising here would turn a
        disk fault into a failed capture task for every trigger."""
        base = recorder_dir()
        path = os.path.join(base, name)
        # stage under a dot-prefixed name and publish by rename: a
        # consumer that lists the directory mid-capture (tests polling
        # for a bundle, `ray-tpu debug list`) must never see a bundle
        # dir whose files are still being written
        stage = os.path.join(base, "." + name)
        try:
            from ..util import fault_injection as fi
            fi.fs_point(FLIGHT_WRITE_SITE, name)
            os.makedirs(stage, exist_ok=True)
            for part in ("meta", "spans", "metrics", "events", "nodes"):
                with open(os.path.join(stage, f"{part}.json"), "w") as f:
                    json.dump(bundle[part], f, default=str)
            try:
                os.rename(stage, path)
            except OSError:
                # name collision with a published bundle: replace it
                shutil.rmtree(path, ignore_errors=True)
                os.rename(stage, path)
        except OSError as e:
            shutil.rmtree(stage, ignore_errors=True)
            from . import runtime_metrics as rtm
            rtm.STORAGE_FAULTS.inc(tags={"site": FLIGHT_WRITE_SITE,
                                         "outcome": "shed"})
            return f"<shed: {e}>"
        # prune oldest past the retention bound (names sort by time)
        keep = max(1, GlobalConfig.flight_recorder_keep)
        existing = list_bundles(base)
        for doomed in existing[:max(0, len(existing) - keep)]:
            shutil.rmtree(os.path.join(base, doomed),
                          ignore_errors=True)
        return path
