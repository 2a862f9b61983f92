"""Serve controller: the single reconciliation authority.

Capability mirror of the reference's `ServeController`
(`serve/controller.py:61`) + `DeploymentStateManager`
(`serve/_private/deployment_state.py:958,1767`): holds target state, starts/
stops replica actors toward it, versions the routing table (long-poll
`serve/_private/long_poll.py` role: routers poll ``snapshot(version)``),
and applies the autoscaling policy on router-reported metrics
(`serve/_private/autoscaling_policy.py:93`).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional


def _process_core():
    """This process's CoreClient, creating it from the worker context
    when needed (a serve actor's __init__ may run before any api call
    lazily built one).  Never bootstraps a cluster."""
    from ..core.driver import get_global_core
    core = get_global_core()
    if core is None and os.environ.get("RAY_TPU_WORKER_CONTEXT"):
        from ..api import _ensure_initialized
        core = _ensure_initialized()
    return core


class ServeController:
    def __init__(self):
        self._deployments: Dict[str, Dict[str, Any]] = {}
        self._version = 0
        self._replica_seq = 0
        # per-node HTTP proxies (reference: http_state.py HTTPProxyState
        # reconciliation); node_id -> {"actor", "address"}
        self._proxies: Dict[str, Dict[str, Any]] = {}
        self._proxy_http: Optional[dict] = None
        self._last_proxy_check = 0.0
        self._replica_nodes: Dict[str, str] = {}  # replica id -> node id
        # drain evacuations in flight: doomed replica id -> {"name",
        # "replacement"} — the replacement is pre-started BEFORE the
        # draining replica stops, so capacity never dips
        self._evacuations: Dict[str, Dict[str, Any]] = {}
        # autoscale scale-downs in flight: replica id -> {"name",
        # "deadline"} — the victim drains (engine sheds new starts,
        # live sessions migrate via the failover client) and is only
        # killed at live_sessions == 0 or the migration deadline, so a
        # scale-down never drops a stream
        self._retiring: Dict[str, Dict[str, Any]] = {}
        # SUSPECT (gray) nodes from the pubsub push: their replicas'
        # capacity is down-weighted by the autoscale policy, growing
        # the fleet around a brownout before it shows up as errors
        self._suspect_nodes: set = set()
        # replica boot-time EWMA (start -> ALIVE in the actor table):
        # the Retry-After on scale-up-in-progress sheds, so clients
        # re-arrive right as the new capacity lands
        self._boot_pending: Dict[str, float] = {}
        self._boot_ewma: Optional[float] = None
        self._last_autoscale = 0.0
        # Node-membership push: a dead/draining node invalidates the
        # replica->node locality cache immediately.  A migrated replica
        # (same actor, new node) otherwise keeps its stale annotation
        # forever and every router evicts it as if it were still on the
        # corpse.
        try:
            core = _process_core()
            if core is not None:
                core.subscribe_node_events(self._on_node_event)
        except Exception:
            pass

    def _on_node_event(self, data: Dict[str, Any]) -> None:
        """A node DIED: drop its replicas' locality annotations so
        routers stop evicting replicas that are mid-restart elsewhere.
        DRAINING keeps the annotations — that eviction is the point.
        SUSPECT membership feeds the autoscale policy's capacity
        down-weighting (routers route around those nodes on their own
        copy of the same events)."""
        ev = data.get("event")
        nid = data.get("node_id") or (data.get("node") or {}).get("id")
        if ev == "suspect" and nid:
            self._suspect_nodes.add(nid)
            return
        if ev in ("rejoined", "added") and nid:
            self._suspect_nodes.discard(nid)
            return
        if ev != "dead":
            return
        if not nid:
            return
        self._suspect_nodes.discard(nid)
        stale = [rid for rid, n in self._replica_nodes.items() if n == nid]
        for rid in stale:
            self._replica_nodes.pop(rid, None)
        if stale:
            # force routers to re-pull: the fresh table drops the stale
            # annotations and _resolve_replica_nodes re-resolves them
            self._version += 1

    # -- deploy / delete ----------------------------------------------------
    def deploy(self, name: str, callable_blob: bytes, init_args: tuple,
               init_kwargs: dict, config: dict,
               route_prefix: Optional[str]) -> bool:
        entry = self._deployments.get(name)
        if entry is None:
            entry = {"replicas": [], "metrics": {}, "last_scaled": 0.0}
            self._deployments[name] = entry
        entry.update(callable_blob=callable_blob, init_args=init_args,
                     init_kwargs=init_kwargs, config=dict(config),
                     route_prefix=route_prefix)
        # full restart on redeploy of code/config (simple + correct);
        # user_config-only updates go through reconfigure()
        self._scale_to(name, 0)
        self._reconcile(name)
        self._version += 1
        return True

    def reconfigure_deployment(self, name: str, user_config: Any) -> bool:
        entry = self._deployments[name]
        entry["config"]["user_config"] = user_config
        from .. import api
        api.get([m.reconfigure.remote(user_config)
                 for r in entry["replicas"]
                 for m in (r.get("gang") or [r["handle"]])], timeout=60.0)
        self._version += 1
        return True

    def delete(self, name: str) -> bool:
        if name in self._deployments:
            self._scale_to(name, 0)
            del self._deployments[name]
            self._version += 1
        return True

    def shutdown_all(self) -> bool:
        for name in list(self._deployments):
            self.delete(name)
        return True

    # -- reconciliation -----------------------------------------------------
    def _reconcile(self, name: str) -> None:
        entry = self._deployments[name]
        cfg = entry["config"]
        target = cfg["num_replicas"]
        auto = cfg.get("autoscaling_config")
        if auto:
            target = max(auto["min_replicas"],
                         min(target, auto["max_replicas"]))
            cfg["num_replicas"] = target
        self._scale_to(name, target)

    def _start_replica(self, name: str, entry: Dict[str, Any]
                       ) -> Dict[str, Any]:
        """Start one replica (or gang replica) and append it to the
        deployment's table; returns the new table row."""
        from .. import api
        from .replica import ServeReplica
        cfg = entry.get("config", {})
        gang_size = int(cfg.get("gang_size", 1) or 1)
        self._replica_seq += 1
        rid = f"{name}#{self._replica_seq}"
        if gang_size > 1:
            # Multi-process replica: a placement-group gang hosting one
            # sharded program (serve/gang.py); the routing table carries
            # only the leader, so the router sees one unit.
            from .gang import start_gang_replica
            rep = start_gang_replica(name, rid, entry, cfg)
            entry["replicas"].append(rep)
            return rep
        opts = dict(cfg.get("ray_actor_options") or {})
        handle = api.remote(ServeReplica).options(
            max_concurrency=int(cfg.get("max_concurrent_queries", 8)),
            num_cpus=opts.get("num_cpus", 0.1),
            # the chip reservation: the replica's worker is then the one
            # process of its node on the TPU platform (core/accelerator.py)
            num_tpus=opts.get("num_tpus", 0.0),
            resources=opts.get("resources"),
            # detached: a replica must outlive the JOB that deployed
            # it (e.g. a `serve-deploy` CLI process) — Serve owns
            # replica lifecycle via scale-down/shutdown, the job GC
            # does not (reference: all serve actors are detached)
            lifetime="detached",
        ).remote(name, rid, entry["callable_blob"],
                 entry["init_args"], entry["init_kwargs"],
                 cfg.get("user_config"))
        rep = {"id": rid, "handle": handle}
        entry["replicas"].append(rep)
        self._boot_pending[rid] = time.monotonic()
        return rep

    def _scale_to(self, name: str, target: int) -> None:
        from .. import api
        entry = self._deployments[name]
        while len(entry["replicas"]) < target:
            self._start_replica(name, entry)
        while len(entry["replicas"]) > target:
            rep = entry["replicas"].pop()
            self._replica_nodes.pop(rep["id"], None)
            self._boot_pending.pop(rep["id"], None)
            self._retiring.pop(rep["id"], None)
            self._audit_kill(name, rep["id"], target)
            if rep.get("gang"):
                from .gang import stop_gang_replica
                stop_gang_replica(rep)
                continue
            try:
                api.kill(rep["handle"])
            except Exception:
                pass
        self._version += 1

    @staticmethod
    def _audit_kill(name: str, replica_id: str, target: int) -> None:
        """Structured cluster event per replica teardown — when a
        request races a kill, the events API says who killed what."""
        why = (f"scale to {target}" if target >= 0
               else "node draining; replacement pre-started"
               if target == -2
               else "autoscale down; sessions migrated first"
               if target == -3 else "found dead; replacing")
        try:
            from .. import state
            state.report_event(
                f"serve: removing replica {replica_id} of {name!r} "
                f"({why})", severity="INFO", source="serve")
        except Exception:
            pass

    # -- per-node HTTP proxies ---------------------------------------------
    def ensure_proxies(self, http: dict) -> Dict[str, str]:
        """Reconcile one HTTPProxy actor per alive node (reference:
        `serve/_private/http_state.py:28` proxy-state manager).  Each
        proxy binds an ephemeral port on its node and the table maps
        node_id -> http address; routers inside each proxy prefer
        same-node replicas, so ingress on any node serves local traffic
        without a cross-node hop when a local replica exists."""
        from .. import api, state
        from ..util.scheduling_strategies import \
            NodeAffinitySchedulingStrategy
        from .http_proxy import HTTPProxy
        self._proxy_http = dict(http)
        alive = {n["id"]: n for n in state.list_nodes() if n.get("alive")}
        # proxies whose ACTOR died while the node stayed alive must be
        # replaced too — check the actor table, not just node membership
        dead_aids = set()
        try:
            dead_aids = {row["actor_id"] for row in state.list_actors()
                         if row.get("state") == "DEAD"}
        except Exception:
            pass
        for nid in list(self._proxies):
            entry = self._proxies[nid]
            if nid in alive and \
                    entry["actor"]._actor_id not in dead_aids:
                continue
            self._proxies.pop(nid)
            try:
                api.kill(entry["actor"])
            except Exception:
                pass
        me = api.get_actor("serve::controller")
        for nid in alive:
            if nid in self._proxies:
                continue
            # Fire-and-forget: the proxy pushes its bound address via
            # register_proxy once live.  NEVER await it here — this
            # method runs inside the controller actor and the proxy's
            # first routing snapshot calls back into this same actor.
            actor = api.remote(HTTPProxy).options(
                num_cpus=0.05, max_concurrency=64, lifetime="detached",
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_id=nid, soft=False),
            ).remote(me, http.get("host", "127.0.0.1"), 0, nid)
            self._proxies[nid] = {"actor": actor, "address": None}
        return self.proxy_table()

    def register_proxy(self, node_id: str, address: str) -> bool:
        entry = self._proxies.get(node_id)
        if entry is not None:
            entry["address"] = address
        return True

    def adopt_proxy(self, node_id: str, actor: Any, address: str) -> bool:
        """Track a proxy created OUTSIDE the controller (the HeadOnly
        boot path) so proxy_statuses reports it and stop_proxies reaps
        it — detached actors have no job GC to fall back on."""
        self._proxies[node_id] = {"actor": actor, "address": address}
        return True

    def proxy_table(self) -> Dict[str, str]:
        """node_id -> address, for proxies that have announced."""
        return {nid: p["address"] for nid, p in self._proxies.items()
                if p["address"]}

    def stop_proxies(self) -> bool:
        from .. import api
        for p in self._proxies.values():
            try:
                api.kill(p["actor"])
            except Exception:
                pass
        self._proxies.clear()
        return True

    def _maybe_reconcile_proxies(self) -> None:
        """Piggybacked on router metric reports: pick up node joins and
        deaths within ~5 s without a dedicated loop."""
        if self._proxy_http is None:
            return
        now = time.monotonic()
        if now - self._last_proxy_check < 5.0:
            return
        self._last_proxy_check = now
        try:
            self.ensure_proxies(self._proxy_http)
        except Exception:
            pass  # transient state-API failure; next report retries

    def _maybe_heal_replicas(self) -> None:
        """Replace DEAD replica actors (reference: deployment_state's
        replica health checks — a replica whose worker died, was
        OOM-killed, or lost its node gets a fresh replacement toward
        the target count).  Throttled; piggybacks on metric reports."""
        now = time.monotonic()
        if now - getattr(self, "_last_heal_check", 0.0) < 5.0:
            return
        self._last_heal_check = now
        try:
            from .. import state
            dead = {row["actor_id"] for row in state.list_actors()
                    if row.get("state") == "DEAD"}
        except Exception:
            return
        if not dead:
            return
        for name, entry in self._deployments.items():
            alive = []
            lost = 0
            for rep in entry["replicas"]:
                handle = (rep.get("gang") or [rep["handle"]])[0]
                if handle._actor_id in dead:
                    lost += 1
                    self._replica_nodes.pop(rep["id"], None)
                    self._audit_kill(name, rep["id"], -1)
                    if rep.get("gang"):
                        from .gang import stop_gang_replica
                        try:
                            stop_gang_replica(rep)
                        except Exception:
                            pass
                else:
                    alive.append(rep)
            if lost:
                entry["replicas"] = alive
                self._reconcile(name)   # refill to the target count
                self._version += 1

    def _maybe_evacuate_draining(self) -> None:
        """Zero-downtime replica evacuation off DRAINING nodes
        (reference rationale: deployment_state's graceful scale — here
        triggered by the cluster's drain protocol).  Two-phase, spread
        over poll ticks: (1) pre-start a replacement for every ALIVE
        replica sitting on a draining node, (2) once the replacement is
        ALIVE on a live node, stop the doomed replica.  Also refreshes
        the locality cache for replicas the core controller already
        migrated (same actor, new node) so routers stop evicting them.
        Throttled; piggybacks on router metric reports."""
        now = time.monotonic()
        if now - getattr(self, "_last_drain_check", 0.0) < 2.0:
            return
        self._last_drain_check = now
        try:
            from .. import state
            nodes = state.list_nodes()
        except Exception:
            return  # transient state-API failure; next tick retries
        alive_ids, draining = set(), set()
        for n in nodes:
            if n.get("alive"):
                alive_ids.add(n["id"])
                if n.get("draining"):
                    draining.add(n["id"])
        # cached annotations naming departed nodes must be re-resolved —
        # a drained node's replicas restarted elsewhere, and routers
        # would keep evicting them on the corpse annotation
        stale = any(nid not in alive_ids
                    for nid in self._replica_nodes.values())
        if not draining and not self._evacuations and not stale:
            return
        try:
            from .. import state
            by_aid = {row.get("actor_id"): row
                      for row in state.list_actors()}
        except Exception:
            return
        replacing = {e["replacement"] for e in self._evacuations.values()}
        for name, entry in self._deployments.items():
            for rep in list(entry["replicas"]):
                rid = rep["id"]
                handle = (rep.get("gang") or [rep["handle"]])[0]
                row = by_aid.get(handle._actor_id) or {}
                nid = row.get("node_id")
                cached = self._replica_nodes.get(rid)
                if nid and cached != nid:
                    # migrated replica: refresh the node annotation or
                    # routers keep treating it as draining forever
                    self._replica_nodes[rid] = nid
                    self._version += 1
                elif not nid and cached and cached not in alive_ids:
                    # mid-restart off a gone node: drop the corpse
                    # annotation so routers may route to it again once
                    # the restart lands
                    self._replica_nodes.pop(rid, None)
                    self._version += 1
                if rid in self._evacuations or rid in replacing:
                    continue
                if nid in draining and row.get("state") == "ALIVE":
                    replacement = self._start_replica(name, entry)
                    # keep the doomed replica LAST so a concurrent
                    # scale-down pops it, never the replacement
                    entry["replicas"].remove(rep)
                    entry["replicas"].append(rep)
                    self._evacuations[rid] = {
                        "name": name, "replacement": replacement["id"]}
                    self._version += 1
        # phase 2: replacements that came up take over; doomed replicas stop
        for rid, info in list(self._evacuations.items()):
            entry = self._deployments.get(info["name"])
            rep = None if entry is None else next(
                (r for r in entry["replicas"] if r["id"] == rid), None)
            new_rep = None if entry is None else next(
                (r for r in entry["replicas"]
                 if r["id"] == info["replacement"]), None)
            if rep is None or new_rep is None:
                self._evacuations.pop(rid, None)
                continue  # deleted/healed under us; reconcile covers it
            nh = (new_rep.get("gang") or [new_rep["handle"]])[0]
            row = by_aid.get(nh._actor_id) or {}
            if row.get("state") != "ALIVE" or row.get("node_id") in draining:
                continue  # replacement not ready yet; next tick
            from .. import api
            if not rep.get("gang"):
                # Migrate live decode sessions off the doomed replica
                # BEFORE stopping it: flip its engines into drain mode
                # (new starts shed with the typed 503; streams hand off
                # via the ``migrating`` reply and the proxy's failover
                # client re-admits them on the replacement), then wait
                # — bounded — for the live-session count to reach zero
                # so a drain with active streams drops none of them.
                from ..core.config import GlobalConfig
                if "session_deadline" not in info:
                    info["session_deadline"] = now + \
                        GlobalConfig.serve_session_migration_timeout_s
                    try:
                        api.get(rep["handle"].prepare_drain.remote(),
                                timeout=10.0)
                    except Exception:
                        pass  # dead/hung replica: the deadline covers it
                live = 0
                try:
                    live = api.get(rep["handle"].drain_status.remote(),
                                   timeout=5.0).get("live_sessions", 0)
                except Exception:
                    live = 0
                if live > 0 and now < info["session_deadline"]:
                    continue   # sessions still handing off; next tick
            entry["replicas"].remove(rep)
            self._replica_nodes.pop(rid, None)
            self._audit_kill(info["name"], rid, -2)
            if rep.get("gang"):
                from .gang import stop_gang_replica
                try:
                    stop_gang_replica(rep)
                except Exception:
                    pass
            else:
                try:
                    api.kill(rep["handle"])
                except Exception:
                    pass
            self._evacuations.pop(rid, None)
            self._version += 1

    # -- routing state ------------------------------------------------------
    def _resolve_replica_nodes(self) -> None:
        """Fill the replica->node cache for locality routing with ONE
        actor-table RPC, at most once per second.  Only truthy node ids
        are cached: a replica still PENDING_CREATION has node_id None,
        and caching that would disable locality for its whole life."""
        unresolved = []
        for entry in self._deployments.values():
            for rep in entry["replicas"]:
                if not self._replica_nodes.get(rep["id"]):
                    unresolved.append(rep)
        if not unresolved:
            return
        now = time.monotonic()
        if now - getattr(self, "_last_node_resolve", 0.0) < 1.0:
            return
        self._last_node_resolve = now
        try:
            from .. import state
            by_aid = {row.get("actor_id"): row.get("node_id")
                      for row in state.list_actors()}
        except Exception:
            return  # transient; next snapshot retries
        newly = 0
        for rep in unresolved:
            handle = (rep.get("gang") or [rep["handle"]])[0]
            nid = by_aid.get(handle._actor_id)  # ids are bytes on the wire
            if nid:
                self._replica_nodes[rep["id"]] = nid
                newly += 1
        if newly:
            # routers that already saw this version must re-pull to get
            # the node annotations, or locality stays off until the next
            # unrelated table change
            self._version += 1

    def snapshot(self, known_version: int = -1) -> Optional[dict]:
        """Routing table if newer than known_version (long-poll pull)."""
        # Reconcile drains on the POLL path too (throttled): when every
        # replica of a deployment is evicted, completions — and with
        # them report_metrics — stop entirely, but failing routers keep
        # polling snapshot; without this hook the stale annotations
        # would never refresh and the outage would be permanent.
        self._maybe_evacuate_draining()
        self._maybe_autoscale()
        if known_version == self._version:
            return None
        self._resolve_replica_nodes()
        now = time.monotonic()
        table = {}
        for name, entry in self._deployments.items():
            table[name] = {
                "route_prefix": entry.get("route_prefix"),
                "ingress": entry["config"].get("ingress", False),
                "max_concurrent_queries":
                    entry["config"].get("max_concurrent_queries", 8),
                # boot-EWMA Retry-After while a scale-up is in flight:
                # routers stamp it on typed sheds so clients re-arrive
                # as the new replica lands
                "scaleup_retry_after_s":
                    self._scaleup_retry_after(name, now),
                "replicas": [{"id": r["id"], "handle": r["handle"],
                              "node_id":
                                  self._replica_nodes.get(r["id"]),
                              # retiring (autoscale drain-down): keep
                              # sid-sticky session ops flowing, take no
                              # NEW sessions
                              "draining": bool(r.get("retiring"))}
                             for r in entry["replicas"]],
            }
        return {"version": self._version, "table": table}

    def list_deployments(self) -> Dict[str, dict]:
        return {name: {"num_replicas": len(e["replicas"]),
                       "route_prefix": e.get("route_prefix"),
                       "config": {k: v for k, v in e["config"].items()
                                  if k != "ray_actor_options"}}
                for name, e in self._deployments.items()}

    # -- autoscaling --------------------------------------------------------
    def report_metrics(self, name: str, ongoing_per_replica) -> bool:
        """Router-reported in-flight counts: the occupancy fallback for
        deployments without a decode engine, and one of the tick
        sources of the autoscale loop.  ``ongoing_per_replica`` is a
        {replica_id: in_flight} mapping (older routers sent a bare
        list; tolerated)."""
        self._maybe_reconcile_proxies()
        self._maybe_heal_replicas()     # 5s-throttled internally
        self._maybe_evacuate_draining()  # 2s-throttled internally
        self._resolve_replica_nodes()   # 1s-throttled internally
        entry = self._deployments.get(name)
        if entry is None:
            return False
        if not isinstance(ongoing_per_replica, dict):
            ongoing_per_replica = {
                r["id"]: c for r, c in zip(entry["replicas"],
                                           ongoing_per_replica or [])}
        entry["metrics"] = {"ongoing": dict(ongoing_per_replica),
                            "ts": time.monotonic()}
        self._maybe_autoscale()         # interval-throttled internally
        return True

    def autoscale_tick(self) -> bool:
        """Explicit loop nudge (HTTP proxies schedule one per
        serve_autoscale_interval_s): keeps the autoscaler — and the
        piggybacked heal/evacuate reconciles — ticking through idle
        valleys, when no request traffic is polling snapshots, so
        scale-DOWN to min_replicas happens without a client trickle."""
        self._maybe_reconcile_proxies()
        self._maybe_heal_replicas()
        self._maybe_evacuate_draining()
        self._maybe_autoscale()
        return True

    def _maybe_autoscale(self) -> None:
        """One pass of the autoscale loop, throttled to
        serve_autoscale_interval_s: fold boot observations, advance
        in-flight retirements, then decide each autoscaled deployment
        via the pure policy (serve/autoscaler.py) over engine
        occupancy series (metrics history) or router-reported counts."""
        from ..core.config import GlobalConfig
        iv = GlobalConfig.serve_autoscale_interval_s
        if iv is None or iv <= 0:
            return
        now = time.monotonic()
        if now - self._last_autoscale < iv:
            return
        self._last_autoscale = now
        self._observe_boots(now)
        self._tick_retirements(now)
        autoscaled = [name for name, e in self._deployments.items()
                      if e["config"].get("autoscaling_config")]
        if autoscaled:
            hist = self._engine_history()
            for name in autoscaled:
                entry = self._deployments.get(name)
                if entry is None:
                    continue
                try:
                    self._autoscale_one(name, entry, now, hist)
                except Exception:
                    # chaos 'error' action or a transient state-API
                    # failure: the decision is re-derived next tick
                    pass
        self._push_deployment_metrics()

    @staticmethod
    def _engine_history() -> Dict[str, Any]:
        """Latest engine-pushed serve gauges from every process's
        metrics-history ring (state.metrics_history plumbing): the
        occupancy/waiting signal for engine deployments.  One fetch
        per tick, shared by every deployment's decision."""
        try:
            from .. import state
            return state.metrics_history(last=4)
        except Exception:
            return {}

    def _latest_engine_gauges(self, hist: Dict[str, Any],
                              name: str) -> Dict[str, Dict[str, float]]:
        """{replica_id: {occupied, waiting, max_slots}} from the newest
        history sample carrying this deployment's label."""
        from ..core import metrics_history as mh
        out: Dict[str, Dict[str, float]] = {}
        fam = {"occupied": "ray_tpu_serve_engine_occupied_slots",
               "waiting": "ray_tpu_serve_engine_waiting_sessions",
               "max_slots": "ray_tpu_serve_engine_max_slots"}
        for proc in (hist.get("processes") or {}).values():
            samples = proc.get("samples") or []
            for field, metric in fam.items():
                for pt in mh.series(samples, metric, kind="gauges",
                                    labels={"deployment": name}):
                    rid = mh.parse_labels(pt["key"]).get("replica")
                    if not rid:
                        continue
                    slot = out.setdefault(rid, {})
                    # series is time-ordered: the last write wins
                    slot[field] = float(pt["value"])
        return out

    def _autoscale_one(self, name: str, entry: Dict[str, Any],
                       now: float, hist: Dict[str, Any]) -> None:
        import collections

        from . import autoscaler
        auto = entry["config"]["autoscaling_config"]
        gauges = self._latest_engine_gauges(hist, name)
        ongoing = (entry.get("metrics") or {}).get("ongoing") or {}
        target_per = float(auto.get(
            "target_num_ongoing_requests_per_replica", 2.0) or 2.0)
        views = []
        for rep in entry["replicas"]:
            rid = rep["id"]
            g = gauges.get(rid)
            if g and g.get("max_slots"):
                occupied = g.get("occupied", 0.0)
                waiting = g.get("waiting", 0.0)
                capacity = g["max_slots"]
            else:
                occupied = float(ongoing.get(rid, 0.0))
                waiting = 0.0
                capacity = max(target_per, 0.1)
            views.append(autoscaler.ReplicaView(
                replica_id=rid, occupied=occupied, waiting=waiting,
                capacity=capacity,
                suspect=self._replica_nodes.get(rid)
                in self._suspect_nodes,
                retiring=bool(rep.get("retiring"))))
        ring = entry.setdefault(
            "signal", collections.deque(maxlen=600))
        ring.append(autoscaler.fleet_sample(
            now, views, float(auto.get("suspect_weight", 0.25) or 0.0)))
        decision = autoscaler.decide(
            auto, views, list(ring), now,
            last_up=entry.get("as_last_up", 0.0),
            last_down=entry.get("as_last_down", 0.0))
        cur = sum(1 for v in views if not v.retiring)
        if decision.target == cur:
            return
        # chaos site: delay or drop the DECISION itself (`ray-tpu chaos
        # validate` knows it).  A dropped decision is simply re-derived
        # next tick from current state — targets are absolute, so a
        # retried decision can never double-scale.
        from ..util import fault_injection as fi
        if fi.ACTIVE is not None:
            act = fi.ACTIVE.point("serve.autoscale", name)
            if act is not None:
                if act["action"] in ("delay", "latency"):
                    time.sleep(max(0.0, act["delay_s"]))
                elif act["action"] == "drop":
                    return
                else:
                    raise RuntimeError(
                        f"chaos: injected serve.autoscale failure for "
                        f"{name}")
        self._apply_decision(name, entry, decision, cur, now)

    def _apply_decision(self, name: str, entry: Dict[str, Any],
                        decision, cur: int, now: float) -> None:
        target = decision.target
        try:
            from .. import state
            state.report_event(
                f"serve: autoscale {name!r} {cur} -> {target} "
                f"({decision.reason})", severity="INFO", source="serve")
        except Exception:
            pass
        if target > cur:
            for _ in range(target - cur):
                self._start_replica(name, entry)
            entry["as_last_up"] = now
            entry["as_dec_up"] = entry.get("as_dec_up", 0) + 1
        else:
            victims = list(decision.victims) or [
                r["id"] for r in reversed(entry["replicas"])
                if not r.get("retiring")]
            for rid in victims[:cur - target]:
                self._begin_retirement(name, entry, rid, now)
            entry["as_last_down"] = now
            entry["as_dec_down"] = entry.get("as_dec_down", 0) + 1
        entry["config"]["num_replicas"] = target
        entry["last_scaled"] = now
        self._version += 1

    def _begin_retirement(self, name: str, entry: Dict[str, Any],
                          rid: str, now: float) -> None:
        """Scale-down via the drain path: the victim stops taking NEW
        sessions (its engine sheds starts; routers skip it via the
        snapshot's ``draining`` flag) while live streams keep their
        sid-sticky access until they migrate — the failover client
        re-admits each one elsewhere on the ``migrating`` reply.  The
        kill happens in _tick_retirements at live_sessions == 0 (or
        the migration deadline)."""
        from .. import api
        from ..core.config import GlobalConfig
        rep = next((r for r in entry["replicas"] if r["id"] == rid),
                   None)
        if rep is None or rid in self._retiring \
                or rid in self._evacuations:
            return
        rep["retiring"] = True
        # doomed replicas sit LAST so an unrelated _scale_to pops them
        # first, never a serving replica
        entry["replicas"].remove(rep)
        entry["replicas"].append(rep)
        self._retiring[rid] = {
            "name": name,
            "deadline": now + GlobalConfig.serve_session_migration_timeout_s}
        if not rep.get("gang"):
            try:
                api.get(rep["handle"].prepare_drain.remote(),
                        timeout=10.0)
            except Exception:
                pass   # dead/hung replica: the deadline covers it

    def _tick_retirements(self, now: float) -> None:
        from .. import api
        for rid, info in list(self._retiring.items()):
            entry = self._deployments.get(info["name"])
            rep = None if entry is None else next(
                (r for r in entry["replicas"] if r["id"] == rid), None)
            if rep is None:
                self._retiring.pop(rid, None)
                continue   # deleted / healed / scaled under us
            live = 0
            if now < info["deadline"] and not rep.get("gang"):
                try:
                    live = api.get(rep["handle"].drain_status.remote(),
                                   timeout=5.0).get("live_sessions", 0)
                except Exception:
                    live = 0
            if live > 0 and now < info["deadline"]:
                continue   # sessions still migrating; next tick
            entry["replicas"].remove(rep)
            self._replica_nodes.pop(rid, None)
            self._boot_pending.pop(rid, None)
            self._audit_kill(info["name"], rid, -3)
            if rep.get("gang"):
                from .gang import stop_gang_replica
                try:
                    stop_gang_replica(rep)
                except Exception:
                    pass
            else:
                try:
                    api.kill(rep["handle"])
                except Exception:
                    pass
            self._retiring.pop(rid, None)
            self._version += 1

    def _observe_boots(self, now: float) -> None:
        """Fold completed replica boots (start -> ALIVE in the actor
        table) into the boot-time EWMA behind scale-up Retry-After
        hints."""
        if not self._boot_pending:
            return
        try:
            from .. import state
            alive = {row["actor_id"] for row in state.list_actors()
                     if row.get("state") == "ALIVE"}
        except Exception:
            return
        by_rid: Dict[str, Any] = {}
        for entry in self._deployments.values():
            for rep in entry["replicas"]:
                by_rid[rep["id"]] = (rep.get("gang")
                                     or [rep["handle"]])[0]
        from ..core.config import GlobalConfig
        alpha = min(1.0, max(
            0.01, GlobalConfig.serve_replica_boot_ewma_alpha))
        for rid, t0 in list(self._boot_pending.items()):
            handle = by_rid.get(rid)
            if handle is None or now - t0 > 600.0:
                self._boot_pending.pop(rid, None)   # gone or wedged
                continue
            if handle._actor_id in alive:
                boot = max(0.1, now - t0)
                self._boot_ewma = boot if self._boot_ewma is None else \
                    alpha * boot + (1.0 - alpha) * self._boot_ewma
                self._boot_pending.pop(rid, None)

    def _scaleup_retry_after(self, name: str, now: float
                             ) -> Optional[float]:
        """Retry-After for sheds while this deployment's scale-up is in
        flight: the EWMA boot time minus how long the oldest pending
        replica has already been booting — clients re-arrive right as
        capacity lands instead of on the generic backoff floor."""
        pending = [t0 for rid, t0 in self._boot_pending.items()
                   if rid.rsplit("#", 1)[0] == name]
        if not pending or self._boot_ewma is None:
            return None
        return max(0.5, self._boot_ewma - (now - min(pending)))

    def _push_deployment_metrics(self) -> None:
        """Replica-count + decision samples to this worker's nodelet
        (same ``serve_metrics`` plumbing the engines use), so metrics
        history carries the replica-count-vs-load timeline."""
        try:
            import asyncio

            from ..core.worker_runtime import current_worker_runtime
            rt = current_worker_runtime()
            if rt is None or rt._loop is None:
                return
            for name, entry in self._deployments.items():
                payload: Dict[str, Any] = {
                    "deployment": name,
                    "replicas": sum(1 for r in entry["replicas"]
                                    if not r.get("retiring"))}
                up = entry.pop("as_dec_up", 0)
                down = entry.pop("as_dec_down", 0)
                if up:
                    payload["decisions_up"] = up
                if down:
                    payload["decisions_down"] = down
                asyncio.run_coroutine_threadsafe(
                    rt.nodelet.notify("serve_metrics", payload),
                    rt._loop)
        except Exception:
            pass
