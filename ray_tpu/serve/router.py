"""Router: replica selection with in-flight caps.

Capability mirror of the reference's `Router`/`ReplicaSet`
(`serve/_private/router.py:62,134,221`): round-robin over replicas,
skipping those at ``max_concurrent_queries``; blocks (with backoff) when
all are saturated.  Runs in-process in every handle/proxy; refreshes its
table by polling the controller's versioned snapshot (the long-poll role).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, Optional

from .. import api


class Router:
    def __init__(self, controller_handle, poll_interval_s: float = 0.25):
        self._controller = controller_handle
        self._version = -1
        self._table: Dict[str, dict] = {}
        self._inflight: Dict[str, int] = {}
        self._rr: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self._poll_interval = poll_interval_s
        self._last_poll = 0.0
        # Locality: prefer replicas on this router's own node (the
        # reference's LocalityScheduling in the replica scheduler) — a
        # per-node proxy then serves node-local traffic without an extra
        # network hop whenever a local replica has capacity.
        try:
            self._node_id = api.get_runtime_context().node_id
        except Exception:
            self._node_id = None
        # Replicas on dead/DRAINING nodes are evicted the moment the
        # controller's `nodes` pubsub event lands — not after the
        # health-check TTL expired (a node death otherwise leaves a
        # window of requests routed to a corpse).
        self._down_nodes: set = set()
        try:
            from .controller import _process_core
            core = _process_core()
            if core is not None:
                core.subscribe_node_events(self._on_node_event)
        except Exception:
            pass  # degraded: the poll TTL + heal loop still converge
        # Prefix affinity (serve/prefix_cache.py): recently routed
        # session prompts -> owning replica.  New sessions sharing a
        # system prompt land where that prefix's KV is already hot, so
        # the replica-side prefix cache hits instead of every replica
        # warming the same prefix independently.  Owners are unique
        # ints (one trie key each); _paff_owner maps them back to
        # replica ids.
        from .prefix_cache import PrefixIndex
        self._paffinity = PrefixIndex(max_owners=512)
        self._paff_owner: Dict[int, str] = {}
        self._paff_seq = 0
        self._refresh(force=True)

    def _on_node_event(self, data) -> None:
        ev = data.get("event")
        if ev in ("dead", "draining", "suspect"):
            # SUSPECT (gray failure / controller-only partition) is
            # routed around exactly like dead/draining — but the node's
            # replicas are NOT torn down, so a rejoin restores them
            nid = data.get("node_id")
            if nid:
                with self._lock:
                    self._down_nodes.add(nid)
        elif ev == "rejoined":
            nid = data.get("node_id")
            if nid:
                with self._lock:
                    self._down_nodes.discard(nid)
        elif ev == "added":
            nid = (data.get("node") or {}).get("id")
            if nid:
                with self._lock:
                    self._down_nodes.discard(nid)

    def _refresh(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last_poll < self._poll_interval:
            return
        self._last_poll = now
        snap = api.get(self._controller.snapshot.remote(self._version),
                       timeout=30.0)
        if snap is None:
            return
        with self._lock:
            self._version = snap["version"]
            self._table = snap["table"]
            self._rr = {name: itertools.cycle(range(
                max(len(e["replicas"]), 1)))
                for name, e in self._table.items()}
            # affinity entries pointing at replicas that left the
            # table are dead weight: evict them
            live = {r["id"] for e in self._table.values()
                    for r in e["replicas"]}
            for owner, rid in list(self._paff_owner.items()):
                if rid not in live:
                    self._paffinity.evict(owner)
                    self._paff_owner.pop(owner, None)

    def deployment_names(self):
        self._refresh()
        return list(self._table)

    def route_prefixes(self) -> Dict[str, str]:
        """deployment -> actual route prefix (HTTP-exposed only)."""
        self._refresh()
        return {name: e["route_prefix"] for name, e in self._table.items()
                if e.get("route_prefix")}

    def match_route(self, path: str) -> Optional[str]:
        self._refresh()
        best = None
        for name, entry in self._table.items():
            prefix = entry.get("route_prefix")
            if prefix is None:
                continue  # handle-only deployment: no HTTP route
            if path == prefix or path.startswith(prefix.rstrip("/") + "/"):
                if best is None or len(prefix) > len(best[1]):
                    best = (name, prefix)
        return best[0] if best else None

    def route_info(self, name: str) -> dict:
        """Deployment routing metadata for the proxy: prefix + whether
        it takes the full http context (@serve.ingress)."""
        self._refresh()
        entry = self._table.get(name, {})
        return {"route_prefix": entry.get("route_prefix"),
                "ingress": entry.get("ingress", False)}

    def _prefix_note(self, tokens, rid: str) -> None:
        """Remember that ``rid`` just admitted a session with this
        prompt — the affinity signal for later sessions sharing its
        prefix.  Caller holds the lock."""
        self._paff_seq += 1
        self._paffinity.insert(tokens, self._paff_seq)
        self._paff_owner[self._paff_seq] = rid
        if len(self._paff_owner) > len(self._paffinity) + 16:
            livemap = set(self._paffinity.owners())
            self._paff_owner = {o: r for o, r
                                in self._paff_owner.items()
                                if o in livemap}

    def _prefix_prefer(self, tokens) -> Optional[str]:
        """Replica holding the longest shared prefix with ``tokens``
        (None on a miss).  Caller holds the lock."""
        owner, depth = self._paffinity.longest_match(tokens)
        if owner is None or depth <= 0:
            return None
        return self._paff_owner.get(owner)

    def assign_request(self, name: str, args: tuple, kwargs: dict,
                       method: Optional[str] = None,
                       timeout_s: float = 60.0,
                       sticky_replica_id: Optional[str] = None,
                       prefix_tokens=None,
                       request_id: Optional[str] = None):
        """Pick a non-saturated replica round-robin and return the result
        ObjectRef; counts in-flight per replica.

        ``request_id`` (the HTTP proxy mints one per request) rides to
        the replica, whose ``serve_queue::``/``serve_exec::`` spans then
        carry it as ``rid`` beside the proxy's own spans of the request.

        ``sticky_replica_id`` pins the request to ONE replica (decode
        sessions: a session's KV cache lives on the replica that ran
        `start`, so its `next_chunk`/`end` must land there — never on a
        load-balancing pass).  A sticky request waits out a saturated
        owner but NEVER spills to a sibling; a vanished owner (scale
        down, crash) raises ReplicaUnavailableError after one forced
        table refresh, because the session's KV cache died with it —
        the proxy-side failover client (serve/failover.py) then
        re-admits the session on a healthy replica via teacher-forced
        replay of its journal, so the stream survives the owner.

        Graceful degradation: a deployment with ZERO live replicas sheds
        the request immediately with the typed ReplicaUnavailableError
        (confirmed against a force-refreshed table first) — holding it
        until the deadline would just stack up doomed requests while the
        deployment restarts.  When replicas exist but all are at their
        in-flight cap, waits under capped exponential backoff with full
        jitter instead of the old fixed 10 ms busy-poll."""
        from ..core.config import GlobalConfig
        from ..exceptions import ReplicaUnavailableError
        from ..util.backoff import ExponentialBackoff
        deadline = time.monotonic() + timeout_s
        bo = ExponentialBackoff(base=GlobalConfig.serve_backoff_base_s,
                                cap=GlobalConfig.serve_backoff_cap_s)
        confirmed_empty = False
        while True:
            self._refresh()
            with self._lock:
                entry = self._table.get(name)
                replicas = entry["replicas"] if entry else []
                cap = entry.get("max_concurrent_queries", 8) if entry else 0
                chosen = None
                sticky_gone = False
                if sticky_replica_id is not None:
                    rep = next((r for r in replicas
                                if r["id"] == sticky_replica_id), None)
                    if rep is None or \
                            rep.get("node_id") in self._down_nodes:
                        sticky_gone = True
                    elif self._inflight.get(rep["id"], 0) < cap:
                        chosen = rep
                elif replicas:
                    # Least-loaded with prefix-affinity and local
                    # preference: locality is a TIE-BREAK among the
                    # least-loaded candidates, never a magnet —
                    # preferring any under-cap local replica outright
                    # would funnel all traffic to it while its
                    # siblings idle.  A session start whose prompt
                    # shares a prefix with a recently routed session
                    # prefers THAT replica (its KV prefix is hot) as
                    # long as it is within one request of the least
                    # load — affinity must not defeat load balance.
                    # RR order breaks remaining ties.
                    start = next(self._rr[name]) % len(replicas)
                    candidates = []
                    for off in range(len(replicas)):
                        rep = replicas[(start + off) % len(replicas)]
                        if rep.get("node_id") in self._down_nodes:
                            continue  # dead/draining node: never route
                        if rep.get("draining"):
                            continue  # retiring: no NEW sessions
                        load = self._inflight.get(rep["id"], 0)
                        if load < cap:
                            candidates.append((load, rep))
                    if candidates:
                        min_load = min(load for load, _ in candidates)
                        if prefix_tokens:
                            want = self._prefix_prefer(prefix_tokens)
                            if want is not None:
                                chosen = next(
                                    (rep for load, rep in candidates
                                     if rep["id"] == want
                                     and load <= min_load + 1), None)
                        if chosen is None:
                            best = [rep for load, rep in candidates
                                    if load == min_load]
                            chosen = next(
                                (rep for rep in best if self._node_id and
                                 rep.get("node_id") == self._node_id),
                                best[0])
                if chosen is not None and prefix_tokens:
                    self._prefix_note(prefix_tokens, chosen["id"])
                if chosen is not None:
                    self._inflight[chosen["id"]] = \
                        self._inflight.get(chosen["id"], 0) + 1
            if chosen is not None:
                call = (args, kwargs, method, request_id) if request_id \
                    else (args, kwargs, method)
                ref = chosen["handle"].handle_request.remote(*call)
                return ref, chosen["id"]
            # server-derived Retry-After: while a scale-up is in
            # flight the snapshot carries the boot-time EWMA hint, so
            # shed clients re-arrive right as the new capacity lands
            # instead of on the generic backoff floor
            hint = (entry or {}).get("scaleup_retry_after_s") or 1.0
            if sticky_replica_id is not None and sticky_gone:
                # the session's owner is out of the table: one forced
                # refresh guards against staleness, then fail loudly —
                # re-routing would hand the sid to a replica that has
                # no such KV cache
                if confirmed_empty:
                    raise ReplicaUnavailableError(
                        f"{name} (replica {sticky_replica_id} owning "
                        f"this decode session is gone)",
                        retry_after_s=hint)
                confirmed_empty = True
                self._refresh(force=True)
                continue
            if not replicas:
                # unknown deployment or zero live replicas: one forced
                # refresh guards against a stale table (deploy racing the
                # poll TTL), then shed fast with the typed error
                if confirmed_empty:
                    raise ReplicaUnavailableError(name,
                                                  retry_after_s=hint)
                confirmed_empty = True
                self._refresh(force=True)
                continue
            confirmed_empty = False
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"no replica available for {name!r} within "
                    f"{timeout_s}s")
            self._refresh(force=True)
            time.sleep(min(bo.next_delay(),
                           max(0.0, deadline - time.monotonic())))

    def complete(self, name: str, replica_id: str) -> None:
        with self._lock:
            if replica_id in self._inflight:
                self._inflight[replica_id] -= 1
                if self._inflight[replica_id] <= 0:
                    del self._inflight[replica_id]
        self._report(name)

    def _report(self, name: str) -> None:
        entry = self._table.get(name)
        if not entry:
            return
        counts = {r["id"]: self._inflight.get(r["id"], 0)
                  for r in entry["replicas"]}
        try:
            self._controller.report_metrics.remote(name, counts)
        except Exception:
            pass
