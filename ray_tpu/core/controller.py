"""Cluster controller — the control plane (GCS equivalent).

One process per cluster.  Owns: node membership + health
(/root/reference/src/ray/gcs/gcs_server/gcs_health_check_manager.h:39),
the actor lifecycle FSM (DEPENDENCIES_UNREADY → PENDING_CREATION → ALIVE →
RESTARTING → DEAD, /root/reference/src/ray/protobuf/gcs.proto:89-98 and
gcs_actor_manager.cc:240), placement groups with 2-phase bundle commit
(gcs_placement_group_manager / placement_group_resource_manager.cc:196),
an internal KV + function table (gcs_kv_manager.cc), the object directory,
and pubsub to connected subscribers (drivers, nodelets).

Scheduling of *tasks* never passes through here (drivers lease directly from
nodelets); only actors and placement groups are scheduled centrally, exactly
as in the reference's GCS-based actor scheduler (gcs_actor_scheduler.cc:53).
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List, Optional, Set

from . import rpc, runtime_metrics as rtm, spill
from ..exceptions import WalWriteError
from .config import GlobalConfig
from .scheduling import NodeView, hybrid_policy, pack_bundles
from .task_spec import ResourceSet, TaskSpec

# Actor FSM states (wire strings).
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"
# Crash-loop quarantine: restart budget exhausted inside the rolling
# window by poison-shaped deaths.  Terminal for callers (they get the
# typed error) but NOT forever — the quarantine TTL or an operator
# `ray-tpu quarantine clear` moves the actor back to RESTARTING.
QUARANTINED = "QUARANTINED"


class _DrainDeadline(Exception):
    """Internal: the graceful-drain budget ran out (or the chaos layer
    forced an overrun) — fall back to the hard-death recovery path."""


class ActorRecord:
    def __init__(self, actor_id: bytes, spec: dict, name: Optional[str],
                 max_restarts: int, detached: bool):
        self.actor_id = actor_id
        self.spec = spec
        self.name = name
        self.max_restarts = max_restarts
        self.detached = detached
        self.state = PENDING_CREATION
        self.address: Optional[str] = None      # "host:port" of the actor worker
        self.node_id: Optional[str] = None
        self.worker_id: Optional[bytes] = None
        self.num_restarts = 0
        self.death_cause: Optional[str] = None
        self.owner_conn_id: Optional[int] = None
        # rolling-window restart accounting: [wall_ts, node, cause] per
        # restart consumed — only stamps inside actor_restart_window_s
        # count against max_restarts, so a long-lived actor that crashes
        # once a day is not condemned (persisted; evidence on quarantine)
        self.restart_stamps: List[list] = []
        # earliest monotonic time the scheduler may place the next
        # incarnation (full-jitter exponential backoff between restarts;
        # runtime-only — a restored controller restarts immediately)
        self.restart_at: float = 0.0
        # wait_actor futures resolved at the ALIVE/DEAD FSM transition
        self.waiters: List[asyncio.Future] = []
        # nodes that recently reported actor-cap saturation → expiry time
        # (scheduling steers around them until the entry lapses)
        self.avoid_nodes: Dict[str, float] = {}

    def to_wire(self):
        return {"actor_id": self.actor_id, "state": self.state,
                "address": self.address, "node_id": self.node_id,
                "name": self.name, "num_restarts": self.num_restarts,
                "death_cause": self.death_cause,
                "quarantined": self.state == QUARANTINED,
                "class_name": self.spec.get("fname", "")}


class PGRecord:
    def __init__(self, pg_id: bytes, bundles: List[Dict[str, float]], strategy: str,
                 name: str = ""):
        self.pg_id = pg_id
        self.bundles = bundles
        self.strategy = strategy
        self.name = name
        self.state = "PENDING"          # PENDING | CREATED | REMOVED
        self.node_ids: List[str] = []   # bundle index -> node id hex
        self.waiters: List[asyncio.Event] = []

    def to_wire(self):
        return {"pg_id": self.pg_id, "state": self.state, "strategy": self.strategy,
                "bundles": self.bundles, "node_ids": self.node_ids,
                "name": self.name}


class NodeRecord:
    def __init__(self, view: NodeView, conn: rpc.Connection):
        self.view = view
        self.conn = conn
        self.last_heartbeat = time.monotonic()
        # resource bundles of lease requests WAITING on this node
        # (heartbeat-reported); the autoscaler's load signal
        self.demand: List[Dict[str, float]] = []
        # last heartbeat-reported disk-health dict ({state, used_frac});
        # the state alone also rides the synced view (view.disk)
        self.disk: Optional[Dict[str, Any]] = None
        # heartbeat-estimated wall-clock offset, node − controller:
        # SUBTRACT it from the node's timestamps to land on the
        # controller clock (RTT-midpoint sample, EWMA-smoothed nodelet-
        # side) — state.timeline() uses it so cross-host spans merge in
        # causal order
        self.clock_offset = 0.0


class Controller:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 heartbeat_timeout_s: Optional[float] = None,
                 persist_dir: Optional[str] = None,
                 standby_of: Optional[str] = None,
                 lease_timeout_s: Optional[float] = None):
        self.server = rpc.RpcServer(host, port)
        # HA role (core/ha.py): leader unless booted with standby_of, in
        # which case this controller replicates the leader's WAL and
        # promotes itself when the leader's lease lapses
        from .ha import HAManager
        self.ha = HAManager(self, standby_of=standby_of,
                            lease_timeout_s=lease_timeout_s)
        # config-backed (RAY_TPU_NODE_DEATH_TIMEOUT_S) unless the caller
        # pins it — the old hardcoded 5.0 was untunable cluster-wide
        self.heartbeat_timeout_s = (
            heartbeat_timeout_s if heartbeat_timeout_s is not None
            else GlobalConfig.node_death_timeout_s)
        self.nodes: Dict[str, NodeRecord] = {}
        # peer-reachability connectivity matrix, folded from the
        # reachability vectors nodelets piggyback on their heartbeats
        from .reachability import ReachMatrix
        self.reach = ReachMatrix(GlobalConfig.peer_reach_fresh_s)
        # SUSPECT quarantine: node_id -> monotonic time it entered.  A
        # suspect node's controller link is down but probing peers still
        # reach it — no new leases/placements land there, serve routers
        # skip it, but its actors and objects are UNTOUCHED; it rejoins
        # with zero restarts when the link heals inside suspect_grace_s.
        self.suspects: Dict[str, float] = {}
        self.actors: Dict[bytes, ActorRecord] = {}
        self.named_actors: Dict[str, bytes] = {}
        # -- blast-radius containment ------------------------------------
        # crash ledger: task/actor signature -> recent death hits
        # [{ts, node, cause, poison}], pruned to poison_window_s.  In-
        # memory only — individual hits are cheap to re-accumulate after
        # a failover; the *decisions* below are what must survive.
        self.crash_ledger: Dict[str, List[dict]] = {}
        # poison quarantine: signature -> WAL-persisted record
        # {sig, kind, since, until, evidence[, actor_id]} — rides
        # heartbeat replies so every lease desk fails the signature fast
        self.quarantine: Dict[str, dict] = {}
        self.pgs: Dict[bytes, PGRecord] = {}
        self.kv: Dict[str, Dict[bytes, bytes]] = {}
        # every process's lifecycle spans (util/tracing.py), by the
        # process's key; outside the KV, so no snapshot carries them
        self.trace_log: Dict[str, Any] = {}
        self.object_dir: Dict[bytes, Set[str]] = {}       # oid -> node ids
        self.object_sizes: Dict[bytes, int] = {}
        self.object_waiters: Dict[bytes, List[asyncio.Event]] = {}
        # -- distributed ref counting (reference: reference_count.h:61) ----
        # A "holder" is either a live connection (borrower process) or a
        # container object ("obj:<hex>" — refs serialized inside a stored
        # value).  The owner requests a free when its local refs drop; the
        # free executes only once no holder borrows the object.
        self.borrows: Dict[bytes, Dict[str, int]] = {}    # oid -> holder -> n
        self.holder_refs: Dict[str, Dict[bytes, int]] = {}  # holder -> oid -> n
        self.pending_free: Set[bytes] = set()
        self.ref_stats = {"lineage_evictions": 0, "deferred_frees": 0,
                          "cascade_frees": 0}
        self.subscribers: Dict[str, Set[rpc.Connection]] = {}  # channel -> conns
        # node drains in progress: node_id -> live progress dict (phase,
        # in-flight count, objects left) surfaced via list_nodes
        self.draining: Dict[str, Dict[str, Any]] = {}
        self._drain_tasks: Dict[str, asyncio.Task] = {}
        # actor_ids mid-migration off a draining node: the old worker's
        # death is intended and must not burn restart budget
        self._migrating: Set[bytes] = set()
        self.view_version = 0
        self.config_snapshot: Dict[str, Any] = {}
        self.jobs: Dict[bytes, dict] = {}
        self._pending_actor_wakeup = asyncio.Event()
        self._tasks: List[asyncio.Task] = []
        self._pub_buf: Dict[int, tuple] = {}   # conn id -> (conn, events)
        self._pub_flusher: Optional[asyncio.Task] = None
        # conn id -> channels whose events were dropped (bounded buffer
        # overflow): the next flush tells the subscriber to resync
        self._pub_resync: Dict[int, set] = {}
        # structured cluster events (reference: src/ray/util/event.h +
        # dashboard/modules/event): bounded ring, newest last
        from collections import deque as _deque
        self.events = _deque(maxlen=GlobalConfig.events_buffer_size)
        self._event_seq = 0
        # self-observation (core/metrics_history.py, flight_recorder.py):
        # the controller samples its own registry into a bounded ring and
        # captures incident bundles on suspect/failover/drain/OOM events
        from .flight_recorder import FlightRecorder
        from .metrics_history import MetricsRing
        self.metrics_ring = MetricsRing()
        self.flight = FlightRecorder(self)
        # overload protection: watermark state machine + admission
        # shedding + credit grants (core/overload.py)
        from .overload import OverloadManager
        self.overload = OverloadManager(self)
        self._lag_ewma = 0.0   # asyncio loop lag (rpc.loop_lag_monitor)
        self._lag_max = 0.0
        # -- durability (reference: gcs_table_storage.h:357 Redis-backed
        # GCS restart; here snapshot+WAL on local disk, persistence.py) ----
        self.pstore = None
        if persist_dir:
            from .persistence import ControllerStore
            self.pstore = ControllerStore(persist_dir)
            self.pstore._snapshot_provider = self._persist_tables_source
            self.pstore.tap = self.ha.offer
            if standby_of is None:
                self._restore(self.pstore.load())
            # a standby leaves its local state to ha._standby_loop: it
            # adopts the leader's snapshot (or, if the leader never
            # appears, promotes from the on-disk tables)
        # chaos layer: `once` fault rules are claimed here (exactly one
        # firing cluster-wide); arm from env config, then let a plan
        # persisted in the KV (applied pre-restart) override it
        self._chaos_claims: Set[str] = set()
        from ..util import fault_injection as fi
        fi.maybe_arm_from_config()
        raw_plan = self.kv.get(fi.CHAOS_KV_NS, {}).get(fi.CHAOS_KV_KEY)
        if raw_plan:
            try:
                fi.arm(raw_plan)
            except (ValueError, KeyError):
                pass
        self._register_handlers()

    # ------------------------------------------------------------ durability
    def _p(self, *record):
        """Append one mutation to the WAL (no-op without persistence).

        A WAL write/fsync failure poisons the store (fsyncgate); the
        leader self-fences RIGHT HERE — before the mutation could be
        acked — and the error propagates so no caller treats the
        mutation as durable.  The RPC gate converts it to an in-band
        ``_not_leader`` so clients re-dial and find the promoted
        standby."""
        if self.pstore is not None:
            try:
                self.pstore.append(*record)
            except WalWriteError as e:
                self.ha.self_fence(str(e))
                raise

    @staticmethod
    def _actor_to_disk(rec: "ActorRecord") -> dict:
        return {"actor_id": rec.actor_id, "spec": rec.spec, "name": rec.name,
                "max_restarts": rec.max_restarts, "detached": rec.detached,
                "state": rec.state, "address": rec.address,
                "node_id": rec.node_id, "num_restarts": rec.num_restarts,
                "death_cause": rec.death_cause,
                "restart_stamps": rec.restart_stamps}

    @staticmethod
    def _pg_to_disk(pg: "PGRecord") -> dict:
        return {"pg_id": pg.pg_id, "bundles": pg.bundles,
                "strategy": pg.strategy, "name": pg.name, "state": pg.state,
                "node_ids": pg.node_ids}

    def _tables_snapshot(self) -> dict:
        return {
            "kv": {ns: dict(d) for ns, d in self.kv.items()},
            "actors": {rec.actor_id: self._actor_to_disk(rec)
                       for rec in self.actors.values()},
            "named_actors": dict(self.named_actors),
            "pgs": {pg.pg_id: self._pg_to_disk(pg)
                    for pg in self.pgs.values()},
            "jobs": {jid: info for jid, info in self.jobs.items()},
            "draining_nodes": list(self.draining),
            "suspect_nodes": list(self.suspects),
            "quarantine": {sig: dict(rec)
                           for sig, rec in self.quarantine.items()},
            "ha_epoch": self.ha.epoch,
        }

    def _persist_tables_source(self) -> dict:
        """WAL-compaction source: the live tables when leading, the
        replicated tables while standing by."""
        if self.ha.is_leader or self.ha.tables is None:
            return self._tables_snapshot()
        return self.ha.tables

    def _restore(self, state: Optional[dict]) -> None:
        """Repopulate tables after a controller restart.  Live nodelets
        re-register through their heartbeat reconnect loops; ALIVE actors
        keep their addresses (their worker processes survived us)."""
        if not state:
            return
        self.ha.epoch = max(self.ha.epoch,
                            int(state.get("ha_epoch", 0) or 0))
        self.kv = {ns: dict(d) for ns, d in state.get("kv", {}).items()}
        for d in state.get("actors", {}).values():
            rec = ActorRecord(d["actor_id"], d["spec"], d.get("name"),
                              d.get("max_restarts", 0),
                              d.get("detached", False))
            rec.state = d.get("state", PENDING_CREATION)
            rec.address = d.get("address")
            rec.node_id = d.get("node_id")
            rec.num_restarts = d.get("num_restarts", 0)
            rec.death_cause = d.get("death_cause")
            rec.restart_stamps = [list(s) for s in
                                  d.get("restart_stamps", [])]
            if rec.state in (PENDING_CREATION, RESTARTING):
                rec.node_id = None  # reschedule once nodes re-register
            self.actors[rec.actor_id] = rec
        self.named_actors = dict(state.get("named_actors", {}))
        for d in state.get("pgs", {}).values():
            pg = PGRecord(d["pg_id"], d["bundles"], d["strategy"],
                          d.get("name", ""))
            pg.state = d.get("state", "PENDING")
            pg.node_ids = list(d.get("node_ids", []))
            self.pgs[pg.pg_id] = pg
        self.jobs = dict(state.get("jobs", {}))
        # drains interrupted by our restart: keep the nodes out of the
        # placement pool; the orchestration resumes (with a fresh default
        # budget) when each nodelet re-registers
        for nid in state.get("draining_nodes", []):
            self.draining[nid] = {"phase": "restored", "in_flight": -1,
                                  "objects_left": -1}
        # suspects survive the restart/promotion with a FRESH grace
        # budget: the quarantined node either re-registers (rejoins with
        # everything intact) or the health loop declares it dead once
        # the restarted grace runs out with no peer reaching it
        for nid in state.get("suspect_nodes", []):
            self.suspects[nid] = time.monotonic()
        # quarantines survive the restart/promotion intact: a poison
        # signature must not get a fresh blast radius just because the
        # controller moved (TTL keeps running on the persisted `until`)
        self.quarantine = {sig: dict(rec) for sig, rec in
                           state.get("quarantine", {}).items()}

    # ------------------------------------------------------------------ setup
    def _register_handlers(self):
        s = self.server
        for name in ("register_node", "heartbeat", "get_cluster_view",
                     "kv_put", "kv_get", "kv_del", "kv_keys", "kv_exists",
                     "trace_append", "trace_dump",
                     "register_actor", "wait_actor", "get_actor", "list_actors",
                     "get_named_actor", "report_actor_death", "kill_actor",
                     "create_placement_group", "wait_placement_group",
                     "remove_placement_group", "list_placement_groups",
                     "object_location_add", "object_location_remove",
                     "object_locations_get", "object_replicate",
                     "object_relay",
                     "free_objects", "list_objects",
                     "ref_inc", "ref_dec", "free_request", "ref_counts",
                     "report_event", "list_events",
                     "subscribe", "publish", "register_job", "finish_job",
                     "list_nodes", "report_worker_failure", "actor_alive",
                     "report_task_crash", "quarantine_list",
                     "quarantine_clear",
                     "drain_node", "ping", "metrics_text", "credit_request",
                     "rpc_attribution", "metrics_history", "debug_capture",
                     "chaos_plan", "chaos_claim",
                     "ha_status", "ha_register_standby", "ha_replicate",
                     "ha_sync_snapshot", "ha_lease", "ha_fence"):
            s.register(name, self._ha_gate(name, getattr(self, "_h_" + name)))

    def _ha_gate(self, name: str, fn):
        """Wrap one RPC handler with the HA protocol: epoch fencing (a
        caller that has seen a newer epoch deposes us), leadership
        rejection (standby/fenced controllers serve only the HA_EXEMPT
        set), and the sync_floor replication gate (a mutating reply is
        held until the standby durably has its WAL records)."""
        from .ha import HA_EXEMPT

        async def gated(conn, data, _name=name, _fn=fn):
            ha = self.ha
            await ha.maybe_fence_from(data)
            if _name not in HA_EXEMPT and not ha.is_leader:
                return {"_not_leader": True, "leader": ha.leader_addr,
                        "epoch": ha.epoch}
            # overload admission: brownout sheds bulk-lane ops with an
            # in-band retriable reply (liveness is never shed)
            ra = self.overload.admit(_name)
            if ra is not None:
                return {"_overload": True, "retry_after_s": ra,
                        "op": _name}
            try:
                if _name in HA_EXEMPT or not ha.sync_gate_active():
                    return await _fn(conn, data)
                seq0 = self.pstore.seq
                result = await _fn(conn, data)
                if self.pstore.seq > seq0:
                    await ha.wait_replicated(self.pstore.seq)
                return result
            except WalWriteError:
                # poisoned WAL: _p already self-fenced; answer in-band
                # so the client's failover machinery re-dials instead of
                # surfacing a transport error for an unacked mutation
                return {"_not_leader": True, "leader": ha.leader_addr,
                        "epoch": ha.epoch}
        return gated

    # ------------------------------------------------------------- chaos
    async def _h_chaos_plan(self, conn, data):
        """Set/clear/read the cluster fault plan.  The plan lives in the
        KV (namespace ``chaos``, persisted — it must survive a controller
        kill mid-scenario) and fans out on the ``chaos`` pubsub channel;
        nodelets re-arm and forward to their workers."""
        import json as _json

        from ..util import fault_injection as fi
        ns = self.kv.setdefault(fi.CHAOS_KV_NS, {})
        if data.get("clear"):
            if ns.pop(fi.CHAOS_KV_KEY, None) is not None:
                self._p("kv_del", fi.CHAOS_KV_NS, fi.CHAOS_KV_KEY)
            fi.disarm()
            self._chaos_claims.clear()
            self._emit_event("INFO", "chaos", "fault plan cleared")
            await self._broadcast("chaos", {"plan": None})
            return None
        plan = data.get("plan")
        if plan is not None:
            raw = _json.dumps(plan).encode()
            ns[fi.CHAOS_KV_KEY] = raw
            self._p("kv_put", fi.CHAOS_KV_NS, fi.CHAOS_KV_KEY, raw)
            fi.arm(plan)
            self._emit_event("WARNING", "chaos",
                             f"fault plan applied ({len(plan)} rules)")
            await self._broadcast("chaos", {"plan": plan})
        cur = ns.get(fi.CHAOS_KV_KEY)
        return _json.loads(cur) if cur else None

    async def _h_chaos_claim(self, conn, data):
        """First-claimer-wins gate for `once` fault rules: exactly one
        process cluster-wide fires the fault, every other matching
        process gets False and skips it."""
        rid = data["id"]
        if rid in self._chaos_claims:
            return False
        self._chaos_claims.add(rid)
        return True

    async def _h_metrics_text(self, conn, data):
        """Prometheus exposition of controller runtime metrics
        (reference: GCS stats export, metric_defs.cc); gauges refresh at
        scrape time."""
        from .. import metrics
        rtm.snapshot_controller(self)
        return metrics.prometheus_text()

    async def _h_rpc_attribution(self, conn, data):
        """Per-op dispatch attribution of THIS controller process —
        count, time-in-handler, latency quantiles, payload bytes — plus
        the WAL append/fsync timing and asyncio loop lag riding along
        (the instruments item 4's serialization hunt reads)."""
        out = {"proc": "controller", "addr": self.address,
               "ops": rpc.attribution_rows(),
               "lanes": rpc.lane_stats(),
               "overload": self.overload.snapshot(),
               "loop_lag": {"ewma_ms": self._lag_ewma * 1e3,
                            "max_ms": self._lag_max * 1e3}}
        if self.pstore is not None:
            out["wal"] = dict(self.pstore.timing)
        return out

    async def _h_metrics_history(self, conn, data):
        """This controller's metrics-history ring (bounded, fixed-
        interval counter deltas + gauges; core/metrics_history.py)."""
        rtm.snapshot_controller(self)
        return self.metrics_ring.to_wire(last=data.get("last"))

    async def _h_debug_capture(self, conn, data):
        """Manual / remotely-triggered flight-recorder capture.  Manual
        grabs (``ray-tpu debug capture``) bypass the per-trigger rate
        limit; component-reported triggers (a nodelet's OOM kill, an
        executor's elastic repair) go through it."""
        trigger = data.get("trigger") or "manual"
        reason = data.get("reason") or ""
        if not GlobalConfig.flight_recorder_enabled:
            return {"ok": False, "error": "flight recorder disabled"}
        if trigger == "manual":
            path = await self.flight.capture("manual", reason,
                                             data.get("meta"))
            return {"ok": True, "path": path}
        self.flight.trigger(trigger, reason, **(data.get("meta") or {}))
        return {"ok": True}

    # ------------------------------------------------------ high availability
    async def _h_ha_status(self, conn, data):
        """Role / epoch / replication-lag probe — served by every role
        (clients use it to find the leader among the address list)."""
        return self.ha.status()

    async def _h_ha_register_standby(self, conn, data):
        """A hot standby joins (leader only — the gate rejects this on a
        non-leader, which redirects the standby to the real leader)."""
        if self.pstore is None:
            return {"error": "leader has no persist dir: HA replication "
                             "needs a WAL to stream"}
        peer_epoch = int(data.get("epoch", 0))
        if peer_epoch > self.ha.epoch:
            # a standby that has durably seen a newer epoch must not
            # join us — we are the stale side of a partition
            await self.ha.fence(peer_epoch, "standby joined with a "
                                            "newer epoch")
            return {"_not_leader": True, "leader": self.ha.leader_addr,
                    "epoch": self.ha.epoch}
        return self.ha.add_standby(data["addr"], conn)

    async def _h_ha_replicate(self, conn, data):
        """Standby side: apply + durably append one batch of the
        leader's WAL records; the reply is the leader's sync_floor ack."""
        ha = self.ha
        if ha.is_leader:
            return {"stale": True, "epoch": ha.epoch,
                    "leader": self.address}
        if int(data.get("epoch", 0)) < ha.epoch:
            return {"stale": True, "epoch": ha.epoch,
                    "leader": ha.leader_addr}
        if ha.tables is None or int(data["from_seq"]) != ha.applied_seq + 1:
            return {"resync": True}
        from . import persistence
        for blob in data["records"]:
            rec = persistence._unpack(blob)
            persistence._apply(ha.tables, rec)
            if self.pstore is not None:
                self.pstore.append_replica(rec)
        ha.applied_seq = int(data["to_seq"])
        ha.last_lease = time.monotonic()
        return {"ok": True, "seq": ha.applied_seq}

    async def _h_ha_sync_snapshot(self, conn, data):
        """Standby side: full-state resync after the incremental stream
        broke (lag bound blown, dropped records, fresh registration)."""
        ha = self.ha
        if ha.is_leader:
            return {"stale": True, "epoch": ha.epoch,
                    "leader": self.address}
        if int(data.get("epoch", 0)) < ha.epoch:
            return {"stale": True, "epoch": ha.epoch,
                    "leader": ha.leader_addr}
        ha.adopt_snapshot(data)
        return {"ok": True, "seq": ha.applied_seq}

    async def _h_ha_lease(self, conn, data):
        if not self.ha.is_leader \
                and int(data.get("epoch", 0)) >= self.ha.epoch:
            self.ha.last_lease = time.monotonic()
            # the renewal carries the leader's durable WAL seq: the
            # standby's own view of its replay lag (leader_seq -
            # applied_seq) surfaces in ha_status / `controller status`
            self.ha.leader_seq = max(self.ha.leader_seq,
                                     int(data.get("seq", 0) or 0))
        return True

    async def _h_ha_fence(self, conn, data):
        """A promoted leader fences its predecessor explicitly (the
        passive path — epoch stamps on client RPCs — also works)."""
        await self.ha.fence(int(data["epoch"]), "fenced by promoted leader",
                            data.get("leader"))
        return True

    async def start(self):
        await self.server.start()
        await self.ha.start()
        self._tasks.append(asyncio.ensure_future(self._health_check_loop()))
        self._tasks.append(asyncio.ensure_future(self._actor_scheduler_loop()))
        self._tasks.append(asyncio.ensure_future(self._quarantine_ttl_loop()))
        from ..util import tracing
        tracing.configure("controller")
        tracing.claim_flusher()
        self._tasks.append(asyncio.ensure_future(self._trace_flush_loop()))
        # self-observation: asyncio loop-lag probe + metrics-history ring
        # (gauges refreshed before each sample so the ring is live)
        self._tasks.append(asyncio.ensure_future(rpc.loop_lag_monitor(self)))
        self._tasks.append(asyncio.ensure_future(
            self.metrics_ring.run(
                refresh=lambda: rtm.snapshot_controller(self))))
        self._tasks.append(asyncio.ensure_future(self.overload.run()))
        return self

    async def _trace_flush_loop(self):
        """The controller appends its own lifecycle spans straight to
        the log every other process ships to over RPC."""
        from ..util import tracing
        while True:
            await asyncio.sleep(GlobalConfig.trace_flush_interval_s)
            batch = tracing.flush_batch()
            if batch is not None:
                self._trace_append(batch)

    async def stop(self):
        await self.ha.stop()
        for t in self._tasks:
            t.cancel()
        await self.server.stop()

    @property
    def address(self) -> str:
        return f"{self.server.host}:{self.server.port}"

    # ---------------------------------------------------------------- helpers
    def _views(self) -> Dict[str, NodeView]:
        return {nid: rec.view for nid, rec in self.nodes.items()}

    def _bump_view(self, node_id: Optional[str] = None):
        """Advance the global Lamport counter; when a node is named, stamp
        its view so delta syncs (``_h_heartbeat``) pick the change up."""
        self.view_version += 1
        if node_id is not None:
            rec = self.nodes.get(node_id)
            if rec is not None:
                rec.view.version = self.view_version

    async def _broadcast(self, channel: str, data: Any):
        """Buffered pub: events are coalesced per subscriber and flushed as
        one ``pub_batch`` frame (reference: the batched long-poll publisher,
        src/ray/pubsub/publisher.h + README — one wire message per
        subscriber per flush instead of per event; matters for the
        high-rate ``logs`` channel)."""
        rtm.PUBSUB_MESSAGES.inc(tags={"channel": channel})
        cap = GlobalConfig.pubsub_max_buffer
        for conn in list(self.subscribers.get(channel, ())):
            if conn.closed:
                self.subscribers[channel].discard(conn)
                continue
            buf = self._pub_buf.setdefault(id(conn), (conn, []))[1]
            buf.append((channel, data))
            # bounded per-subscriber buffer: a slow consumer drops its
            # OLDEST event and is told to resync the channel snapshot
            # instead of running the controller out of memory
            if 0 < cap < len(buf):
                dropped_ch, _ = buf.pop(0)
                rtm.PUBSUB_DROPPED.inc(tags={"channel": dropped_ch})
                self._pub_resync.setdefault(id(conn), set()).add(
                    dropped_ch)
        if self._pub_buf and self._pub_flusher is None:
            self._pub_flusher = asyncio.ensure_future(self._flush_pubs())

    async def _flush_pubs(self):
        try:
            while self._pub_buf:
                buf, self._pub_buf = self._pub_buf, {}
                resync, self._pub_resync = self._pub_resync, {}
                for cid, (conn, events) in buf.items():
                    if conn.closed:
                        continue
                    chans = resync.pop(cid, None)
                    try:
                        if chans:
                            # overflow happened: force the batch form so
                            # the resync list rides along
                            await conn.notify(
                                "pub_batch", {"events": events,
                                              "resync": sorted(chans)})
                        elif len(events) == 1:
                            ch, data = events[0]
                            await conn.notify("pub:" + ch, data)
                        else:
                            await conn.notify("pub_batch",
                                              {"events": events})
                    except Exception:
                        pass
                # resync owed to conns with no buffered events this round
                for cid, chans in resync.items():
                    self._pub_resync.setdefault(cid, set()).update(chans)
                if self._pub_buf:
                    await asyncio.sleep(          # coalesce the burst
                        GlobalConfig.pubsub_coalesce_s)
        finally:
            self._pub_flusher = None

    # ------------------------------------------------------------- node table
    async def _h_ping(self, conn, data):
        return "pong"

    async def _h_credit_request(self, conn, data):
        """Grant a submission-credit window sized by the overload state
        (drivers call this; nodelets get credits on the heartbeat
        reply).  Rides the liveness lane so a grant is never queued
        behind the very backlog it regulates."""
        return {"credits": self.overload.credits_for(
                    int(data.get("want", 0))),
                "state": self.overload.state,
                "retry_after_s": GlobalConfig.overload_shed_retry_after_s}

    async def _h_register_node(self, conn, data):
        view = NodeView(data["node_id"], data["addr"], data["resources"],
                        data["resources"], True, data.get("labels"))
        self.nodes[data["node_id"]] = NodeRecord(view, conn)
        conn.peer_info["node_id"] = data["node_id"]
        conn.on_close = self._node_conn_closed
        if data["node_id"] in self.suspects:
            # the quarantined node's link healed (its reconnect loop
            # re-registered): rejoin with actors/objects untouched
            await self._rejoin_node(data["node_id"])
        if data["node_id"] in self.draining:
            # re-registration of a node whose drain our restart (or a
            # dropped connection) interrupted: stay out of the placement
            # pool and resume the drain with a fresh default budget
            view.draining = True
            if data["node_id"] not in self._drain_tasks:
                self._start_drain(data["node_id"],
                                  GlobalConfig.drain_timeout_s)
        self._bump_view(data["node_id"])
        self.config_snapshot.update(data.get("config") or {})
        await self._broadcast("nodes", {"event": "added", "node": view.to_wire()})
        self._pending_actor_wakeup.set()
        return {"view": [v.to_wire() for v in self._views().values()],
                "view_version": self.view_version,
                "config": self.config_snapshot}

    def _node_conn_closed(self, conn):
        nid = conn.peer_info.get("node_id")
        if nid and nid in self.nodes \
                and self.nodes[nid].conn is conn:
            # a lost controller link is not proof of death: peers may
            # still reach the node (controller-only partition) — the
            # suspect path decides
            asyncio.ensure_future(
                self._on_node_silent(nid, "connection lost"))

    async def _h_heartbeat(self, conn, data):
        """Resource report + versioned view sync in one round trip.

        The reply carries only views stamped NEWER than the reporter's
        high-water mark (``view_version`` it last applied) — the
        versioned-delta design of the reference's RaySyncer
        (`ray_syncer.h:75-88` NodeState versions) in place of its older
        full-view broadcaster.  Availability changes bump the reporting
        node's stamp, so peers see fresh utilization within one heartbeat
        period instead of only at membership events."""
        nid = data["node_id"]
        rec = self.nodes.get(nid)
        if rec is None:
            return {"unknown_node": True}
        rec.last_heartbeat = time.monotonic()
        rec.demand = data.get("demand") or []
        if "clock_offset" in data:
            # RTT-midpoint clock-offset estimate the nodelet derived
            # from OUR `now` stamp on an earlier reply
            rec.clock_offset = float(data["clock_offset"])
        if nid in self.suspects:
            # the controller link healed inside the grace budget
            await self._rejoin_node(nid)
        # fold the piggybacked peer-reachability vector into the
        # connectivity matrix; changed unreachable sets ride the
        # versioned view sync so every nodelet's scheduler sees them
        reach = data.get("reach")
        if reach:
            self.reach.report(nid, reach)
            unreach = self.reach.unreachable_from(nid)
            if unreach != rec.view.unreachable:
                rec.view.unreachable = unreach
                self._bump_view(nid)
        # fold the disk-health watermark into the synced view: every
        # nodelet's scheduler stops picking red peers as spill-back
        # targets within one heartbeat period
        disk = data.get("disk")
        if isinstance(disk, dict):
            rec.disk = disk
            state = disk.get("state", "ok")
            if state != rec.view.disk:
                prev = rec.view.disk
                rec.view.disk = state
                self._bump_view(nid)
                if state == "red":
                    self._emit_event(
                        "WARN", "controller",
                        f"node {nid[:12]} disk red "
                        f"({disk.get('used_frac', 0):.2f} used): spill "
                        f"target excluded, proactive spill stopped",
                        node_id=nid)
                    self.flight.trigger(
                        "disk_pressure",
                        f"node {nid[:12]} at "
                        f"{disk.get('used_frac', 0):.2f} disk usage",
                        node_id=nid[:12])
                elif prev == "red":
                    self._emit_event(
                        "INFO", "controller",
                        f"node {nid[:12]} disk recovered to {state} "
                        f"({disk.get('used_frac', 0):.2f} used)",
                        node_id=nid)
        new_avail = ResourceSet(data["available"])
        new_total = ResourceSet(data["total"])
        if (new_avail.to_dict() != rec.view.available.to_dict()
                or new_total.to_dict() != rec.view.total.to_dict()):
            rec.view.available = new_avail
            rec.view.total = new_total
            self._bump_view(nid)
        if not rec.view.alive:
            rec.view.alive = True
            self._bump_view(nid)
        self._pending_actor_wakeup.set()
        # `now` lets the nodelet estimate its clock offset from the RTT
        # midpoint of this very round trip
        reply: Dict[str, Any] = {"view_version": self.view_version,
                                 "now": time.time()}
        # flow control rides the heartbeat: submission credits plus the
        # overload state (nodelets pause optional work under brownout)
        reply["overload"] = self.overload.state
        # poison-quarantine table (tiny) rides every beat: lease desks
        # cluster-wide fail a quarantined signature fast, and clears /
        # TTL expiries lift within one heartbeat period
        reply["quarantine"] = self.quarantine
        if data.get("want_credits"):
            reply["credits"] = self.overload.credits_for()
        known = data.get("view_version", -1)
        if known != self.view_version:
            reply["delta"] = [v.to_wire() for v in self._views().values()
                              if v.version > known]
        return reply

    async def _h_get_cluster_view(self, conn, data):
        return {"view": [v.to_wire() for v in self._views().values()],
                "view_version": self.view_version}

    async def _h_list_nodes(self, conn, data):
        return self.node_rows()

    def node_rows(self) -> List[Dict[str, Any]]:
        # demand rides the node ROWS, not the synced views — it churns
        # every heartbeat and would bloat the versioned delta stream
        out = []
        now = time.monotonic()
        for rec in self.nodes.values():
            nid = rec.view.node_id
            row = {**rec.view.to_wire(), "demand": rec.demand}
            row["state"] = ("DRAINING" if rec.view.draining and
                            rec.view.alive else
                            "SUSPECT" if nid in self.suspects and
                            rec.view.alive else
                            "ALIVE" if rec.view.alive else "DEAD")
            row["health"] = {
                "heartbeat_age_s": round(now - rec.last_heartbeat, 3),
                "heartbeat_timeout_s": self.heartbeat_timeout_s,
                "suspect_grace_s": GlobalConfig.suspect_grace_s,
                "peer_probe_fanout": GlobalConfig.peer_probe_fanout,
            }
            row["clock_offset_s"] = round(rec.clock_offset, 6)
            disk = getattr(rec, "disk", None)
            if disk:
                row["disk_used_frac"] = round(
                    float(disk.get("used_frac", 0.0)), 4)
            if nid in self.suspects:
                row["suspect_for_s"] = round(now - self.suspects[nid], 3)
                row["peers_reaching"] = sorted(
                    self.reach.reachable_by(nid, now))
            unreach = self.reach.unreachable_from(nid, now)
            if unreach:
                row["unreachable_peers"] = sorted(unreach)
            drain = self.draining.get(nid)
            if drain is not None:
                row["drain"] = dict(drain)
            out.append(row)
        return out

    # ------------------------------------------------------------ node drain
    async def _h_drain_node(self, conn, data):
        """Graceful, phased evacuation of one node ahead of a planned
        departure (maintenance event / preemption notice).  Phases:
        stop new leases and placements → evacuate sole-copy objects to
        peers → migrate actors elsewhere (no restart budget burned) →
        wait for in-flight tasks up to the deadline → cleanly
        deregister.  On deadline overrun the node takes the existing
        hard-death path, so lineage/restart recovery is the safety net
        rather than the plan."""
        node_id = data["node_id"]
        rec = self.nodes.get(node_id)
        if rec is None or not rec.view.alive:
            return {"ok": False, "error": f"unknown or dead node "
                                          f"{node_id[:16]}"}
        timeout_s = float(data.get("timeout_s") or
                          GlobalConfig.drain_timeout_s)
        if node_id in self._drain_tasks:
            task = self._drain_tasks[node_id]
        else:
            task = self._start_drain(node_id, timeout_s)
        if not data.get("wait", True):
            return {"ok": True, "started": True}
        outcome = await asyncio.shield(task)
        return {"ok": True, "outcome": outcome,
                "node_id": node_id}

    def _start_drain(self, node_id: str, timeout_s: float) -> asyncio.Task:
        task = asyncio.ensure_future(self._drain_node(node_id, timeout_s))
        self._drain_tasks[node_id] = task
        task.add_done_callback(
            lambda _t, nid=node_id: self._drain_tasks.pop(nid, None))
        return task

    async def _drain_node(self, node_id: str, timeout_s: float) -> str:
        from ..util import fault_injection as fi
        from ..util import tracing
        rec = self.nodes[node_id]
        t0 = time.time()
        deadline = time.monotonic() + timeout_s
        prog = self.draining.setdefault(
            node_id, {"in_flight": -1, "objects_left": -1})
        prog.update(phase="lease_stop", started=t0, timeout_s=timeout_s)
        self._p("drain", node_id)
        rec.view.draining = True
        self._bump_view(node_id)
        self._emit_event("WARNING", "controller",
                         f"draining node {node_id[:12]} "
                         f"(budget {timeout_s:g}s)", node_id=node_id)
        # immediate fan-out: nodelets stop spilling leases here, serve
        # routers drop this node's replicas without waiting for a poll
        await self._broadcast("nodes", {"event": "draining",
                                        "node_id": node_id})
        outcome = "completed"
        try:
            # Phase 1 — the nodelet refuses new leases/actor starts.
            reply = await rec.conn.call("drain", {"timeout_s": timeout_s},
                                        timeout=10)
            prog["in_flight"] = reply.get("in_flight", -1)
            prog["objects_left"] = reply.get("objects_left", -1)
            if fi.ACTIVE is not None and \
                    fi.ACTIVE.point("drain.deadline", node_id):
                raise _DrainDeadline()
            # Phase 2 — sole-copy objects move to live peers (the
            # nodelet pushes primaries; the object directory follows).
            prog["phase"] = "evacuate_objects"
            ev = await rec.conn.call(
                "drain_evacuate", {},
                timeout=max(2.0, deadline - time.monotonic()))
            prog["objects_left"] = ev.get("left", -1)
            # Phase 3 — actors restart elsewhere, proactively.
            prog["phase"] = "migrate_actors"
            await self._drain_migrate_actors(node_id, deadline)
            # Phase 4 — wait for in-flight leases/tasks to finish.
            prog["phase"] = "wait_in_flight"
            while True:
                await self._drain_migrate_actors(node_id, deadline)
                st = await rec.conn.call("drain_status", {}, timeout=5)
                prog["in_flight"] = st.get("in_flight", -1)
                prog["objects_left"] = st.get("objects_left", -1)
                if st.get("in_flight", 0) == 0 \
                        and not self._actors_on(node_id):
                    break
                if time.monotonic() > deadline:
                    raise _DrainDeadline()
                await asyncio.sleep(GlobalConfig.drain_poll_interval_s)
            # Phase 5 — clean deregister: the nodelet stops heartbeating
            # (it must not resurrect), then leaves the membership table.
            prog["phase"] = "deregister"
            await self._mark_node_dead(node_id, "drained")
            await self._fence_drained_node(node_id, rec)
        except _DrainDeadline:
            outcome = "deadline"
            self._emit_event(
                "ERROR", "controller",
                f"drain of node {node_id[:12]} overran its "
                f"{timeout_s:g}s budget; falling back to hard death",
                node_id=node_id)
            self.flight.trigger("drain_deadline",
                                f"budget {timeout_s:g}s overrun",
                                node_id=node_id[:12])
            await self._mark_node_dead(node_id, "drain deadline exceeded")
            await self._fence_drained_node(node_id, rec)
        except (rpc.RpcError, asyncio.TimeoutError, OSError) as e:
            outcome = "error"
            await self._mark_node_dead(node_id, f"drain failed: {e}")
            await self._fence_drained_node(node_id, rec)
        finally:
            self.draining.pop(node_id, None)
            self._p("drain_del", node_id)
            dur = time.time() - t0
            rtm.NODE_DRAINS.inc(tags={"outcome": outcome})
            rtm.DRAIN_DURATION.observe(dur, tags={"outcome": outcome})
            tracing.record_span(f"drain::{node_id[:12]}", "drain",
                                t0, time.time(), node_id=node_id[:12],
                                outcome=outcome)
        return outcome

    async def _fence_drained_node(self, node_id: str, rec: NodeRecord):
        """A drained (or drain-failed) node must STAY gone: the host is
        departing, so its nodelet stops heartbeating (a beat would
        resurrect the membership row) and the record leaves the table."""
        try:
            await rec.conn.call("drain_complete", {}, timeout=5)
        except (rpc.RpcError, OSError):
            pass
        self.nodes.pop(node_id, None)

    def _actors_on(self, node_id: str) -> List["ActorRecord"]:
        return [a for a in self.actors.values()
                if a.node_id == node_id
                and a.state in (ALIVE, PENDING_CREATION)]

    async def _drain_migrate_actors(self, node_id: str, deadline: float):
        """Restart every actor living on the draining node somewhere
        else — without burning restart budget (the departure is planned,
        not a failure).  The old worker is killed DETACHED (the nodelet
        forgets its actor binding first) so its death reports nothing."""
        rec = self.nodes.get(node_id)
        migrated = []
        for actor in self._actors_on(node_id):
            if actor.state != ALIVE:
                continue  # pending creations re-route via the retry path
            old_addr = actor.address
            strat = (actor.spec.get("strategy") or {})
            pinned_here = (strat.get("node_id") == node_id
                           and not strat.get("soft")) \
                or actor.spec.get("pg") is not None
            if pinned_here:
                # Hard node affinity / committed PG bundle: this actor
                # CANNOT live anywhere else — a planned departure retires
                # it (its owner replaces per-node actors: the serve proxy
                # reconciler re-creates proxies, train's FailureConfig
                # restarts the gang from its proactive drain checkpoint).
                await self._on_actor_failure(
                    actor, f"node {node_id[:12]} drained", intended=True)
                if rec is not None and old_addr:
                    try:
                        await rec.conn.call("detach_kill_worker",
                                            {"address": old_addr},
                                            timeout=10)
                    except rpc.RpcError:
                        pass
                continue
            self._migrating.add(actor.actor_id)
            rtm.ACTORS_MIGRATED.inc()
            self._emit_event(
                "INFO", "controller",
                f"migrating actor {actor.actor_id.hex()[:12]} "
                f"({actor.spec.get('fname', '?')}) off draining node "
                f"{node_id[:12]}", actor_id=actor.actor_id.hex())
            actor.state = RESTARTING
            actor.address = None
            actor.worker_id = None
            actor.node_id = None
            self._p("actor", self._actor_to_disk(actor))
            await self._broadcast("actors", actor.to_wire())
            if rec is not None and old_addr:
                try:
                    await rec.conn.call("detach_kill_worker",
                                        {"address": old_addr}, timeout=10)
                except rpc.RpcError:
                    pass
            migrated.append(actor)
        self._pending_actor_wakeup.set()
        # wait for the migrated actors to land elsewhere (or die for
        # reasons of their own) inside the drain budget
        while time.monotonic() < deadline:
            if all(a.state in (ALIVE, DEAD) for a in migrated):
                break
            await asyncio.sleep(0.1)
        for a in migrated:
            self._migrating.discard(a.actor_id)

    async def _health_check_loop(self):
        period = self.heartbeat_timeout_s / 3
        woke = time.monotonic()
        while True:
            await asyncio.sleep(period)
            now = time.monotonic()
            late, woke = now - woke - period, now
            if late > period:
                # This process was itself frozen or its loop held up (the
                # host stalls for seconds while a process holding the
                # chip starts or is torn down; a long GC).  Heartbeats
                # that arrived meanwhile are still queued behind this
                # wake-up, so that time says nothing about the nodes:
                # silence is counted in the time this loop was able to
                # listen.  A node that is really dead still runs out of
                # it, one period per round, however late every round is.
                for rec in self.nodes.values():
                    rec.last_heartbeat = min(now, rec.last_heartbeat + late)
            for nid, rec in list(self.nodes.items()):
                if not rec.view.alive:
                    continue
                if nid in self.suspects:
                    await self._check_suspect(nid, now)
                elif now - rec.last_heartbeat > self.heartbeat_timeout_s:
                    await self._on_node_silent(nid, "heartbeat timeout")
            # restored suspects whose node never re-registered (promoted
            # standby / controller restart): no NodeRecord exists, but
            # the grace budget still runs down
            for nid in list(self.suspects):
                if nid not in self.nodes:
                    await self._check_suspect(nid, now)

    async def _on_node_silent(self, node_id: str, reason: str):
        """The controller lost its own signal from a node (heartbeat
        timeout or dropped connection).  Binary death is wrong when the
        failure is a controller-only partition: if probing peers still
        reach the node it is quarantined SUSPECT instead — nothing is
        killed, and a link that heals inside ``suspect_grace_s`` rejoins
        the node with zero restarts.  Only a node the controller AND
        its peers cannot reach takes the hard-death path.  Peers are
        probed ON DEMAND first: the piggybacked gossip may be a probe
        round stale, and deciding a real death off a stale "reachable"
        would delay recovery by the whole freshness window."""
        from .reachability import classify_silent_node
        await self._solicit_probes(node_id)
        if classify_silent_node(self.reach, node_id) == "suspect":
            await self._mark_node_suspect(node_id, reason)
        else:
            await self._mark_node_dead(node_id, reason)

    async def _solicit_probes(self, node_id: str):
        """Ask a couple of live peers to probe ``node_id`` RIGHT NOW and
        fold the answers — fresh directed evidence replaces whatever
        stale entries the background gossip left, so suspect/dead
        decisions never wait out the freshness window."""
        if self.overload.state == "brownout":
            return  # optional on-demand probes pause under brownout
        rec_t = self.nodes.get(node_id)
        addr = rec_t.view.addr if rec_t is not None else None
        peers = sorted(
            (nid, rec) for nid, rec in self.nodes.items()
            if nid != node_id and rec.view.alive and not rec.view.draining
            and nid not in self.suspects and not rec.conn.closed)
        peers = peers[:max(1, GlobalConfig.peer_probe_fanout)]
        if not peers:
            return

        async def _ask(nid, rec):
            try:
                ok = await rec.conn.call(
                    "probe_peer_now", {"node_id": node_id, "addr": addr},
                    timeout=GlobalConfig.peer_probe_timeout_s * 2 + 1.0)
                return nid, bool(ok)
            except (rpc.RpcError, asyncio.TimeoutError, OSError):
                return nid, None  # the PROBER is unreachable: no evidence
        results = await asyncio.gather(*(_ask(n, r) for n, r in peers))
        for nid, ok in results:
            if ok is not None:
                self.reach.report(nid, {node_id: ok})

    async def _mark_node_suspect(self, node_id: str, reason: str):
        if node_id in self.suspects:
            return
        self.suspects[node_id] = time.monotonic()
        self._p("suspect", node_id)
        rec = self.nodes.get(node_id)
        if rec is not None:
            rec.view.suspect = True
            self._bump_view(node_id)
        self._emit_event(
            "WARNING", "controller",
            f"node {node_id[:12]} SUSPECT ({reason}): peers still reach "
            f"it — quarantined for up to "
            f"{GlobalConfig.suspect_grace_s:g}s, nothing killed",
            node_id=node_id)
        # routers/peers stop targeting it NOW, without waiting for the
        # versioned view delta to propagate
        await self._broadcast("nodes", {"event": "suspect",
                                        "node_id": node_id,
                                        "reason": reason})
        self.flight.trigger("node_suspect", reason, node_id=node_id[:12])

    async def _check_suspect(self, node_id: str, now: float):
        """Re-evaluate one quarantined node every health tick: grace
        exhausted or peer evidence gone → dead (today's recovery path);
        heartbeats resuming rejoin it in ``_h_heartbeat`` instead."""
        since = self.suspects.get(node_id)
        if since is None:
            return
        if now - since > GlobalConfig.suspect_grace_s:
            await self._suspect_died(
                node_id, f"suspect grace "
                         f"({GlobalConfig.suspect_grace_s:g}s) exceeded")
            return
        if not self.reach.reachable_by(node_id):
            # stale-looking quarantine: re-probe on demand before the
            # verdict (a heartbeat may already have rejoined it — the
            # dict re-check below covers the await window)
            await self._solicit_probes(node_id)
            if node_id in self.suspects \
                    and not self.reach.reachable_by(node_id):
                await self._suspect_died(
                    node_id, "unreachable by controller and probing peers")

    async def _suspect_died(self, node_id: str, reason: str):
        if node_id in self.nodes:
            await self._mark_node_dead(node_id, reason)
            return
        # no membership record (suspect restored by a promoted standby,
        # node never re-registered): run the death consequences directly
        self._clear_suspect(node_id, "died")
        self.reach.forget(node_id)
        self._emit_event("ERROR", "controller",
                         f"node {node_id[:12]} died: {reason}",
                         node_id=node_id)
        await self._broadcast("nodes", {"event": "dead",
                                        "node_id": node_id,
                                        "reason": reason})
        for oid, locs in list(self.object_dir.items()):
            locs.discard(node_id)
            if not locs:
                del self.object_dir[oid]
        for actor in list(self.actors.values()):
            if actor.node_id == node_id \
                    and actor.state in (ALIVE, PENDING_CREATION):
                await self._on_actor_failure(
                    actor, f"node {node_id} died: {reason}")

    def _clear_suspect(self, node_id: str, outcome: str) -> bool:
        """Leave quarantine (either direction); True if it was in it."""
        if self.suspects.pop(node_id, None) is None:
            return False
        self._p("suspect_del", node_id)
        rtm.SUSPECT_TRANSITIONS.inc(tags={"outcome": outcome})
        rec = self.nodes.get(node_id)
        if rec is not None and rec.view.suspect:
            rec.view.suspect = False
            self._bump_view(node_id)
        return True

    async def _rejoin_node(self, node_id: str):
        if not self._clear_suspect(node_id, "rejoined"):
            return
        self._emit_event(
            "INFO", "controller",
            f"node {node_id[:12]} rejoined from SUSPECT: link healed, "
            f"actors/objects intact", node_id=node_id)
        self._pending_actor_wakeup.set()
        await self._broadcast("nodes", {"event": "rejoined",
                                        "node_id": node_id})

    async def _mark_node_dead(self, node_id: str, reason: str):
        rec = self.nodes.get(node_id)
        if rec is None or not rec.view.alive:
            return
        self._clear_suspect(node_id, "died")
        self.reach.forget(node_id)
        rec.view.alive = False
        rec.view.suspect = False
        self._bump_view(node_id)
        if reason == "drained":
            # planned departure that quiesced in budget: not an error
            self._emit_event("INFO", "controller",
                             f"node {node_id[:12]} drained cleanly",
                             node_id=node_id)
        else:
            self._emit_event("ERROR", "controller",
                             f"node {node_id[:12]} died: {reason}",
                             node_id=node_id)
        await self._broadcast("nodes", {"event": "dead", "node_id": node_id,
                                        "reason": reason})
        # Purge object locations on that node.
        for oid, locs in list(self.object_dir.items()):
            locs.discard(node_id)
            if not locs:
                del self.object_dir[oid]
        # Restart or kill actors that lived there.
        for actor in list(self.actors.values()):
            if actor.node_id == node_id and actor.state in (ALIVE, PENDING_CREATION):
                await self._on_actor_failure(actor, f"node {node_id} died: {reason}")

    # ----------------------------------------------------------------- spans
    def _trace_append(self, data) -> None:
        """One process's flush: its new spans, or with ``reset`` its
        whole ring (it saw this controller restart or a flush fail).
        Kept per process in the same per-category ring the process
        keeps, never WAL-logged, and retained after the process exits."""
        from ..util import tracing
        ring = self.trace_log.get(data["key"])
        if ring is None or data.get("reset"):
            ring = self.trace_log[data["key"]] = tracing.SpanRing()
        ring.extend(data["spans"])

    async def _h_trace_append(self, conn, data):
        self._trace_append(data)
        return True

    async def _h_trace_dump(self, conn, data):
        return [ev for ring in self.trace_log.values()
                for ev in ring.events()]

    # --------------------------------------------------------------------- kv
    async def _h_kv_put(self, conn, data):
        ns_name = data.get("ns", "")
        ns = self.kv.setdefault(ns_name, {})
        key = data["key"]
        if data.get("overwrite", True) or key not in ns:
            ns[key] = data["value"]
            # persist=False: ephemeral liveness keys (dashboard-agent
            # heartbeats) must not append a WAL record per beat — they
            # are rewritten every ~2s and meaningless after a restart
            if data.get("persist", True):
                self._p("kv_put", ns_name, key, data["value"])
            return True
        return False

    async def _h_kv_get(self, conn, data):
        return self.kv.get(data.get("ns", ""), {}).get(data["key"])

    async def _h_kv_del(self, conn, data):
        hit = self.kv.get(data.get("ns", ""), {}).pop(data["key"], None) is not None
        if hit:
            self._p("kv_del", data.get("ns", ""), data["key"])
        return hit

    async def _h_kv_exists(self, conn, data):
        return data["key"] in self.kv.get(data.get("ns", ""), {})

    async def _h_kv_keys(self, conn, data):
        prefix = data.get("prefix", b"")
        return [k for k in self.kv.get(data.get("ns", ""), {}) if k.startswith(prefix)]

    # ------------------------------------------------------------------ actors
    async def _h_register_actor(self, conn, data):
        rtm.ACTORS_CREATED.inc()
        spec = data["spec"]
        actor_id = spec["actor_new"]
        name = data.get("name") or None
        if name and name in self.named_actors:
            existing = self.actors.get(self.named_actors[name])
            if existing is not None and existing.state != DEAD:
                if data.get("get_if_exists"):
                    return {"actor_id": existing.actor_id, "existing": True}
                return {"error": f"actor name {name!r} already taken"}
        rec = ActorRecord(actor_id, spec, name, data.get("max_restarts", 0),
                          data.get("detached", False))
        self.actors[actor_id] = rec
        if name:
            self.named_actors[name] = actor_id
        self._p("actor", self._actor_to_disk(rec))
        self._pending_actor_wakeup.set()
        return {"actor_id": actor_id, "existing": False}

    async def _actor_scheduler_loop(self):
        """Drives PENDING/RESTARTING actors toward ALIVE, like the
        reference's GcsActorScheduler (gcs_actor_scheduler.cc:53-55).
        Creations run CONCURRENTLY (one task per actor): a gang actor's
        constructor may block until its peers exist (mesh-join barriers),
        so awaiting one creation before scheduling the next would deadlock
        every gang of size > 1."""
        while True:
            self._pending_actor_wakeup.clear()
            for actor in list(self.actors.values()):
                if actor.state in (PENDING_CREATION, RESTARTING) \
                        and actor.node_id is None \
                        and time.monotonic() >= actor.restart_at \
                        and not getattr(actor, "scheduling", False):
                    actor.scheduling = True
                    asyncio.ensure_future(self._schedule_one(actor))
            try:
                await asyncio.wait_for(self._pending_actor_wakeup.wait(), timeout=0.5)
            except asyncio.TimeoutError:
                pass

    async def _schedule_one(self, actor: ActorRecord):
        # NOTE: creations stay concurrent and unbounded here — gang-actor
        # constructors block on their peers, so serializing dispatch
        # would deadlock gangs.  The 5k-burst thundering herd is bounded
        # on the NODELET side instead (admission semaphore around the
        # worker-pop loop, released before the blocking create_actor
        # push — nodelet._h_start_actor).
        try:
            await self._try_schedule_actor(actor)
        finally:
            actor.scheduling = False
            # A PROGRESS pass (the actor got a node, or left the pending
            # states) re-wakes the scheduler immediately — peers waiting
            # on it (gangs, PG bundles) proceed at once.  A NO-PROGRESS
            # pass re-wakes on a short timer instead: waking
            # unconditionally made one unschedulable actor spin the loop
            # at 100% CPU (every pass re-queued it, which re-woke the
            # pass) — a promoted standby hit this hard, with every
            # restored actor pending until the nodelets re-register.
            if actor.node_id is not None \
                    or actor.state not in (PENDING_CREATION, RESTARTING):
                self._pending_actor_wakeup.set()
            else:
                asyncio.get_event_loop().call_later(
                    0.05, self._pending_actor_wakeup.set)

    async def _try_schedule_actor(self, actor: ActorRecord):
        spec = TaskSpec(actor.spec)
        strategy = dict(spec.scheduling_strategy)
        pg_id = actor.spec.get("pg")
        if pg_id:
            pg = self.pgs.get(pg_id)
            if pg is None or pg.state != "CREATED":
                return  # wait for the PG
            strategy["node_id"] = pg.node_ids[max(actor.spec.get("bundle", 0), 0)]
        views = self._views()
        now = time.monotonic()
        for n, expiry in list(actor.avoid_nodes.items()):
            if expiry < now:
                del actor.avoid_nodes[n]
        # Schedule around nodes that recently reported actor-cap
        # saturation — but NEVER prune a node the strategy pins (PG
        # bundle / node affinity): pruning the pinned node makes
        # hybrid_policy return None forever even after the cap frees.
        pinned = strategy.get("node_id")
        if actor.avoid_nodes:
            pruned = {k: v for k, v in views.items()
                      if k not in actor.avoid_nodes or k == pinned}
            if pruned:
                views = pruned
        node_id = hybrid_policy(views, spec.resources, None,
                                strategy=strategy)
        if node_id is None:
            return
        rec = self.nodes.get(node_id)
        if rec is None or not rec.view.alive or rec.view.draining \
                or rec.view.suspect:
            return
        actor.node_id = node_id
        t_place = time.time()
        try:
            result = await rec.conn.call("start_actor", {"spec": actor.spec},
                                         timeout=120)
        except Exception as e:
            actor.node_id = None
            await self._on_actor_failure(actor, f"creation RPC failed: {e}")
            return
        if not result.get("ok"):
            actor.node_id = None
            if result.get("saturated"):
                actor.avoid_nodes[node_id] = time.monotonic() + 5.0
            if result.get("retry"):
                self._pending_actor_wakeup.set()
            else:
                await self._on_actor_failure(actor, result.get("error", "creation failed"))
        else:
            # actor placement span: controller pick -> worker dedicated
            # (the central-scheduling leg tasks never take)
            from ..util import tracing
            tracing.record_span(
                f"schedule_actor::{spec.function_name}", "sched",
                t_place, time.time(),
                task_id=spec.task_id.hex(), trace=spec.trace_id,
                actor_id=actor.actor_id.hex(), node_id=node_id[:12])

    async def _h_actor_alive(self, conn, data):
        """Called by the actor's worker process once the instance exists."""
        actor = self.actors.get(data["actor_id"])
        if actor is None:
            return False
        self._migrating.discard(actor.actor_id)
        actor.state = ALIVE
        actor.address = data["address"]
        actor.worker_id = data["worker_id"]
        actor.node_id = data["node_id"]
        self._p("actor", self._actor_to_disk(actor))
        self._notify_actor_waiters(actor)
        await self._broadcast("actors", actor.to_wire())
        return True

    def _notify_actor_waiters(self, actor: ActorRecord):
        """Resolve every parked ``wait_actor`` future at the FSM
        transition that settles it (ALIVE, DEAD or QUARANTINED) —
        waiters are event-driven, not poll-driven."""
        for fut in actor.waiters:
            if not fut.done():
                fut.set_result(actor.state)
        actor.waiters.clear()

    async def _h_wait_actor(self, conn, data):
        actor = self.actors.get(data["actor_id"])
        if actor is None:
            return {"error": "no such actor"}
        timeout = data.get("timeout", 60.0)
        deadline = time.monotonic() + timeout
        while actor.state not in (ALIVE, DEAD, QUARANTINED):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return {"state": actor.state, "timeout": True}
            fut = asyncio.get_event_loop().create_future()
            actor.waiters.append(fut)
            try:
                await asyncio.wait_for(fut, timeout=remaining)
            except asyncio.TimeoutError:
                return {"state": actor.state, "timeout": True}
            finally:
                if fut in actor.waiters:
                    actor.waiters.remove(fut)
        return actor.to_wire()

    async def _h_get_actor(self, conn, data):
        actor = self.actors.get(data["actor_id"])
        return actor.to_wire() if actor else None

    async def _h_list_actors(self, conn, data):
        return [a.to_wire() for a in self.actors.values()]

    async def _h_get_named_actor(self, conn, data):
        aid = self.named_actors.get(data["name"])
        if aid is None:
            return None
        actor = self.actors.get(aid)
        if actor is None or actor.state == DEAD:
            return None
        return actor.to_wire() | {"spec": actor.spec}

    async def _h_report_actor_death(self, conn, data):
        actor = self.actors.get(data["actor_id"])
        if actor is None:
            return False
        await self._on_actor_failure(actor, data.get("reason", "worker died"),
                                     intended=data.get("intended", False))
        return True

    async def _h_report_worker_failure(self, conn, data):
        """Nodelet tells us a worker process died; fail its actor if any."""
        actor_id = data.get("actor_id")
        if actor_id:
            actor = self.actors.get(actor_id)
            if actor is not None:
                await self._on_actor_failure(
                    actor, data.get("reason", "worker crashed"),
                    cause=data.get("cause"))
        return True

    # ------------------------------------------------- poison quarantine
    def _quarantine_put(self, rec: dict) -> None:
        """Declare one quarantine: WAL it (it must survive failover),
        count it, capture an incident bundle, tell the operator."""
        self.quarantine[rec["sig"]] = rec
        self._p("quarantine", rec)
        rtm.QUARANTINES.inc(tags={"kind": rec.get("kind", "task")})
        nodes = sorted({e.get("node", "")[:12]
                        for e in rec.get("evidence", ())})
        self._emit_event(
            "ERROR", "controller",
            f"{rec.get('kind', 'task')} signature {rec['sig']!r} "
            f"quarantined as poison after "
            f"{len(rec.get('evidence', ()))} worker deaths on "
            f"{len(nodes)} node(s) {nodes}; clears at TTL or "
            f"`ray-tpu quarantine clear`", sig=rec["sig"])
        self.flight.trigger(
            "crash_loop",
            f"{rec.get('kind', 'task')} signature {rec['sig']} "
            f"quarantined ({len(rec.get('evidence', ()))} deaths)",
            sig=rec["sig"])

    def _quarantine_remove(self, sig: str, reason: str) -> bool:
        rec = self.quarantine.pop(sig, None)
        if rec is None:
            return False
        self._p("quarantine_del", sig)
        self._emit_event("INFO", "controller",
                         f"quarantine lifted for {sig!r} ({reason})",
                         sig=sig)
        aid = rec.get("actor_id")
        if aid is not None:
            actor = self.actors.get(bytes.fromhex(aid))
            if actor is not None and actor.state == QUARANTINED:
                # budget refreshed: the crash-loop actor gets another
                # rolling window of restarts
                actor.state = RESTARTING
                actor.death_cause = None
                actor.restart_stamps = []
                actor.restart_at = 0.0
                self._p("actor", self._actor_to_disk(actor))
                self._pending_actor_wakeup.set()
        return True

    async def _h_report_task_crash(self, conn, data):
        """Crash-ledger entry from a nodelet whose leased worker died.

        Every leased death lands here (the cause carries its shape);
        only POISON-shaped causes count toward the quarantine threshold
        — preemption-shaped deaths (chaos kills, planned kills) retry
        freely forever.  The reply returns the fresh verdict plus the
        window's crash sites, so the reporting nodelet (and the driver
        blocked on its death-info query) see the ledger state with zero
        propagation latency."""
        sig = data["sig"]
        cause = data.get("cause") or {}
        now = time.time()
        win = GlobalConfig.poison_window_s
        hits = self.crash_ledger.setdefault(sig, [])
        hits.append({"ts": now, "node": data.get("node_id", ""),
                     "cause": cause.get("kind", "unknown"),
                     "poison": bool(cause.get("poison"))})
        hits[:] = [h for h in hits if now - h["ts"] <= win]
        q = self.quarantine.get(sig)
        thr = GlobalConfig.poison_task_threshold
        if q is None and thr > 0 \
                and sum(1 for h in hits if h["poison"]) >= thr:
            q = {"sig": sig, "kind": "task", "since": now,
                 "until": now + GlobalConfig.poison_quarantine_ttl_s,
                 "evidence": [{"ts": h["ts"], "node": h["node"],
                               "cause": h["cause"]} for h in hits]}
            self._quarantine_put(q)
        return {"quarantined": q,
                "avoid": sorted({h["node"] for h in hits if h["node"]})}

    async def _h_quarantine_list(self, conn, data):
        return sorted(self.quarantine.values(),
                      key=lambda r: r.get("since", 0))

    async def _h_quarantine_clear(self, conn, data):
        sigs = [data["sig"]] if data.get("sig") else list(self.quarantine)
        return {"cleared": [s for s in sigs if self._quarantine_remove(
            s, "cleared by operator")]}

    async def _quarantine_ttl_loop(self):
        """Leader-only expiry sweep.  TTL expiry NEVER happens inside
        WAL replay (_apply is clock-free by lint); the runtime loop
        appends an explicit `quarantine_del`, so replicas replay the
        same decision instead of re-deriving it from their own clocks."""
        while True:
            await asyncio.sleep(0.5)
            if not self.ha.is_leader:
                continue
            now = time.time()
            for sig, rec in list(self.quarantine.items()):
                if now >= rec.get("until", 0):
                    try:
                        self._quarantine_remove(sig, "TTL expired")
                    except WalWriteError:
                        break  # fenced: the new leader owns expiry now
            for sig, hits in list(self.crash_ledger.items()):
                hits[:] = [h for h in hits
                           if now - h["ts"] <= GlobalConfig.poison_window_s]
                if not hits:
                    del self.crash_ledger[sig]

    async def _on_actor_failure(self, actor: ActorRecord, reason: str,
                                intended: bool = False,
                                cause: Optional[dict] = None):
        if actor.state == DEAD:
            return
        if actor.actor_id in self._migrating and actor.worker_id is None \
                and actor.state == RESTARTING:
            # the OLD incarnation dying IS the drain migration — the
            # reschedule is already queued; burning restart budget (or
            # killing a max_restarts=0 actor) here would turn a planned
            # departure into a failure
            return
        actor.address = None
        actor.worker_id = None
        actor.node_id = None
        # Rolling-window restart accounting: only stamps inside the
        # window hold budget (num_restarts stays the lifetime total for
        # observability).
        now_wall = time.time()
        win = GlobalConfig.actor_restart_window_s
        actor.restart_stamps = [s for s in actor.restart_stamps
                                if now_wall - s[0] <= win]
        used = len(actor.restart_stamps)
        kind = (cause or {}).get("kind", "?")
        node = (cause or {}).get("node", "")
        if not intended and used < actor.max_restarts:
            actor.restart_stamps.append([now_wall, node, kind])
            actor.num_restarts += 1
            rtm.ACTORS_RESTARTED.inc()
            actor.state = RESTARTING
            # full-jitter exponential backoff between incarnations: a
            # crash-looping constructor must not grind the scheduler
            # (and its node's worker pool) at restart_delay granularity
            from ..util.backoff import ExponentialBackoff
            bo = ExponentialBackoff(
                base=GlobalConfig.actor_restart_backoff_base_s,
                cap=GlobalConfig.actor_restart_backoff_cap_s)
            bo.attempt = used
            actor.restart_at = time.monotonic() + bo.next_delay()
            self._pending_actor_wakeup.set()
        elif not intended and actor.max_restarts > 0 \
                and bool((cause or {}).get("poison")) \
                and GlobalConfig.poison_task_threshold > 0:
            # budget exhausted INSIDE the window by poison-shaped deaths:
            # crash loop — quarantine instead of a terminal DEAD, so the
            # TTL (or an operator clear) can give it another window
            actor.state = QUARANTINED
            actor.death_cause = f"crash loop ({used} restarts in " \
                                f"{win:.0f}s window): {reason}"
            sig = (f"actor:{actor.spec.get('fname', '?')}:"
                   f"{actor.actor_id.hex()[:12]}")
            if sig not in self.quarantine:
                self._quarantine_put({
                    "sig": sig, "kind": "actor", "since": now_wall,
                    "until": now_wall +
                    GlobalConfig.poison_quarantine_ttl_s,
                    "actor_id": actor.actor_id.hex(),
                    "evidence": [{"ts": s[0], "node": s[1],
                                  "cause": s[2]}
                                 for s in actor.restart_stamps]
                    + [{"ts": now_wall, "node": node, "cause": kind}]})
            self._notify_actor_waiters(actor)
        else:
            actor.state = DEAD
            actor.death_cause = reason
            if not intended:
                self._emit_event(
                    "ERROR", "controller",
                    f"actor {actor.actor_id.hex()[:12]} "
                    f"({actor.spec.get('fname', '?')}) died: {reason}",
                    actor_id=actor.actor_id.hex())
            if actor.name:
                self.named_actors.pop(actor.name, None)
            self._notify_actor_waiters(actor)
        self._p("actor", self._actor_to_disk(actor))
        await self._broadcast("actors", actor.to_wire())

    async def _h_kill_actor(self, conn, data):
        actor = self.actors.get(data["actor_id"])
        if actor is None:
            return False
        if data.get("no_restart", True):
            actor.max_restarts = actor.num_restarts  # exhaust restarts
        addr = actor.address
        node = self.nodes.get(actor.node_id) if actor.node_id else None
        await self._on_actor_failure(actor, "killed via kill_actor",
                                     intended=data.get("no_restart", True))
        if node is not None and actor.worker_id is None and addr:
            try:
                await node.conn.call("kill_worker_at", {"address": addr}, timeout=5)
            except Exception:
                pass
        return True

    # --------------------------------------------------------- placement groups
    async def _h_create_placement_group(self, conn, data):
        pg = PGRecord(data["pg_id"], data["bundles"], data.get("strategy", "PACK"),
                      data.get("name", ""))
        self.pgs[pg.pg_id] = pg
        self._p("pg", self._pg_to_disk(pg))
        await self._try_create_pg(pg)
        return {"pg_id": pg.pg_id, "state": pg.state}

    async def _try_create_pg(self, pg: PGRecord):
        if pg.state != "PENDING":
            return
        placement = pack_bundles(self._views(), pg.bundles, pg.strategy)
        if placement is None:
            return
        # 2-phase commit: prepare on every node, then commit; abort on failure
        # (reference: placement_group_resource_manager.cc Prepare/Commit).
        prepared: List[int] = []
        ok = True
        for idx, node_id in enumerate(placement):
            rec = self.nodes.get(node_id)
            if rec is None or not rec.view.alive:
                ok = False
                break
            try:
                r = await rec.conn.call("pg_prepare", {
                    "pg_id": pg.pg_id, "bundle_index": idx,
                    "resources": pg.bundles[idx]}, timeout=10)
                if not r:
                    ok = False
                    break
                prepared.append(idx)
            except Exception:
                ok = False
                break
        if not ok:
            for idx in prepared:
                rec = self.nodes.get(placement[idx])
                if rec:
                    try:
                        await rec.conn.call("pg_abort", {"pg_id": pg.pg_id,
                                                         "bundle_index": idx})
                    except Exception:
                        pass
            return
        committed: List[int] = []
        try:
            for idx, node_id in enumerate(placement):
                await self.nodes[node_id].conn.call("pg_commit", {
                    "pg_id": pg.pg_id, "bundle_index": idx}, timeout=10)
                committed.append(idx)
        except Exception:
            # A node died mid-commit: roll everything back so nothing leaks,
            # and leave the PG PENDING for the next attempt.
            for idx in range(len(placement)):
                rec = self.nodes.get(placement[idx])
                if rec is None or not rec.view.alive:
                    continue
                op = "pg_return" if idx in committed else "pg_abort"
                try:
                    await rec.conn.call(op, {"pg_id": pg.pg_id,
                                             "bundle_index": idx}, timeout=10)
                except Exception:
                    pass
            return
        pg.node_ids = placement
        pg.state = "CREATED"
        self._p("pg", self._pg_to_disk(pg))
        for ev in pg.waiters:
            ev.set()
        pg.waiters.clear()
        self._pending_actor_wakeup.set()
        await self._broadcast("pgs", pg.to_wire())

    async def _h_wait_placement_group(self, conn, data):
        pg = self.pgs.get(data["pg_id"])
        if pg is None:
            return {"error": "no such placement group"}
        deadline = time.monotonic() + data.get("timeout", 60.0)
        while pg.state == "PENDING":
            await self._try_create_pg(pg)
            if pg.state != "PENDING":
                break
            ev = asyncio.Event()
            pg.waiters.append(ev)
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return {"state": pg.state, "timeout": True}
            try:
                await asyncio.wait_for(ev.wait(), timeout=min(remaining, 0.5))
            except asyncio.TimeoutError:
                pass
        return pg.to_wire()

    async def _h_remove_placement_group(self, conn, data):
        pg = self.pgs.get(data["pg_id"])
        if pg is None:
            return False
        if pg.state == "CREATED":
            for idx, node_id in enumerate(pg.node_ids):
                rec = self.nodes.get(node_id)
                if rec is not None and rec.view.alive:
                    try:
                        await rec.conn.call("pg_return", {"pg_id": pg.pg_id,
                                                          "bundle_index": idx})
                    except Exception:
                        pass
        pg.state = "REMOVED"
        self._p("pg_del", pg.pg_id)
        await self._broadcast("pgs", pg.to_wire())
        return True

    async def _h_list_placement_groups(self, conn, data):
        return [p.to_wire() for p in self.pgs.values()]

    # ----------------------------------------------------------- object dir
    async def _h_object_location_add(self, conn, data):
        oid = data["object_id"]
        self.object_dir.setdefault(oid, set()).add(data["node_id"])
        if "size" in data:
            self.object_sizes[oid] = data["size"]
        for ev in self.object_waiters.pop(oid, []):
            ev.set()
        return True

    async def _h_object_location_remove(self, conn, data):
        oid = data["object_id"]
        locs = self.object_dir.get(oid)
        if locs:
            locs.discard(data["node_id"])
            if not locs:
                self.object_dir.pop(oid, None)
        return True

    async def _h_object_locations_get(self, conn, data):
        oid = data["object_id"]
        timeout = data.get("timeout", 0.0)
        deadline = time.monotonic() + timeout
        while True:
            locs = self.object_dir.get(oid)
            if locs:
                addrs = [self.nodes[n].view.addr for n in locs
                         if n in self.nodes and self.nodes[n].view.alive]
                ids = [n for n in locs if n in self.nodes and self.nodes[n].view.alive]
                if addrs:
                    return {"locations": addrs, "node_ids": ids,
                            "size": self.object_sizes.get(oid, 0)}
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return {"locations": [], "node_ids": [], "size": 0}
            ev = asyncio.Event()
            self.object_waiters.setdefault(oid, []).append(ev)
            try:
                await asyncio.wait_for(ev.wait(), timeout=remaining)
            except asyncio.TimeoutError:
                pass

    async def _h_object_replicate(self, conn, data):
        """Replicate an object onto a live peer node with a primary pin
        there (the drain-era ``pull {pin_primary}`` machinery).  The
        target is the caller's RING NEIGHBOR — the next alive,
        non-draining node after ``exclude_node`` in sorted-id order — so
        elastic train snapshots land deterministically off-host and one
        host's death never loses its own shard."""
        oid = data["object_id"]
        exclude = data.get("exclude_node")
        ring = sorted(nid for nid, rec in self.nodes.items()
                      if rec.view.alive and not rec.view.draining
                      and nid != exclude)
        if not ring:
            return {"ok": False, "error": "no live peer to replicate to"}
        target = data.get("node_id")
        if target is None:
            target = (next((n for n in ring if n > (exclude or "")),
                           ring[0]))
        rec = self.nodes.get(target)
        if rec is None or not rec.view.alive:
            return {"ok": False, "error": f"target {target!r} not alive"}
        try:
            r = await rec.conn.call(
                "pull", {"object_id": oid,
                         "timeout": float(data.get("timeout", 20.0)),
                         "pin_primary": True},
                timeout=float(data.get("timeout", 20.0)) + 10.0)
        except rpc.RpcError as e:
            return {"ok": False, "error": str(e), "node_id": target}
        return {"ok": bool(r.get("ok")), "node_id": target,
                "error": r.get("error")}

    async def _h_object_relay(self, conn, data):
        """Alternate-path fetch, relay rung: the requester exhausted its
        direct sources (asymmetric partition — every holder exists but
        the requester cannot reach them), so pick a MUTUALLY REACHABLE
        peer C (requester→C and C→holder both clean per the
        connectivity matrix), have C pull a copy, and hand its address
        back for the requester to refetch from.  The relay copy lands
        in the object directory like any replica, so even a raced
        retry finds it."""
        oid = data["object_id"]
        requester = data.get("node_id") or ""
        timeout = float(data.get("timeout", 10.0))
        now = time.monotonic()
        holders = {n for n in self.object_dir.get(oid, set())
                   if n != requester and n in self.nodes
                   and self.nodes[n].view.alive}
        if not holders:
            return {"ok": False, "error": "no live holder to relay from"}
        req_cant = self.reach.unreachable_from(requester, now)
        cands = []
        for nid, rec in self.nodes.items():
            if nid == requester or nid in holders:
                continue
            if not rec.view.alive or rec.view.draining \
                    or nid in self.suspects:
                continue
            if nid in req_cant:
                continue  # the requester can't reach this relay either
            cant = self.reach.unreachable_from(nid, now)
            if any(h not in cant for h in holders):
                cands.append((nid, rec))
        for nid, rec in sorted(cands, key=lambda p: p[0]):
            try:
                r = await rec.conn.call(
                    "pull", {"object_id": oid, "timeout": timeout},
                    timeout=timeout + 5.0)
            except (rpc.RpcError, asyncio.TimeoutError, OSError):
                continue
            if r.get("ok"):
                self._emit_event(
                    "INFO", "controller",
                    f"object {oid.hex()[:12]} relayed via node "
                    f"{nid[:12]} for partitioned requester "
                    f"{requester[:12]}", node_id=nid)
                return {"ok": True, "node_id": nid,
                        "addr": rec.view.addr}
        return {"ok": False,
                "error": "no mutually-reachable relay peer succeeded"}

    async def _h_free_objects(self, conn, data):
        """Immediate (unconditional) free — spilling/testing paths."""
        await self._do_free(data["object_ids"])
        return True

    # ------------------------------------------- distributed ref counting
    def _conn_holder(self, conn, data) -> str:
        h = data.get("holder")
        if h:
            return h
        key = f"conn:{id(conn)}"
        # First borrow through this connection: chain a close hook so a
        # crashed/exited process's borrows are swept (the reference gets
        # this from the owner failing its borrower RPC client).
        if not conn.peer_info.get("_ref_holder"):
            conn.peer_info["_ref_holder"] = key
            prev = conn.on_close

            def _closed(c, prev=prev, key=key):
                if prev:
                    prev(c)
                asyncio.ensure_future(self._clear_holder(key))
            conn.on_close = _closed
        return key

    async def _h_ref_inc(self, conn, data):
        holder = self._conn_holder(conn, data)
        for oid in data["object_ids"]:
            self.borrows.setdefault(oid, {})
            self.borrows[oid][holder] = self.borrows[oid].get(holder, 0) + 1
            hr = self.holder_refs.setdefault(holder, {})
            hr[oid] = hr.get(oid, 0) + 1
        return True

    async def _h_ref_dec(self, conn, data):
        holder = self._conn_holder(conn, data)
        freeable = []
        for oid in data["object_ids"]:
            if self._drop_borrow(oid, holder):
                freeable.append(oid)
        if freeable:
            await self._do_free(freeable)
        return True

    def _drop_borrow(self, oid: bytes, holder: str) -> bool:
        """Returns True if the object became freeable (pending + unborrowed)."""
        d = self.borrows.get(oid)
        if d is not None:
            n = d.get(holder, 0) - 1
            if n > 0:
                d[holder] = n
            else:
                d.pop(holder, None)
            if not d:
                self.borrows.pop(oid, None)
        hr = self.holder_refs.get(holder)
        if hr is not None:
            n = hr.get(oid, 0) - 1
            if n > 0:
                hr[oid] = n
            else:
                hr.pop(oid, None)
            if not hr:
                self.holder_refs.pop(holder, None)
        return oid in self.pending_free and not self.borrows.get(oid)

    async def _clear_holder(self, holder: str):
        """Drop every borrow held by a dead process / freed container."""
        oids = list(self.holder_refs.get(holder, {}).keys())
        freeable = []
        for oid in oids:
            d = self.borrows.get(oid)
            if d is not None:
                d.pop(holder, None)
                if not d:
                    self.borrows.pop(oid, None)
            if oid in self.pending_free and not self.borrows.get(oid):
                freeable.append(oid)
        self.holder_refs.pop(holder, None)
        if freeable:
            self.ref_stats["cascade_frees"] += len(freeable)
            await self._do_free(freeable)

    async def _h_free_request(self, conn, data):
        """Owner dropped its last local ref: free now if unborrowed, else
        defer until every borrower (process or container) lets go."""
        now, deferred = [], 0
        for oid in data["object_ids"]:
            if self.borrows.get(oid):
                self.pending_free.add(oid)
                deferred += 1
            else:
                now.append(oid)
        self.ref_stats["deferred_frees"] += deferred
        if now:
            await self._do_free(now)
        return True

    async def _h_list_objects(self, conn, data):
        """Cluster object table with node attribution (reference: `ray list
        objects` / `ray memory` via internal_api.py + state aggregator)."""
        out = []
        for oid, locs in self.object_dir.items():
            out.append({
                "object_id": oid.hex(),
                "size": self.object_sizes.get(oid, 0),
                "node_ids": sorted(locs),
                "pending_free": oid in self.pending_free,
                "borrows": {h: n
                            for h, n in self.borrows.get(oid, {}).items()},
            })
        # borrowed-but-not-located (inline/spilled) objects still show up
        for oid, holders in self.borrows.items():
            if oid not in self.object_dir:
                out.append({"object_id": oid.hex(), "size": 0,
                            "node_ids": [],
                            "pending_free": oid in self.pending_free,
                            "borrows": dict(holders)})
        return out

    async def _h_ref_counts(self, conn, data):
        """Debug/observability: outstanding borrows (ray memory equivalent)."""
        return {
            "borrows": {oid.hex(): {h: n for h, n in d.items()}
                        for oid, d in self.borrows.items()},
            "pending_free": [o.hex() for o in self.pending_free],
            "stats": dict(self.ref_stats),
        }

    async def _do_free(self, oids: List[bytes]):
        by_node: Dict[str, List[bytes]] = {}
        spill_ns = self.kv.get("spill", {})
        spill_paths: List[str] = []
        for oid in oids:
            self.pending_free.discard(oid)
            for nid in self.object_dir.pop(oid, set()):
                by_node.setdefault(nid, []).append(oid)
            self.object_sizes.pop(oid, None)
            # Sweep spill storage for freed objects (worker-spilled files are
            # registered here; shared-fs/single-machine sessions can unlink).
            path = spill_ns.pop(oid, None)
            if path is not None:
                spill_paths.append(path.decode()
                                   if isinstance(path, bytes) else path)
        if spill_paths:
            # off-loop: a batch free of spilled objects is N serial
            # unlinks — on the controller loop that stalls every
            # handler behind the disk (PR-13 loop-blocking lint)
            def _sweep(paths=spill_paths):
                for p in paths:
                    spill.delete_file(p)
            await asyncio.to_thread(_sweep)
        for nid, node_oids in by_node.items():
            rec = self.nodes.get(nid)
            if rec is not None and rec.view.alive:
                try:
                    await rec.conn.notify("free_local", {"object_ids": node_oids})
                except Exception:
                    pass
        # Containment cascade: refs pinned by a freed container are released
        # (may recursively free nested containers).
        for oid in oids:
            await self._clear_holder(f"obj:{oid.hex()}")
        return True

    # ---------------------------------------------------------------- pubsub
    # ----------------------------------------------------------------- events
    def _emit_event(self, severity: str, source: str, message: str,
                    **meta):
        self._event_seq += 1
        ev = {"seq": self._event_seq, "ts": time.time(),
              "severity": severity, "source": source, "message": message,
              "meta": meta}
        self.events.append(ev)
        asyncio.ensure_future(self._broadcast("events", ev))

    async def _h_report_event(self, conn, data):
        self._emit_event(data.get("severity", "INFO"),
                         data.get("source", "user"),
                         data.get("message", ""),
                         **(data.get("meta") or {}))
        return True

    async def _h_list_events(self, conn, data):
        sev = data.get("severity")
        limit = int(data.get("limit", 200))
        out = [e for e in self.events
               if sev is None or e["severity"] == sev]
        return out[-limit:]

    async def _h_subscribe(self, conn, data):
        self.subscribers.setdefault(data["channel"], set()).add(conn)
        return True

    async def _h_publish(self, conn, data):
        await self._broadcast(data["channel"], data["data"])
        return True

    # ------------------------------------------------------------------- jobs
    async def _h_register_job(self, conn, data):
        self.jobs[data["job_id"]] = {"start": time.time(), "driver": data.get("driver")}
        self._p("job", data["job_id"], self.jobs[data["job_id"]])
        return True

    async def _h_finish_job(self, conn, data):
        job_id = data["job_id"]
        if self.jobs.pop(job_id, None) is not None:
            self._p("job_del", job_id)
        # Kill the job's non-detached actors.
        for actor in list(self.actors.values()):
            if actor.detached or actor.state == DEAD:
                continue
            if actor.actor_id[:len(job_id)] == job_id:
                await self._on_actor_failure(actor, "job finished", intended=True)
        return True


async def run_controller(host: str, port: int,
                         heartbeat_timeout_s: Optional[float] = None,
                         persist_dir: Optional[str] = None,
                         standby_of: Optional[str] = None,
                         lease_timeout_s: Optional[float] = None):
    c = Controller(host, port, heartbeat_timeout_s, persist_dir=persist_dir,
                   standby_of=standby_of, lease_timeout_s=lease_timeout_s)
    await c.start()
    return c
