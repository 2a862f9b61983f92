"""Nodelet — the per-node daemon (raylet equivalent).

Owns the node's shared-memory object store segment, the worker pool
(/root/reference/src/ray/raylet/worker_pool.cc:276 StartWorkerProcess), the
local scheduler implementing the worker-lease protocol with spillback
(/root/reference/src/ray/raylet/node_manager.cc:1880 HandleRequestWorkerLease
+ cluster_task_manager.cc:44), placement-group bundle prepare/commit
(placement_group_resource_manager.cc:196), and node-to-node chunked object
transfer (object_manager.cc push/pull, object_manager.proto:22-63).

Drivers and workers on this node talk to the nodelet over TCP; the nodelet
holds one persistent connection to the controller for heartbeats, the cluster
resource view, and the object directory.
"""

from __future__ import annotations

import asyncio
import os
import signal
import subprocess
import sys
import time
import traceback
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Set

from . import accelerator, rpc, runtime_metrics as rtm, spill, worker_zygote
from ..util import fault_injection as fi
from .config import GlobalConfig
from .ids import NodeID, WorkerID
from .object_store import client as store_client
from .scheduling import NodeView, hybrid_policy
from .task_spec import ResourceSet, TaskSpec


# Resolved at import time: preexec_fn runs in the post-fork child of a
# (potentially) multithreaded parent, where import/dlopen can deadlock
# on inherited locks — the hook below must be a single pre-bound C call.
try:
    import ctypes as _ctypes
    import signal as _signal
    _libc_prctl = _ctypes.CDLL("libc.so.6", use_errno=True).prctl
    _SIGTERM = int(_signal.SIGTERM)
except Exception:              # non-glibc platform: hook becomes a no-op
    _libc_prctl = None
    _SIGTERM = 15


def _pdeathsig_term() -> None:
    """preexec hook: deliver SIGTERM to the child when its parent dies
    (PR_SET_PDEATHSIG) — covers SIGKILLed nodelets, which can never run
    their own teardown."""
    if _libc_prctl is not None:
        _libc_prctl(1, _SIGTERM, 0, 0, 0)  # PR_SET_PDEATHSIG == 1


class WorkerProc:
    def __init__(self, worker_id: bytes, proc: subprocess.Popen,
                 lang: str = "py", platform: str = accelerator.CPU):
        self.worker_id = worker_id
        self.proc = proc
        self.lang = lang          # "py" | "cpp" (executes native tasks)
        # the one JAX platform this process was started on; a worker is
        # only ever leased to work that wants the same one
        self.platform = platform
        self.port: Optional[int] = None
        self.registered = asyncio.Event()
        self.spawned_at = time.monotonic()
        # wall clock of the moment the nodelet set out to start this
        # process (`_spawn_worker` moves it back to its own entry): the
        # start of the worker's ``setup:worker_spawn`` span
        self.spawn_asked = time.time()
        self.state = "starting"   # starting | idle | leased | actor | dead
        self.lease_id: Optional[bytes] = None
        self.actor_id: Optional[bytes] = None
        self.conn: Optional[rpc.Connection] = None
        # function name of the task signature this worker was last leased
        # for — the death classifier's signature source (a worker chaos-
        # killed at execution start dies before it ever reports
        # task_state, so _running_tasks alone cannot attribute it)
        self.leased_fname: Optional[str] = None

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.port}"


# A worker that held four chips took 19.3 s from its exit to being reaped
# (my chip run, PR 21); past this it is stuck in the driver, not slow.
_CHIP_REAP_TIMEOUT_S = 60.0


def idle_worker_for(workers, lang: str, platform: str):
    """The idle worker that may take work wanting ``platform``: same
    language, and started on that platform — a process that has (or may
    have) initialised JAX on the other one is never reused across."""
    for w in workers:
        if w.state == "idle" and w.lang == lang and w.platform == platform:
            return w
    return None


class Lease:
    def __init__(self, lease_id: bytes, worker: WorkerProc, resources: ResourceSet):
        self.lease_id = lease_id
        self.worker = worker
        self.resources = resources


class Nodelet:
    def __init__(self, *, controller_addr: str, session_dir: str,
                 resources: Dict[str, float], host: str = "127.0.0.1", port: int = 0,
                 node_id: Optional[NodeID] = None,
                 object_store_memory: Optional[int] = None,
                 labels: Optional[Dict[str, str]] = None,
                 worker_env: Optional[Dict[str, str]] = None,
                 reserved_platform: Optional[str] = None):
        self.node_id = node_id or NodeID.from_random()
        # what a worker leased for a TPU reservation runs on: the TPU,
        # unless this node is held to the CPU (core/accelerator.py)
        self.reserved_platform = reserved_platform or \
            accelerator.reserved_platform(os.environ)
        self.controller_addr = controller_addr
        self.session_dir = session_dir
        self.labels = labels or {}
        self.worker_env = worker_env or {}
        self.server = rpc.RpcServer(host, port)
        self.total = ResourceSet(resources)
        self.available = ResourceSet(resources)
        self.store_path = os.path.join(
            "/dev/shm" if os.path.isdir("/dev/shm") else session_dir,
            f"rtstore-{self.node_id.hex()[:12]}")
        self.store_capacity = object_store_memory or (
            GlobalConfig.object_store_memory_mb * 1024 * 1024)
        self.store: Optional[store_client.StoreClient] = None
        self.controller: Optional[rpc.Connection] = None
        self.workers: Dict[bytes, WorkerProc] = {}
        self.leases: Dict[bytes, Lease] = {}
        self.view: Dict[str, NodeView] = {}
        self.view_version = -1
        self.pg_prepared: Dict[tuple, ResourceSet] = {}   # (pg_id, idx) -> reserved
        self.pg_committed: Dict[tuple, ResourceSet] = {}
        self._lease_cv = asyncio.Condition()
        self._lease_waiters = 0
        self._pull_locks: Dict[bytes, asyncio.Lock] = {}
        self._pull_sem = asyncio.Semaphore(GlobalConfig.max_concurrent_pulls)
        # Store pins on primary copies, oid -> size (dict also gives
        # insertion order so proactive spilling walks oldest-first).
        self._primary_pins: Dict[bytes, int] = {}
        self._spilling: Set[bytes] = set()          # oids mid-spill
        self._spill_tombstones: Set[bytes] = set()  # freed while mid-spill
        self._running_tasks: Dict[bytes, dict] = {}   # worker_id -> task
        self._task_counts: Dict[str, int] = {}        # fname -> finished
        from collections import deque as _deque
        self._task_spans = _deque(                    # finished-task spans
            maxlen=GlobalConfig.task_spans_buffer_size)
        self._peer_conns: Dict[str, rpc.Connection] = {}
        self._tasks: List[asyncio.Task] = []
        self._next_worker_seq = 0
        self._pending_actor_starts = 0
        self._actor_admission = asyncio.Semaphore(32)
        # Spawns parked in `await zygote.spawn()` are not yet in
        # self.workers; count them or a burst blows past the pool caps.
        self._spawns_inflight = 0
        # short node tag for the runtime self-metrics battery
        self._mnode = {"node": self.node_id.hex()[:12]}
        # resource bundles of lease requests currently WAITING here —
        # heartbeat-reported to the controller as the autoscaler's load
        # signal (reference: ResourceDemandScheduler's pending demand)
        self._demand_tokens: Dict[int, Dict[str, float]] = {}
        self._demand_seq = 0
        self.zygote: Optional[worker_zygote.ZygoteClient] = None
        self._stopping = False
        # controller overload state + submission credits (both absorbed
        # from heartbeat replies): brownout pauses optional pushes, soft
        # rations them by the credit window
        self._ctl_overload = "normal"
        self._ctl_credits = 0
        # Drain mode (planned departure): no new leases or actor starts
        # are granted here; in-flight work finishes and sole-copy
        # objects evacuate to peers before the controller deregisters us.
        self.draining = False
        self._drain_deadline: Optional[float] = None
        #: last cumulative serve counter value per (deployment,
        #: replica, key) — `_h_serve_metrics` folds deltas from them
        #: (float-valued: device/phase seconds travel cumulative too)
        self._serve_counter_seen: Dict[tuple, float] = {}
        #: recent recompile events per (deployment, replica) — (mono
        #: ts, n) pairs the compile-storm detector sums over its
        #: sliding window
        self._compile_events: Dict[tuple, deque] = {}
        #: recent TTFT/ITL samples per (deployment, kind) for the p95
        #: SLO evaluator — raw values, because the history ring folds
        #: histograms to _count/_sum which cannot yield a quantile
        self._slo_samples: Dict[tuple, deque] = {}
        #: tenant labels admitted into serve latency histograms
        #: (cardinality cap serve_tenant_label_max; overflow -> other)
        self._serve_tenants: Set[str] = set()
        self._drain_finished = False   # heartbeats stop; never resurrect
        self._evac_rr = 0              # round-robin cursor over peers
        # Peer-reachability gossip: a few rotating peers are probed per
        # probe round (RPC port + object-transfer port); fresh results
        # piggyback on the heartbeat and feed the controller's
        # connectivity matrix (suspect/quarantine decisions, A↛B-aware
        # scheduling, relay-peer selection).
        self._peer_reach: Dict[str, tuple] = {}   # nid -> (ok, mono ts)
        self._probe_rr = 0
        # wall-clock offset vs the controller (EWMA of heartbeat RTT-
        # midpoint samples; + means this host's clock runs ahead of the
        # controller's) — reported on the heartbeat so state.timeline()
        # merges cross-host spans in causal order
        self._clock_offset: Optional[float] = None
        # Disk-health watermark state of the spill filesystem (statvfs
        # by _disk_monitor_loop): "ok" | "low" (peers stop spilling
        # leases here) | "red" (proactive spill stops too).  Rides the
        # heartbeat into the controller's view/state.nodes().
        self.disk_health: Dict[str, Any] = {
            "state": "ok", "used_frac": 0.0, "free_bytes": 0}
        # -- blast-radius containment (typed death attribution) ---------
        # Kills WE initiated are recorded against the worker id BEFORE
        # the kill signal goes out, so the reap-loop classifier can tell
        # a chaos preemption / OOM kill / operator kill apart from a
        # genuine crash (which counts against poison quarantine).
        self._chaos_kills: Set[bytes] = set()
        self._oom_victims: Set[bytes] = set()
        self._intended_kills: Set[bytes] = set()
        # classified deaths, bounded, keyed by worker id — drivers whose
        # worker connection dropped ask `worker_death_info` here before
        # deciding whether the task is retry-worthy
        self._recent_deaths: "OrderedDict[bytes, dict]" = OrderedDict()
        # poison-quarantine view (sig -> record) absorbed from
        # controller heartbeat replies and crash-report replies: leases
        # for a quarantined signature fail fast with the evidence trail
        self._quarantine_view: Dict[str, dict] = {}
        # crash-site anti-affinity: sig -> {node_id -> wall expiry} —
        # retries of a recently-crashed signature spread away from the
        # nodes it already died on (soft: never empties the candidates)
        self._crash_sites: Dict[str, Dict[str, float]] = {}
        # bounded metrics-history ring (core/metrics_history.py),
        # sampled by a start() task, served via `metrics_history`
        from .metrics_history import MetricsRing
        self.metrics_ring = MetricsRing()
        self._register_handlers()

    # ------------------------------------------------------------------ setup
    def _register_handlers(self):
        s = self.server
        for name in ("register_worker", "lease", "return_lease", "start_actor",
                     "pull", "fetch_meta", "fetch", "free_local", "pg_prepare",
                     "pg_commit", "pg_abort", "pg_return", "kill_worker_at",
                     "node_info", "stats", "put_location", "ping",
                     "task_state", "task_state_batch", "node_stats",
                     "tail_log", "task_spans", "prestart_workers",
                     "metrics_text", "rpc_attribution", "metrics_history",
                     "chaos_injected", "serve_metrics",
                     "drain", "drain_status", "drain_evacuate",
                     "drain_complete", "detach_kill_worker",
                     "peer_probe", "probe_peer_now", "worker_death_info"):
            s.register(name, getattr(self, "_h_" + name))

    @property
    def address(self) -> str:
        return f"{self.server.host}:{self.server.port}"

    async def start(self):
        # identity + chaos arming first: proc-filtered fault rules must
        # see kind "nodelet" from the very first (chaos-visible) dial
        from ..util import tracing
        tracing.configure("nodelet", self.node_id.hex())
        fi.maybe_arm_from_config()
        store_client.create_segment(self.store_path, self.store_capacity)
        self.store = store_client.StoreClient(self.store_path)
        # Native object plane: C++ in-store transfer server (transfer.cc) —
        # peers fetch segment-to-segment, bypassing the Python RPC codec.
        try:
            self.transfer_port = self.store.serve_transfers()
        except store_client.StoreError:
            self.transfer_port = None  # chunked-RPC fallback still works
        await self.server.start()
        await self._connect_controller()
        if GlobalConfig.worker_fork_server:
            try:
                self.zygote = await worker_zygote.ZygoteClient.create(
                    self.session_dir)
            except Exception:
                traceback.print_exc()
                self.zygote = None  # exec fallback for every spawn
        for _ in range(GlobalConfig.worker_pool_initial_size):
            await self._spawn_worker()
        self._tasks.append(asyncio.ensure_future(self._heartbeat_loop()))
        self._tasks.append(asyncio.ensure_future(self._reap_loop()))
        if GlobalConfig.memory_monitor_interval_s > 0:
            self._tasks.append(
                asyncio.ensure_future(self._memory_monitor_loop()))
        if GlobalConfig.disk_monitor_interval_s > 0:
            self._tasks.append(
                asyncio.ensure_future(self._disk_monitor_loop()))
        if GlobalConfig.spill_check_interval_s > 0:
            self._tasks.append(asyncio.ensure_future(self._spill_loop()))
        self._lag_ewma = 0.0
        self._lag_max = 0.0
        if GlobalConfig.peer_probe_interval_s > 0:
            self._tasks.append(
                asyncio.ensure_future(self._peer_probe_loop()))
        self._tasks.append(asyncio.ensure_future(rpc.loop_lag_monitor(self)))
        self._tasks.append(asyncio.ensure_future(self._trace_flush_loop()))
        self._tasks.append(asyncio.ensure_future(
            self.metrics_ring.run(
                refresh=lambda: rtm.snapshot_nodelet(self))))
        self._agent_proc = None
        if GlobalConfig.dashboard_agent:
            # per-node dashboard agent (reference: raylet spawning
            # dashboard/agent.py); failures are non-fatal — the head
            # falls back to scraping this nodelet directly
            try:
                os.makedirs(os.path.join(self.session_dir, "logs"),
                            exist_ok=True)
                logf = open(os.path.join(self.session_dir, "logs",
                                         f"dashboard_agent_"
                                         f"{self.node_id.hex()[:8]}.log"),
                            "ab")
                self._agent_proc = subprocess.Popen(
                    [sys.executable, "-m", "ray_tpu.dashboard.agent",
                     "--node-id", self.node_id.hex(),
                     "--session-dir", self.session_dir,
                     "--controller", self.controller_addr,
                     "--nodelet-addr", self.address],
                    stdout=logf, stderr=subprocess.STDOUT,
                    start_new_session=True,
                    # die with the nodelet even when it is SIGKILLed —
                    # orphaned agents otherwise outlive crashed clusters
                    # and heartbeat into nothing forever
                    preexec_fn=_pdeathsig_term)
                logf.close()
            except Exception:
                traceback.print_exc()
        return self

    async def _connect_controller(self):
        """Dial + register with the LEADER controller.  Also the
        RECONNECT path: a restarted (persistence-restored) or freshly
        promoted controller learns its live nodes only from these
        re-registrations, so the heartbeat loop calls this whenever the
        connection drops.  ``controller_addr`` may be an address LIST
        (leader + hot standbys) — the probe follows leadership, so a
        leader-host death fails this nodelet over to the promoted
        standby transparently."""
        # The controller calls back over this same connection (actor starts,
        # PG 2PC, frees) — give it the full handler table plus pubsub.
        handlers = dict(self.server.handlers)
        handlers["pub:nodes"] = self._on_nodes_event
        handlers["pub:chaos"] = self._on_chaos_event
        handlers["pub:_resync"] = self._on_pub_resync
        self.controller, _ep, st = await rpc.connect_leader(
            self.controller_addr, handlers=handlers,
            retries=GlobalConfig.rpc_connect_retries)
        self._ctl_epoch = max(getattr(self, "_ctl_epoch", 0),
                              int((st or {}).get("epoch", 0) or 0))
        reply = await self.controller.call("register_node", {
            "node_id": self.node_id.hex(),
            "addr": self.address,
            "resources": self.total.to_dict(),
            "labels": self.labels,
            "config": GlobalConfig.snapshot(),
            "_ha_epoch": self._ctl_epoch,
        })
        if isinstance(reply, dict) and reply.get("_not_leader"):
            # lost a leadership race between probe and register: the
            # heartbeat loop redials (and re-probes) on the next beat
            await self.controller.close()
            raise rpc.ConnectionLost("controller lost leadership during "
                                     "registration")
        await self.controller.call("subscribe", {"channel": "nodes"})
        await self.controller.call("subscribe", {"channel": "chaos"})
        # a freshly restarted/promoted controller holds no spans (they
        # never go through the WAL): re-ship this nodelet's full ring
        # on the next flush tick
        from ..util import tracing as _tracing
        _tracing.mark_dirty()
        # Late joiners (and reconnects after a controller restart) pull
        # the current fault plan; a plan applied mid-run must cover nodes
        # added after `ray-tpu chaos apply`.
        try:
            plan = await self.controller.call("chaos_plan", {})
            # arm only on CHANGE: heartbeat reconnects land here too, and
            # re-arming an identical plan would reset its nth counters
            if plan and (fi.ACTIVE is None or fi.ACTIVE.raw != plan):
                fi.arm(plan)
        except rpc.RpcError:
            pass
        self._apply_view(reply["view"], reply["view_version"])

    async def stop(self):
        self._stopping = True
        for t in self._tasks:
            t.cancel()
        # Stop the zygote FIRST: its exit-push read loop lives on this
        # (now-stopping) event loop, so ForkedProc.poll() must fall back
        # to its direct os.kill liveness probe for the waits below to
        # ever observe an exit.
        if self.zygote is not None:
            self.zygote.stop()
        agent = getattr(self, "_agent_proc", None)
        if agent is not None and agent.poll() is None:
            agent.terminate()
        for w in self.workers.values():
            if w.proc.poll() is None:
                w.proc.terminate()
        # One shared deadline — not 2 s per worker (a 1k-worker node
        # would stall shutdown for half an hour serially).
        deadline = time.monotonic() + GlobalConfig.worker_shutdown_grace_s
        for w in self.workers.values():
            try:
                w.proc.wait(timeout=max(0.05, deadline - time.monotonic()))
            except Exception:
                w.proc.kill()
        if agent is not None:           # same escalation workers get —
            try:                        # no zombies held by this process
                agent.wait(timeout=max(0.05,
                                       deadline - time.monotonic()))
            except Exception:
                agent.kill()
        await self.server.stop()
        if self.controller:
            await self.controller.close()
        if self.store:
            self.store.close()
        try:
            os.unlink(self.store_path)
        except OSError:
            pass

    # ----------------------------------------------------------- cluster view
    def _apply_view(self, view_wire: List[dict], version: int):
        self.view = {d["id"]: NodeView.from_wire(d) for d in view_wire}
        self.view_version = version
        self._refresh_self_view()

    def _apply_delta(self, delta_wire: List[dict], version: int):
        """Merge a versioned delta (only the CHANGED node views ship —
        reference: RaySyncer's per-node versioned sync vs the legacy
        full-view broadcaster).  Per-view version guard keeps a stale
        delta from clobbering a newer view."""
        for d in delta_wire:
            nv = NodeView.from_wire(d)
            cur = self.view.get(nv.node_id)
            if cur is None or nv.version >= cur.version:
                self.view[nv.node_id] = nv
        self.view_version = version
        self._refresh_self_view()

    def _refresh_self_view(self):
        me = self.view.get(self.node_id.hex())
        if me is not None:
            me.available = self.available.copy()
            me.total = self.total.copy()
            if self.draining:
                me.draining = True

    async def _on_nodes_event(self, conn, data):
        if data.get("event") == "dead":
            nv = self.view.get(data["node_id"])
            if nv:
                nv.alive = False
            self._peer_conns.pop(data.get("addr", ""), None)
            self._peer_reach.pop(data["node_id"], None)
        elif data.get("event") == "suspect":
            # quarantined peer: stop spilling leases there immediately
            # (the versioned view delta may be a heartbeat away)
            nv = self.view.get(data["node_id"])
            if nv:
                nv.suspect = True
        elif data.get("event") == "rejoined":
            nv = self.view.get(data["node_id"])
            if nv:
                nv.suspect = False
        elif data.get("event") == "draining":
            # stop spilling leases to the draining peer NOW — the
            # versioned view delta may be a heartbeat away
            nv = self.view.get(data["node_id"])
            if nv:
                nv.draining = True
            if data["node_id"] == self.node_id.hex():
                self.draining = True

    async def _on_pub_resync(self, conn, channel):
        """The publisher's bounded buffer overflowed and dropped events
        we will never see: invalidate the incremental state so the next
        heartbeat pulls a full snapshot instead of trusting a view with
        holes in it."""
        if channel == "nodes":
            self.view_version = -1   # forces a full-view delta next beat
        elif channel == "chaos":
            try:
                plan = await self.controller.call("chaos_plan", {})
                if plan and (fi.ACTIVE is None or fi.ACTIVE.raw != plan):
                    fi.arm(plan)
            except (rpc.RpcError, OSError):
                pass

    async def _on_chaos_event(self, conn, data):
        """Runtime fault-plan push: re-arm locally and fan out to every
        live worker on this node (workers hold no controller
        subscription of their own)."""
        plan = data.get("plan")
        if plan:
            fi.arm(plan)
        else:
            fi.disarm()
        for w in list(self.workers.values()):
            if w.conn is not None and not w.conn.closed:
                try:
                    await w.conn.notify("chaos_update", {"plan": plan})
                except Exception:
                    pass

    async def _h_chaos_injected(self, conn, data):
        """A worker's injection report: crashing workers notify here just
        before exiting so the fault is visible in a SCRAPED registry
        (worker registries never are)."""
        fi.count_injection(data.get("site", "?"), data.get("action", "?"))
        return True

    async def _heartbeat_loop(self):
        while True:
            if self._drain_finished:
                # cleanly deregistered: a heartbeat now would resurrect
                # the node in the controller's membership table
                return
            try:
                if self.controller is None or self.controller.closed:
                    await self._connect_controller()
                if fi.ACTIVE is not None and fi.ACTIVE.point(
                        "nodelet.heartbeat", self.node_id.hex()):
                    # blackholed beat: simulates a partition — enough of
                    # these in a row and the controller declares us dead
                    await asyncio.sleep(GlobalConfig.heartbeat_interval_s)
                    continue
                rtm.HEARTBEATS.inc(tags=self._mnode)
                hb = {
                    "node_id": self.node_id.hex(),
                    "available": self.available.to_dict(),
                    "total": self.total.to_dict(),
                    "view_version": self.view_version,
                    "demand":
                        list(self._demand_tokens.values())[:64],
                    "reach": self._fresh_reach(),
                    "disk": {"state": self.disk_health["state"],
                             "used_frac": self.disk_health["used_frac"]},
                    "_ha_epoch": getattr(self, "_ctl_epoch", 0),
                }
                if self._clock_offset is not None:
                    hb["clock_offset"] = round(self._clock_offset, 6)
                if self._ctl_credits <= 0:
                    hb["want_credits"] = True
                t0_wall = time.time()
                reply = await self.controller.call("heartbeat", hb,
                                                   timeout=5)
                self._note_clock(t0_wall, time.time(), reply)
                if isinstance(reply, dict):
                    # flow control rides the beat: overload state gates
                    # optional pushes, credits ration them under "soft"
                    self._ctl_overload = reply.get(
                        "overload", self._ctl_overload)
                    if "credits" in reply:
                        self._ctl_credits = int(reply["credits"])
                    if "quarantine" in reply:
                        # full-table sync (tiny): quarantines declared
                        # elsewhere fail-fast at OUR lease desk too, and
                        # TTL expiries / operator clears lift them here
                        self._quarantine_view = dict(
                            reply["quarantine"] or {})
                if reply and reply.get("_not_leader"):
                    # beat landed on a deposed/standby controller: find
                    # the current leader and re-register there
                    self._ctl_epoch = max(
                        getattr(self, "_ctl_epoch", 0),
                        int(reply.get("epoch", 0) or 0))
                    await self.controller.close()
                    await self._connect_controller()
                elif reply and reply.get("unknown_node"):
                    # a freshly promoted leader answered before we
                    # re-registered (race with its own restore):
                    # re-register
                    await self.controller.close()
                    await self._connect_controller()
                elif reply and "view" in reply:
                    self._apply_view(reply["view"], reply["view_version"])
                elif reply and "delta" in reply:
                    self._apply_delta(reply["delta"], reply["view_version"])
            except (rpc.RpcError, OSError):
                pass
            await asyncio.sleep(GlobalConfig.heartbeat_interval_s)

    def _note_clock(self, t0_wall: float, t1_wall: float, reply) -> None:
        """Fold one clock-offset sample from a heartbeat round trip: the
        controller stamped its wall clock into the reply, which was read
        roughly at the RTT midpoint of [t0, t1].  offset = local −
        controller (SUBTRACT it from local stamps to land on the
        controller clock); EWMA-smoothed so one slow beat doesn't yank
        the timeline."""
        if not isinstance(reply, dict) or "now" not in reply:
            return
        sample = (t0_wall + t1_wall) / 2.0 - float(reply["now"])
        if self._clock_offset is None:
            self._clock_offset = sample
        else:
            self._clock_offset = 0.8 * self._clock_offset + 0.2 * sample

    # -------------------------------------------- peer-reachability gossip
    def _fresh_reach(self) -> Dict[str, bool]:
        """Probe results young enough to count as evidence — the
        reachability vector piggybacked on the next heartbeat."""
        now = time.monotonic()
        fresh = GlobalConfig.peer_reach_fresh_s
        return {nid: ok for nid, (ok, ts) in self._peer_reach.items()
                if now - ts <= fresh}

    async def _h_peer_probe(self, conn, data):
        """A peer is probing our RPC plane; the reply carries the
        object-transfer port so the prober can check the data plane
        too (gray failures break them independently)."""
        return {"ok": True, "transfer_port": self.transfer_port,
                "node_id": self.node_id.hex()}

    async def _h_probe_peer_now(self, conn, data):
        """On-demand probe solicited by the controller while it decides
        suspect-vs-dead for a silent node: probe the target immediately
        and answer with the outcome (also folded into our own gossip so
        the next heartbeat carries it)."""
        nid = data.get("node_id") or ""
        nv = self.view.get(nid)
        if nv is None:
            addr = data.get("addr")
            if not addr:
                return False
            from types import SimpleNamespace
            nv = SimpleNamespace(node_id=nid, addr=addr)
        ok = await self._probe_peer(nv)
        if nid:
            self._peer_reach[nid] = (ok, time.monotonic())
        return ok

    async def _peer_probe_loop(self):
        """Probe a few rotating peers per round (RPC port + transfer
        port) and remember the outcome; results ride the heartbeat into
        the controller's connectivity matrix.  A probe round records a
        ``peer_probe`` span only when some peer's state CHANGED — a
        healthy cluster's trace buffer stays quiet."""
        from ..util import tracing
        while True:
            await asyncio.sleep(GlobalConfig.peer_probe_interval_s)
            if self._drain_finished or self._stopping:
                return
            me = self.node_id.hex()
            peers = sorted((nv for nv in self.view.values()
                            if nv.alive and nv.node_id != me),
                           key=lambda nv: nv.node_id)
            if not peers:
                continue
            fanout = max(1, GlobalConfig.peer_probe_fanout)
            chosen, seen = [], set()
            for i in range(min(fanout, len(peers))):
                nv = peers[(self._probe_rr + i) % len(peers)]
                if nv.node_id not in seen:
                    seen.add(nv.node_id)
                    chosen.append(nv)
            self._probe_rr = (self._probe_rr + len(chosen)) % len(peers)
            t0 = time.time()
            changed = {}
            for nv in chosen:
                ok = await self._probe_peer(nv)
                prev = self._peer_reach.get(nv.node_id)
                self._peer_reach[nv.node_id] = (ok, time.monotonic())
                if prev is None or prev[0] != ok:
                    changed[nv.node_id[:12]] = ok
            if changed:
                tracing.record_span(
                    f"peer_probe::{me[:8]}", "peer_probe",
                    t0, time.time(), node_id=me[:12],
                    changed={k: ("reachable" if v else "unreachable")
                             for k, v in changed.items()})

    async def _probe_peer(self, nv) -> bool:
        """One peer probe: RPC round trip, then a TCP dial of the
        peer's object-transfer port — both planes must answer for the
        peer to count as reachable from here."""
        if fi.ACTIVE is not None and fi.ACTIVE.point(
                "nodelet.peer_probe", nv.node_id,
                peer=nv.node_id) is not None:
            return False  # injected false negative (chaos)
        timeout = GlobalConfig.peer_probe_timeout_s
        try:
            conn = await asyncio.wait_for(self._peer(nv.addr),
                                          timeout=timeout)
            r = await asyncio.wait_for(conn.call("peer_probe", {}),
                                       timeout=timeout)
            tport = r.get("transfer_port") if isinstance(r, dict) else None
            if tport:
                host = nv.addr.rsplit(":", 1)[0]
                _r, w = await asyncio.wait_for(
                    asyncio.open_connection(host, int(tport)),
                    timeout=timeout)
                w.close()
            return True
        except (rpc.RpcError, asyncio.TimeoutError, OSError):
            # drop the cached conn if it died so a healed link redials
            cached = self._peer_conns.get(nv.addr)
            if cached is not None and cached.closed:
                self._peer_conns.pop(nv.addr, None)
            return False

    async def _trace_flush_loop(self):
        """Ship the spans this nodelet recorded since the last tick to
        the controller (see util/tracing.py)."""
        from ..util import tracing
        if not tracing.claim_flusher():
            return
        while True:
            await asyncio.sleep(GlobalConfig.trace_flush_interval_s)
            # brownout: trace flushes are optional work — hold the spans
            # locally (the ring bounds them) until recovery;
            # soft: ration flushes by the heartbeat credit window
            if self._ctl_overload == "brownout":
                continue
            if self._ctl_overload == "soft":
                if self._ctl_credits <= 0:
                    continue
                self._ctl_credits -= 1
            batch = tracing.flush_batch()
            if batch is None:
                continue
            await tracing.flush_sent(lambda: self.controller.call(
                "trace_append", batch, timeout=10))

    async def _reap_loop(self):
        """Detect dead worker processes (the reference raylet gets
        SIGCHLD), and reclaim spawns that never REGISTER: a live-but-
        hung child still counts as 'starting', and one of those would
        gate the spawn throttle forever — observed as a full-suite
        serve flake where a replica's worker never came up because a
        single wedged spawn from cluster boot blocked every later one."""
        while True:
            await asyncio.sleep(0.2)
            now = time.monotonic()
            for w in list(self.workers.values()):
                if w.state != "dead" and w.proc.poll() is not None:
                    await self._on_worker_death(w)
                elif w.state == "starting" and now - w.spawned_at > \
                        GlobalConfig.worker_register_timeout_s:
                    print(f"worker {w.worker_id.hex()[:8]} never "
                          f"registered within "
                          f"{GlobalConfig.worker_register_timeout_s}s; "
                          f"killing and replacing it",
                          file=sys.stderr, flush=True)
                    w.proc.kill()
                    await self._on_worker_death(w)

    def _classify_death(self, w: WorkerProc) -> dict:
        """Attribute one worker corpse to a typed cause.

        ``poison`` shapes the retry decision downstream: preemption-
        shaped deaths (chaos kills, planned kills) retry freely, while
        poison-shaped ones (real signals, OOM kills, nonzero exits)
        count against the controller's quarantine threshold.  Kills this
        nodelet initiated were pre-recorded against the worker id, so
        the returncode alone never has to guess."""
        if fi.ACTIVE is not None and fi.ACTIVE.point(
                "nodelet.death_classify", w.worker_id.hex()) is not None:
            # attribution subsystem degraded by chaos: conservative —
            # an unexplained corpse counts as poison, never as free retry
            return {"kind": "unknown", "poison": True,
                    "detail": "death attribution degraded (chaos)"}
        if w.worker_id in self._intended_kills:
            return {"kind": "intended_kill", "poison": False,
                    "detail": "operator/controller-requested kill"}
        if w.worker_id in self._chaos_kills:
            return {"kind": "chaos_kill", "poison": False,
                    "detail": "chaos-injected kill (preemption-shaped)"}
        if w.worker_id in self._oom_victims:
            return {"kind": "oom_kill", "poison": True,
                    "detail": "nodelet memory monitor killed the worker"}
        rc = w.proc.returncode
        if rc is not None and rc < 0:
            try:
                name = signal.Signals(-rc).name
            except ValueError:
                name = f"SIG{-rc}"
            return {"kind": f"signal:{name}", "poison": True,
                    "detail": f"terminated by {name}"}
        if rc == fi.CRASH_EXIT_CODE:
            # the chaos layer's own crash action exits with a reserved
            # code precisely so it reads as injected, not as user poison
            return {"kind": "chaos_kill", "poison": False,
                    "detail": f"chaos crash exit ({rc})"}
        if rc:
            return {"kind": f"exit:{rc}", "poison": True,
                    "detail": f"exited with code {rc}"}
        return {"kind": "exit:0", "poison": False, "detail": "clean exit"}

    def _note_crash_sites(self, sig: str, nodes) -> None:
        if not nodes:
            return
        expiry = time.time() + GlobalConfig.poison_window_s
        site = self._crash_sites.setdefault(sig, {})
        for nid in nodes:
            site[nid] = expiry

    async def _on_worker_death(self, w: WorkerProc):
        prev_state = w.state
        w.state = "dead"
        self.workers.pop(w.worker_id, None)
        rtm.WORKERS_DIED.inc(tags=self._mnode)
        cause = self._classify_death(w)
        rtm.TASK_DEATHS.inc(tags={"node": self._mnode["node"],
                                  "cause": cause["kind"]})
        # The worker's batched finish event may have died in its buffer;
        # the process is gone, so its "running" entry is stale by
        # definition — close it out as interrupted.
        run = self._running_tasks.pop(w.worker_id, None)
        if run is not None:
            self._task_spans.append({
                "name": run.get("name", "?"),
                "worker_id": w.worker_id.hex(),
                "task_id": run.get("task_id", ""),
                "start": run.get("start"), "end": time.time(),
                "interrupted": True, "cause": cause["kind"]})
        death = {"worker_id": w.worker_id.hex(), "ts": time.time(),
                 "node_id": self.node_id.hex(), "cause": cause["kind"],
                 "poison": cause["poison"], "detail": cause["detail"],
                 "quarantined": None, "avoid": []}
        if prev_state == "leased" and w.lease_id in self.leases:
            lease = self.leases.pop(w.lease_id)
            self.available.release(lease.resources)
            await self._notify_lease_waiters()
            fname = w.leased_fname or (run or {}).get("name")
            if fname:
                # Crash ledger report — SYNCHRONOUS on purpose: the
                # reply carries any quarantine verdict plus the crash-
                # site set, and the driver's death-info query blocks on
                # this entry, so a poison signature is contained after
                # the threshold with zero propagation latency.
                death["sig"] = f"task:{fname}"
                try:
                    r = await self.controller.call("report_task_crash", {
                        "sig": death["sig"],
                        "node_id": self.node_id.hex(),
                        "cause": {"kind": cause["kind"],
                                  "poison": cause["poison"],
                                  "node": self.node_id.hex()},
                    }, timeout=5)
                    if isinstance(r, dict):
                        death["quarantined"] = r.get("quarantined")
                        death["avoid"] = r.get("avoid") or []
                        if r.get("quarantined"):
                            self._quarantine_view[death["sig"]] = \
                                r["quarantined"]
                        self._note_crash_sites(death["sig"],
                                               death["avoid"])
                except (rpc.RpcError, OSError, asyncio.TimeoutError):
                    pass
        if prev_state == "actor" and w.actor_id is not None:
            try:
                await self.controller.call("report_worker_failure", {
                    "actor_id": w.actor_id,
                    "reason": f"worker died: {cause['kind']} "
                              f"({cause['detail']})",
                    "cause": {"kind": cause["kind"],
                              "poison": cause["poison"],
                              "node": self.node_id.hex()},
                })
            except rpc.RpcError:
                pass
            # Actor lifetime resources are released exactly once on death
            # (cleared here; also cleared by start_actor's own error paths).
            res = getattr(w, "actor_resources", None)
            if res is not None:
                w.actor_resources = None
                self.available.release(res)
                await self._notify_lease_waiters()
        # publish for driver death-info queries (bounded ring), then
        # retire the one-shot attribution marks
        self._recent_deaths[w.worker_id] = death
        while len(self._recent_deaths) > 256:
            self._recent_deaths.popitem(last=False)
        self._chaos_kills.discard(w.worker_id)
        self._oom_victims.discard(w.worker_id)
        self._intended_kills.discard(w.worker_id)
        if (prev_state in ("idle", "starting") and not self._stopping
                and not self._drain_finished
                and len(self.workers) < GlobalConfig.worker_pool_initial_size):
            await self._spawn_worker()

    async def _h_worker_death_info(self, conn, data):
        """Driver-side death attribution: after a worker connection
        drops, the driver asks the granting nodelet WHY before deciding
        to retry.  Parks briefly for the reap loop + crash-ledger round
        trip, so the reply reflects any quarantine the controller just
        declared — closing the window where a poison task could burn
        extra workers between the kill and the next heartbeat."""
        wid = data.get("worker_id")
        deadline = time.monotonic() + min(3.0, data.get("timeout", 2.0))
        while True:
            d = self._recent_deaths.get(wid)
            if d is not None:
                return d
            if time.monotonic() > deadline:
                return {"unknown": True}
            await asyncio.sleep(0.05)

    # ------------------------------------------------------- memory monitor
    @staticmethod
    def _memory_usage_fraction() -> float:
        """System memory pressure from /proc/meminfo (reference:
        MemoryMonitor::GetMemoryBytes, src/ray/common/memory_monitor.cc —
        cgroup/system available vs total)."""
        total = avail = None
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        total = int(line.split()[1])
                    elif line.startswith("MemAvailable:"):
                        avail = int(line.split()[1])
                    if total is not None and avail is not None:
                        break
        except OSError:
            return 0.0
        if not total:
            return 0.0
        return 1.0 - (avail or 0) / total

    @staticmethod
    def _worker_rss_kb(pid: int) -> int:
        try:
            with open(f"/proc/{pid}/statm") as f:
                pages = int(f.read().split()[1])
            return pages * (os.sysconf("SC_PAGE_SIZE") // 1024)
        except (OSError, ValueError, IndexError):
            return 0

    def _pick_oom_victim(self) -> Optional[WorkerProc]:
        """Kill policy (reference: worker_killing_policy.cc — prefer
        retriable work, newest first): leased task workers before actors,
        and among candidates the largest RSS."""
        leased = [w for w in self.workers.values() if w.state == "leased"]
        actors = [w for w in self.workers.values() if w.state == "actor"]
        for group in (leased, actors):
            if group:
                return max(group,
                           key=lambda w: self._worker_rss_kb(w.proc.pid))
        return None

    async def _memory_monitor_loop(self):
        """OOM protection (reference: raylet MemoryMonitor + worker
        killing): above the usage threshold, kill one worker per tick —
        its task fails with a retriable worker-died error (or the actor
        restarts under max_restarts) instead of the kernel OOM-killing the
        nodelet or store."""
        while True:
            await asyncio.sleep(GlobalConfig.memory_monitor_interval_s)
            try:
                frac = self._memory_usage_fraction()
                if frac < GlobalConfig.memory_usage_threshold:
                    continue
                victim = self._pick_oom_victim()
                if victim is None:
                    continue
                print(f"MEMORY PRESSURE {frac:.3f} >= "
                      f"{GlobalConfig.memory_usage_threshold}: killing "
                      f"worker {victim.worker_id.hex()[:8]} "
                      f"(state={victim.state}, "
                      f"rss={self._worker_rss_kb(victim.proc.pid)}kB)",
                      file=sys.stderr, flush=True)
                self._oom_kills = getattr(self, "_oom_kills", 0) + 1
                rtm.OOM_KILLS.inc(tags=self._mnode)
                # marked BEFORE the kill: the reap loop attributes the
                # corpse to us, not to a mystery SIGKILL
                self._oom_victims.add(victim.worker_id)
                victim.proc.kill()
                try:
                    await self.controller.notify("report_event", {
                        "severity": "ERROR", "source": "memory_monitor",
                        "message": f"OOM-killed worker "
                                   f"{victim.worker_id.hex()[:8]} at "
                                   f"{frac:.2f} memory usage",
                        "meta": {"node_id": self.node_id.hex()}})
                    # incident bundle at the controller: the spans and
                    # metrics window AROUND the kill, while they exist
                    await self.controller.notify("debug_capture", {
                        "trigger": "oom_kill",
                        "reason": f"worker "
                                  f"{victim.worker_id.hex()[:8]} at "
                                  f"{frac:.2f} usage",
                        "meta": {"node_id": self.node_id.hex()[:12]}})
                except Exception:
                    pass
            except Exception:
                pass  # the monitor must never die

    def _disk_usage(self):
        """statvfs snapshot of the spill filesystem (sync: runs via
        to_thread off the event loop)."""
        st = os.statvfs(spill.spill_root())
        total = st.f_frsize * st.f_blocks
        free = st.f_frsize * st.f_bavail
        used_frac = 1.0 - (free / total) if total else 0.0
        return used_frac, free

    async def _disk_monitor_loop(self):
        """Disk-health watermarks beside the memory monitor: statvfs the
        spill filesystem and classify ok / low / red
        (``disk_low_water_frac`` / ``disk_red_frac``).  LOW nodes stop
        being chosen as lease spill-back targets; RED additionally stops
        proactive spilling (writes there would only fail) and fires a
        ``disk_pressure`` incident bundle at the controller.  The state
        rides every heartbeat into ``state.nodes()`` / ``ray-tpu
        status``."""
        while True:
            await asyncio.sleep(GlobalConfig.disk_monitor_interval_s)
            try:
                try:
                    used_frac, free = await asyncio.to_thread(
                        self._disk_usage)
                except OSError:
                    continue  # spill root vanished: keep last state
                if used_frac >= GlobalConfig.disk_red_frac:
                    state = "red"
                elif used_frac >= GlobalConfig.disk_low_water_frac:
                    state = "low"
                else:
                    state = "ok"
                prev = self.disk_health["state"]
                self.disk_health = {"state": state,
                                    "used_frac": round(used_frac, 4),
                                    "free_bytes": free}
                if state == prev:
                    continue
                # reflect immediately in our own view so local spillback
                # decisions don't wait a heartbeat round-trip
                me = self.view.get(self.node_id.hex())
                if me is not None:
                    me.disk = state
                if state == "red" and prev != "red":
                    print(f"DISK PRESSURE {used_frac:.3f} >= "
                          f"{GlobalConfig.disk_red_frac}: proactive spill "
                          f"stopped on node {self.node_id.hex()[:12]} "
                          f"({free >> 20} MiB free)",
                          file=sys.stderr, flush=True)
                    try:
                        await self.controller.notify("report_event", {
                            "severity": "ERROR", "source": "disk_monitor",
                            "message": f"disk red at {used_frac:.2f} used "
                                       f"({free >> 20} MiB free): spill "
                                       f"target excluded, proactive spill "
                                       f"stopped",
                            "meta": {"node_id": self.node_id.hex()}})
                        await self.controller.notify("debug_capture", {
                            "trigger": "disk_pressure",
                            "reason": f"node "
                                      f"{self.node_id.hex()[:12]} at "
                                      f"{used_frac:.2f} disk usage",
                            "meta": {"node_id": self.node_id.hex()[:12]}})
                    except Exception:
                        pass
            except Exception:
                pass  # the monitor must never die

    async def _spill_loop(self):
        """Proactive spilling under store pressure (reference:
        `src/ray/raylet/local_object_manager.cc` SpillObjectsOfSize — the
        raylet, not the writer, decides when pinned primaries move to
        external storage).  Above the high-water mark, pinned primary
        copies spill oldest-first to the configured backend
        (external_storage.py) until usage drops below the low-water mark;
        the store copy is then deleted so new creates stop hitting
        StoreFullError.  Restore stays transparent: readers fall back to
        the spill KV entry exactly as for writer-inline spills."""
        while True:
            await asyncio.sleep(GlobalConfig.spill_check_interval_s)
            try:
                if self.disk_health["state"] == "red":
                    # spilling onto a red disk can only trade memory
                    # pressure for ENOSPC failures: hold copies in memory
                    # (put-side backpressure takes over) until it clears
                    continue
                st = self.store.stats()
                cap = st["capacity_bytes"] or 1
                if st["used_bytes"] / cap < GlobalConfig.spill_threshold_frac:
                    continue
                min_bytes = GlobalConfig.spill_min_object_bytes
                for oid, size in list(self._primary_pins.items()):
                    if 0 < size < min_bytes:
                        continue  # known-small: skip without touching the store
                    if (self.store.stats()["used_bytes"] / cap
                            < GlobalConfig.spill_low_water_frac):
                        break
                    await self._spill_one(oid)
            except Exception:
                # pressure relief must never die, but must not fail silently
                traceback.print_exc(file=sys.stderr)

    async def _spill_one(self, oid: bytes) -> bool:
        """Spill one pinned primary copy; returns True if store space was
        reclaimed."""
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            self._primary_pins.pop(oid, None)
            return False
        self._spilling.add(oid)
        try:
            return await self._spill_locked(oid, view)
        except OSError:
            # disk fault mid-spill (ENOSPC/EIO): degrade, don't fail —
            # the primary copy stays pinned in memory and put-side
            # backpressure carries the pressure until space frees
            spill.count_fault(spill.SPILL_WRITE_SITE, "retained")
            return False
        finally:
            self._spilling.discard(oid)
            self._spill_tombstones.discard(oid)

    async def _spill_locked(self, oid: bytes, view) -> bool:
        try:
            if len(view) < GlobalConfig.spill_min_object_bytes:
                return False
            nbytes = len(view)
            url = await asyncio.to_thread(spill.write_object, oid, [view])
            rtm.OBJECTS_SPILLED.inc(tags=self._mnode)
            rtm.BYTES_SPILLED.inc(nbytes, tags=self._mnode)
        finally:
            del view
            self.store.release(oid)
        # The write awaited: _h_free_local may have freed this object
        # meanwhile — and the controller's spill-ns sweep for it already
        # ran, so registering now would leak the KV entry and the file
        # forever.  _h_free_local leaves a tombstone for oids mid-spill
        # (self._spilling); check it after EVERY await below and undo.
        if oid in self._spill_tombstones or oid not in self._primary_pins:
            self._spill_tombstones.discard(oid)
            await asyncio.to_thread(spill.delete_file, url)
            return False
        self._spilled_objects = getattr(self, "_spilled_objects", 0) + 1
        await self.controller.call("kv_put", {
            **spill.kv_entry(oid), "value": url.encode()})
        await self.controller.call("object_location_remove", {
            "object_id": oid, "node_id": self.node_id.hex()})
        if oid in self._spill_tombstones:
            # freed between our registration and now: the sweep missed the
            # fresh KV entry — clean up both ourselves.
            self._spill_tombstones.discard(oid)
            await self.controller.call("kv_del", spill.kv_entry(oid))
            await asyncio.to_thread(spill.delete_file, url)
            return False
        if self._primary_pins.pop(oid, None) is not None:
            self.store.release(oid)  # drop the primary pin
        try:
            self.store.delete(oid)
        except store_client.StoreError:
            pass
        return True

    # ------------------------------------------------------------ worker pool
    async def _spawn_worker(self, lang: str = "py",
                            platform: str = accelerator.CPU) -> WorkerProc:
        """Fork a worker from the zygote (~10 ms) or exec one (~250 ms),
        with JAX held to ``platform`` from before its first import.

        The fork-server path is the default for Python; it falls back to
        the exec path transparently if the zygote is missing or died.
        C++ workers (lang="cpp") always exec the native worker binary
        (reference: C++ workers are their own executable too —
        cpp/src/ray/runtime/).
        """
        asked = time.time()
        worker_id = WorkerID.from_random().binary()
        self._next_worker_seq += 1
        log_path = os.path.join(self.session_dir, "logs",
                                f"worker-{self.node_id.hex()[:8]}-{self._next_worker_seq}.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        env = dict(self.worker_env)
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["JAX_PLATFORMS"] = platform
        if lang == "cpp":
            return await self._spawn_cpp_worker(worker_id, log_path, env)
        proc = None
        if self.zygote is not None and not self.zygote.dead:
            self._spawns_inflight += 1
            try:
                pid = await self.zygote.spawn(
                    {"nodelet": self.address,
                     "controller": self.controller_addr,
                     "store": self.store_path,
                     "node_id": self.node_id.hex(),
                     "worker_id": worker_id.hex(),
                     "session_dir": self.session_dir},
                    log_path, env)
                proc = worker_zygote.ForkedProc(pid, self.zygote)
                rtm.WORKERS_SPAWNED.inc(
                    tags={**self._mnode, "mode": "fork"})
            except Exception:
                proc = None  # zygote sick: exec below, heal at next boot
            finally:
                self._spawns_inflight -= 1
        if proc is None:
            full_env = dict(os.environ)
            full_env.update(env)
            logf = open(log_path, "ab")
            proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.core.worker_main",
                 "--nodelet", self.address,
                 "--controller", self.controller_addr,
                 "--store", self.store_path,
                 "--node-id", self.node_id.hex(),
                 "--worker-id", worker_id.hex(),
                 "--session-dir", self.session_dir],
                stdout=logf, stderr=subprocess.STDOUT, env=full_env,
                start_new_session=True)
            logf.close()
            rtm.WORKERS_SPAWNED.inc(tags={**self._mnode, "mode": "exec"})
        w = WorkerProc(worker_id, proc, platform=platform)
        w.spawn_asked = asked
        self.workers[worker_id] = w
        return w

    async def _spawn_cpp_worker(self, worker_id: bytes, log_path: str,
                                env: Dict[str, str]) -> WorkerProc:
        """Exec the native C++ worker binary (built on demand from
        ray_tpu/cpp/worker_main.cc; speaks the same register/push_task
        wire protocol as the Python worker runtime)."""
        from ..cpp import build as cpp_build
        from .object_store import client as store_client
        loop = asyncio.get_event_loop()
        # g++ runs off-loop: a cold multi-second compile must not stall
        # heartbeats/leases (it's an mtime-checked no-op afterwards)
        binary = await loop.run_in_executor(None,
                                            cpp_build.ensure_worker_built)
        store_lib = await loop.run_in_executor(None,
                                              store_client._ensure_built)
        full_env = dict(os.environ)
        full_env.update(env)
        full_env["RAY_TPU_STORE_LIB"] = store_lib
        logf = open(log_path, "ab")
        proc = subprocess.Popen(
            [binary,
             "--nodelet", self.address,
             "--controller", self.controller_addr,
             "--store", self.store_path,
             "--node-id", self.node_id.hex(),
             "--worker-id", worker_id.hex(),
             "--session-dir", self.session_dir],
            stdout=logf, stderr=subprocess.STDOUT, env=full_env,
            start_new_session=True)
        logf.close()
        rtm.WORKERS_SPAWNED.inc(tags={**self._mnode, "mode": "cpp"})
        w = WorkerProc(worker_id, proc, lang="cpp")
        self.workers[worker_id] = w
        return w

    async def _h_register_worker(self, conn, data):
        w = self.workers.get(data["worker_id"])
        if w is None:
            return {"error": "unknown worker"}
        w.port = data["port"]
        w.conn = conn
        w.state = "idle"
        w.registered.set()
        from ..util import tracing
        # asked for the process -> its runtime registered: fork or exec,
        # imports, the connections back (on the TPU platform this is the
        # chip holder's first phase of set-up)
        tracing.record_span("setup:worker_spawn", "setup", w.spawn_asked,
                            time.time(), worker_pid=w.proc.pid,
                            platform=w.platform, lang=w.lang)
        conn.peer_info["worker_id"] = data["worker_id"]
        await self._notify_lease_waiters()
        return {"config": GlobalConfig.snapshot(), "node_id": self.node_id.hex()}

    async def _h_prestart_workers(self, conn, data):
        for _ in range(data.get("count", 1)):
            if len(self.workers) + self._spawns_inflight \
                    < GlobalConfig.worker_pool_max_size:
                await self._spawn_worker()
        return True

    async def _pop_idle_worker(self, waiting: int = 1, lang: str = "py",
                               platform: str = accelerator.CPU
                               ) -> Optional[WorkerProc]:
        w = idle_worker_for(self.workers.values(), lang, platform)
        if w is not None:
            return w
        # Spawn by demand, not per poll: at most ``waiting`` workers may be
        # concurrently starting, else a burst of lease retries forks an
        # import storm that starves the very workers it is waiting on.
        # Actor-dedicated workers never come back, so they live under their
        # own (large) cap — else the 16-worker pool cap deadlocks the 17th
        # actor forever.  The starting-throttle counts only the requested
        # language, so a burst of python spawns can't starve a cpp lease.
        starting = self._spawns_inflight + sum(
            1 for w in self.workers.values()
            if w.state == "starting" and w.lang == lang
            and w.platform == platform)
        actor_workers = sum(1 for w in self.workers.values()
                            if w.state == "actor")
        # The pool cap is per language and platform: a full pool of idle
        # PYTHON workers (which are never reaped) must not starve the
        # first cpp lease forever, nor a full pool of CPU workers the
        # first lease that reserved the TPU, and vice versa.
        pool = self._spawns_inflight + sum(
            1 for w in self.workers.values()
            if w.state not in ("dead", "actor") and w.lang == lang
            and w.platform == platform)
        if starting < waiting and pool < GlobalConfig.worker_pool_max_size \
                and actor_workers < GlobalConfig.actor_workers_max:
            await self._spawn_worker(lang=lang, platform=platform)
        return None

    async def _notify_lease_waiters(self):
        # wave stats: each notify_all is one scheduler WAVE — the whole
        # waiter cohort re-runs admission; cohort size + depth-at-grant
        # histograms are the batching signals item 4 reads
        rtm.SCHED_WAVES.inc(tags=self._mnode)
        if self._lease_waiters:
            rtm.SCHED_WAVE_BATCH.observe(self._lease_waiters,
                                         tags=self._mnode)
        self._refresh_self_view()
        async with self._lease_cv:
            self._lease_cv.notify_all()

    # -------------------------------------------------------- lease protocol
    async def _h_lease(self, conn, data):
        """Grant a worker lease, queue until possible, or spill to a peer.

        The driver retries at the spillback target; hard node-affinity and
        placement-group shadow resources arrive here as plain resource names,
        so one code path covers them all.
        """
        spec = TaskSpec.from_wire(data["spec"])
        q = self._poisoned(spec.function_name)
        if q is not None:
            # poison quarantine: fail fast with the evidence trail
            # instead of burning another worker on a known-bad signature
            return {"poisoned": q}
        avoid = set(data.get("avoid") or ())
        request = spec.resources
        strategy = spec.scheduling_strategy
        deadline = time.monotonic() + data.get("timeout",
                                               GlobalConfig.lease_request_timeout_s)
        my_id = self.node_id.hex()
        self._lease_waiters += 1
        self._demand_seq += 1
        tok = self._demand_seq
        self._demand_tokens[tok] = request.to_dict()
        t_req = time.time()
        try:
            reply = await self._lease_inner(spec, request, strategy,
                                            deadline, my_id, avoid)
            if fi.ACTIVE is not None and reply.get("granted"):
                act = fi.ACTIVE.point("nodelet.lease", spec.function_name)
                if act is not None and act["action"] == "kill_worker":
                    # the granted worker dies ``delay_s`` after the grant
                    # — i.e. mid-dispatch or mid-step, pinning down the
                    # driver's re-lease/retry semantics
                    w = self.workers.get(reply["worker_id"])
                    if w is not None:
                        # pre-attributed: the classifier must read this
                        # corpse as injected preemption, not poison
                        self._chaos_kills.add(w.worker_id)
                        asyncio.get_event_loop().call_later(
                            max(0.0, act["delay_s"]),
                            lambda proc=w.proc: proc.poll() is None
                            and proc.kill())
            if reply.get("granted"):
                # scheduling latency: lease request arrival -> worker
                # grant, attributed to the task whose spec rode the
                # request (spillbacks/timeouts are not grants)
                from ..util import tracing
                now = time.time()
                rtm.SCHED_LATENCY.observe(now - t_req, tags=self._mnode)
                tracing.record_span(
                    f"schedule::{spec.function_name}", "sched", t_req, now,
                    task_id=spec.task_id.hex(), trace=spec.trace_id)
            return reply
        finally:
            self._lease_waiters -= 1
            self._demand_tokens.pop(tok, None)

    def _poisoned(self, fname: str) -> Optional[dict]:
        """Active quarantine record for a task signature, if any."""
        rec = self._quarantine_view.get(f"task:{fname}")
        if rec is not None and rec.get("until", 0) > time.time():
            return rec
        return None

    def _crash_site_nodes(self, fname: str) -> Set[str]:
        """Nodes this signature recently died on (anti-affinity)."""
        site = self._crash_sites.get(f"task:{fname}")
        if not site:
            return set()
        now = time.time()
        live = {n for n, exp in site.items() if exp > now}
        if not live:
            self._crash_sites.pop(f"task:{fname}", None)
        return live

    async def _lease_inner(self, spec, request, strategy, deadline, my_id,
                           avoid=None):
        # Arg-locality hint for the connectivity matrix: the task's ref
        # args are fetchable from (at least) this submitting node, so a
        # spillback target that freshly reported it cannot reach US
        # would wedge the task's arg fetch behind a severed link —
        # hybrid_policy avoids such targets (softly: the relay rung of
        # the fetch ladder remains the safety net).
        try:
            arg_nodes = {my_id} if spec.arg_ref_ids() else None
        except (KeyError, TypeError):
            arg_nodes = None
        while True:
            self._refresh_self_view()
            # Disk-health filter, SOFT like arg_nodes: peers whose spill
            # filesystem is past the red watermark are skipped as
            # spill-back targets (work sent there could neither spill
            # nor absorb a put under pressure), unless that empties the
            # candidate set.  LOW nodes stay eligible — they are only
            # flagged for operators.
            views = {nid: v for nid, v in self.view.items()
                     if nid == my_id or getattr(v, "disk", "ok") != "red"}
            views = views if views else self.view
            # Crash-site anti-affinity, SOFT like the filters above: the
            # driver's death-info evidence plus our own crash-site view
            # steer a recently-crashed signature away from the nodes it
            # already died on — ruling out a bad host without ever
            # emptying the candidate set.
            shun = set(avoid or ()) | self._crash_site_nodes(
                spec.function_name)
            if shun:
                spread = {nid: v for nid, v in views.items()
                          if nid not in shun}
                if spread:
                    views = spread
            if self.draining:
                # never grant here again: spill to a live peer when one
                # fits, else tell the driver to retry (it re-evaluates
                # against the synced view, which now marks us DRAINING)
                target = hybrid_policy(views, request, None,
                                       strategy=strategy,
                                       arg_nodes=arg_nodes)
                if target is not None and target != my_id:
                    nv = self.view.get(target)
                    rtm.LEASES_SPILLBACK.inc(tags=self._mnode)
                    return {"spillback": nv.addr, "node_id": target}
                return {"retry": True, "draining": True}
            target = hybrid_policy(
                views, request, my_id,
                spread_threshold=GlobalConfig.scheduler_spread_threshold,
                strategy=strategy, arg_nodes=arg_nodes)
            if target is not None and target != my_id:
                nv = self.view.get(target)
                rtm.LEASES_SPILLBACK.inc(tags=self._mnode)
                return {"spillback": nv.addr, "node_id": target}
            if target is None and not self.total.fits(request):
                # Infeasible everywhere we know of; wait for cluster growth.
                if time.monotonic() > deadline:
                    totals = {n.node_id[:8]: n.total.res for n in self.view.values()}
                    rtm.LEASES_INFEASIBLE.inc(tags=self._mnode)
                    return {"error": f"infeasible resource request {request.res} "
                                     f"(cluster node totals: {totals})",
                            "infeasible": True}
            if self.available.fits(request):
                worker = await self._pop_idle_worker(
                    self._lease_waiters, lang=spec.lang,
                    platform=accelerator.worker_platform(
                        request.to_dict(), self.reserved_platform))
                if worker is not None:
                    lease_id = os.urandom(16)
                    self.available.acquire(request)
                    worker.state = "leased"
                    worker.lease_id = lease_id
                    worker.leased_fname = spec.function_name
                    self.leases[lease_id] = Lease(lease_id, worker, request)
                    self._refresh_self_view()
                    rtm.LEASES_GRANTED.inc(tags=self._mnode)
                    rtm.SCHED_QUEUE_DEPTH_AT_GRANT.observe(
                        self._lease_waiters, tags=self._mnode)
                    return {"granted": True, "lease_id": lease_id,
                            "worker_id": worker.worker_id,
                            "worker_addr": worker.address}
            if time.monotonic() > deadline:
                return {"timeout": True}
            async with self._lease_cv:
                try:
                    await asyncio.wait_for(self._lease_cv.wait(), timeout=0.2)
                except asyncio.TimeoutError:
                    pass

    async def _h_return_lease(self, conn, data):
        lease = self.leases.pop(data["lease_id"], None)
        if lease is None:
            return False
        self.available.release(lease.resources)
        if lease.worker.state == "leased":
            lease.worker.state = "idle"
            lease.worker.lease_id = None
        await self._notify_lease_waiters()
        return True

    async def _h_start_actor(self, conn, data):
        """Controller asks us to host an actor: dedicate a worker + resources
        for the actor's lifetime and push the creation task to it."""
        spec = TaskSpec.from_wire(data["spec"])
        request = spec.resources
        if self.draining:
            # planned departure in progress: the controller's scheduler
            # re-places the actor on a live node (draining views are
            # infeasible there too — this covers the race window)
            return {"ok": False, "retry": True, "error": "node draining"}
        if not self.available.fits(request):
            return {"ok": False, "retry": True, "error": "resources busy"}
        if sum(1 for w in self.workers.values() if w.state == "actor") \
                + self._pending_actor_starts \
                >= GlobalConfig.actor_workers_max:
            # hard per-node actor-process cap (in-flight starts counted,
            # else 64 concurrent handlers overshoot it): tell the
            # controller NOW so it schedules elsewhere — zero-resource
            # actors otherwise pack onto this node until the 30s pop
            # deadline, starving creations while other nodes idle
            # (found by the 5k-actor scale probe, round 5)
            return {"ok": False, "retry": True, "saturated": True,
                    "error": "actor worker cap reached"}
        deadline = time.monotonic() + \
            GlobalConfig.actor_worker_startup_timeout_s
        worker = None
        self._pending_actor_starts += 1
        # Admission bound on the worker-pop loop: a 5k-creation burst
        # otherwise parks thousands of handlers in the cv-wait below,
        # each waking on every lease event — O(pending^2) wakeup work
        # that collapses creation throughput.  The permit is released
        # BEFORE the blocking create_actor push, so gang-actor
        # constructors that wait on >32 peers cannot deadlock on it.
        try:
            await asyncio.wait_for(
                self._actor_admission.acquire(),
                timeout=max(0.1, deadline - time.monotonic()))
        except asyncio.TimeoutError:
            self._pending_actor_starts -= 1
            return {"ok": False, "retry": True,
                    "error": "actor admission queue full"}
        try:
            while worker is None:
                # a burst of actor creations may fork several workers at
                # once (capped) instead of strictly one at a time
                worker = await self._pop_idle_worker(
                    waiting=min(self._pending_actor_starts,
                                GlobalConfig.actor_spawn_parallelism),
                    lang=spec.lang,
                    platform=accelerator.worker_platform(
                        request.to_dict(), self.reserved_platform))
                if worker is None:
                    if time.monotonic() > deadline:
                        return {"ok": False, "retry": True,
                                "error": "no worker available"}
                    async with self._lease_cv:
                        try:
                            await asyncio.wait_for(self._lease_cv.wait(),
                                                   timeout=0.2)
                        except asyncio.TimeoutError:
                            pass
        finally:
            self._actor_admission.release()
            self._pending_actor_starts -= 1
        self.available.acquire(request)
        worker.state = "actor"
        worker.actor_id = spec.actor_creation_id.binary()
        worker.actor_resources = request  # type: ignore[attr-defined]
        self._refresh_self_view()
        try:
            reply = await worker.conn.call("create_actor", {"spec": data["spec"]},
                                           timeout=120)
        except (rpc.RpcError, asyncio.TimeoutError) as e:
            # Release exactly once: clear actor_resources so the reap loop
            # (which releases on dead 'actor' workers) can't double-release.
            held = getattr(worker, "actor_resources", None) is not None
            worker.actor_resources = None
            if worker.state == "actor" and worker.proc.poll() is None:
                worker.proc.terminate()  # unknown state; recycle the process
            if held:
                await self._release_off_chip(request, [worker])
            return {"ok": False, "retry": True, "error": str(e)}
        if not reply.get("ok"):
            if getattr(worker, "actor_resources", None) is not None:
                worker.actor_resources = None
                self.available.release(request)
            worker.state = "idle"
            worker.actor_id = None
            await self._notify_lease_waiters()
            return {"ok": False, "retry": False, "error": reply.get("error")}
        return {"ok": True, "worker_addr": worker.address}

    async def _h_kill_worker_at(self, conn, data):
        for w in self.workers.values():
            if w.address == data["address"] and w.proc.poll() is None:
                self._intended_kills.add(w.worker_id)
                w.proc.terminate()
                return True
        return False

    async def _h_detach_kill_worker(self, conn, data):
        """Kill a worker with its actor binding FORGOTTEN first: the
        death is a planned migration, so the reap loop must not report
        an actor failure (which would burn restart budget — or kill a
        max_restarts=0 actor — for a departure the controller itself
        orchestrated)."""
        for w in self.workers.values():
            if w.address == data["address"] and w.proc.poll() is None:
                w.actor_id = None
                self._intended_kills.add(w.worker_id)
                w.proc.terminate()
                return True
        return False

    # ------------------------------------------------------------- drain
    async def _h_drain(self, conn, data):
        """Enter drain mode: no new leases or actor starts; existing
        leases/tasks run to completion.  Returns the quiesce baseline."""
        self.draining = True
        # the controller's evacuation budget: tracked so drain_status
        # (and anyone tailing this nodelet) can see the runway left
        budget = float(data.get("timeout_s") or 0.0)
        self._drain_deadline = (time.monotonic() + budget) if budget \
            else None
        me = self.view.get(self.node_id.hex())
        if me is not None:
            me.draining = True
        # wake queued lease waiters so they re-evaluate (spillback or
        # retry) instead of sleeping toward their deadline here
        await self._notify_lease_waiters()
        return {"ok": True, "in_flight": len(self.leases),
                "objects_left": len(self._primary_pins)}

    async def _h_drain_status(self, conn, data):
        st = {"in_flight": len(self.leases),
              "running": len(self._running_tasks),
              "objects_left": len(self._primary_pins),
              "actor_workers": sum(1 for w in self.workers.values()
                                   if w.state == "actor")}
        if self._drain_deadline is not None:
            st["budget_left_s"] = round(
                self._drain_deadline - time.monotonic(), 3)
        return st

    def _evac_peers(self):
        me = self.node_id.hex()
        return [nv for nv in self.view.values()
                if nv.alive and not nv.draining and nv.node_id != me]

    async def _h_drain_evacuate(self, conn, data):
        """Push every pinned primary (each the sole durable copy on this
        node) to a live peer, which takes over the primary pin and the
        directory entry.  Our local copy STAYS until deregistration so
        readers mid-get finish; `_mark_node_dead` purges our directory
        entries.  A failed evacuation leaves the object to the lineage-
        reconstruction safety net — exactly the crash path, minus the
        surprise."""
        moved = failed = 0
        for oid in list(self._primary_pins):
            if fi.ACTIVE is not None and \
                    fi.ACTIVE.point("drain.evacuate", oid.hex()) is not None:
                failed += 1  # injected evacuation failure (chaos suite)
                continue
            peers = self._evac_peers()
            if not peers:
                failed += 1
                continue
            ok = False
            for i in range(len(peers)):
                peer = peers[(self._evac_rr + i) % len(peers)]
                try:
                    pconn = await self._peer(peer.addr)
                    r = await pconn.call(
                        "pull", {"object_id": oid, "timeout": 30.0,
                                 "pin_primary": True}, timeout=40)
                except (rpc.RpcError, OSError):
                    continue
                if r.get("ok"):
                    ok = True
                    break
            self._evac_rr += 1
            if ok:
                # the peer holds the primary pin now; release ours (the
                # unpinned local copy remains a plain replica)
                if self._primary_pins.pop(oid, None) is not None:
                    self.store.release(oid)
                moved += 1
                rtm.OBJECTS_EVACUATED.inc(tags=self._mnode)
            else:
                failed += 1
        return {"moved": moved, "failed": failed,
                "left": len(self._primary_pins)}

    async def _h_drain_complete(self, conn, data):
        """The controller deregistered us cleanly: stop heartbeating
        (a beat now would resurrect the node) and wind the worker pool
        down.  The process itself stays up — the store keeps serving
        reads until the host actually goes away."""
        self._drain_finished = True
        for w in self.workers.values():
            if w.state in ("idle", "starting") and w.proc.poll() is None:
                w.proc.terminate()
        return True

    # --------------------------------------------------- placement-group 2PC
    async def _h_pg_prepare(self, conn, data):
        req = ResourceSet(data["resources"])
        if not self.available.fits(req):
            return False
        self.available.acquire(req)
        self.pg_prepared[(data["pg_id"], data["bundle_index"])] = req
        self._refresh_self_view()
        return True

    async def _h_pg_commit(self, conn, data):
        key = (data["pg_id"], data["bundle_index"])
        req = self.pg_prepared.pop(key, None)
        if req is None:
            return False
        self.pg_committed[key] = req
        # Shadow resources let tasks target the bundle (reference naming:
        # CPU_group_{index}_{pgid} and CPU_group_{pgid}).
        hexid = data["pg_id"].hex() if isinstance(data["pg_id"], bytes) else data["pg_id"]
        shadow = {}
        for k, v in req.res.items():
            shadow[f"{k}_group_{data['bundle_index']}_{hexid}"] = v
            shadow[f"{k}_group_{hexid}"] = v
        self.total.release(ResourceSet(shadow))
        self.available.release(ResourceSet(shadow))
        await self._notify_lease_waiters()
        return True

    async def _h_pg_abort(self, conn, data):
        req = self.pg_prepared.pop((data["pg_id"], data["bundle_index"]), None)
        if req is not None:
            self.available.release(req)
            await self._notify_lease_waiters()
        return True

    async def _h_pg_return(self, conn, data):
        key = (data["pg_id"], data["bundle_index"])
        req = self.pg_committed.pop(key, None)
        if req is None:
            return False
        hexid = data["pg_id"].hex() if isinstance(data["pg_id"], bytes) else data["pg_id"]
        shadow = {}
        for k, v in req.res.items():
            shadow[f"{k}_group_{data['bundle_index']}_{hexid}"] = v
            shadow[f"{k}_group_{hexid}"] = v
        self.total.acquire(ResourceSet(shadow))
        self.available.acquire(ResourceSet(shadow))
        # the bundle is gone, and with it whatever still runs under it on
        # the chip (a gang that was just told to exit, or an actor that
        # outlived its placement group)
        await self._release_off_chip(req, [
            w for w in self.workers.values()
            if any(name in shadow for name in self._held_by(w))])
        return True

    def _held_by(self, w: WorkerProc) -> Dict[str, float]:
        held = getattr(w, "actor_resources", None)
        if held is None and w.lease_id in self.leases:
            held = self.leases[w.lease_id].resources
        return held.to_dict() if held is not None else {}

    async def _release_off_chip(self, resources: ResourceSet,
                                ran_under: List[WorkerProc]) -> None:
        """The one rule for when a reservation that may include ``TPU``
        is available again: no process that ran under it can still have
        the chip open.  Either it has been reaped (a zombie's threads
        still hold the chip for seconds), or it sits idle in the pool of
        its platform, where the next ``TPU`` work is handed to it and to
        no other process (`idle_worker_for`).

        A returned lease and a dead worker or actor satisfy that as they
        are (`_h_return_lease`, `_on_worker_death`).  This is for a
        reservation that ends while its chip worker lives — a bundle that
        is returned, an actor whose creation failed: the worker is
        killed, and the resources follow once it is reaped."""
        holders = [w for w in ran_under if w.platform != accelerator.CPU
                   and w.state != "idle" and w.proc.poll() is None]
        if not holders:
            self.available.release(resources)
            await self._notify_lease_waiters()
            return
        for w in holders:
            self._intended_kills.add(w.worker_id)
            w.proc.kill()

        async def _reaped():
            deadline = time.monotonic() + _CHIP_REAP_TIMEOUT_S
            while any(w.proc.poll() is None for w in holders):
                if time.monotonic() > deadline:
                    # not reapable (uninterruptible in the driver): the
                    # next claimant is pinned to the TPU, so it fails
                    # loudly if the chip really is still held
                    print(f"chip worker(s) "
                          f"{[w.proc.pid for w in holders]} not reaped "
                          f"{_CHIP_REAP_TIMEOUT_S}s after SIGKILL; "
                          f"releasing their reservation",
                          file=sys.stderr, flush=True)
                    break
                await asyncio.sleep(0.05)
            self.available.release(resources)
            await self._notify_lease_waiters()

        task = asyncio.ensure_future(_reaped())
        self._tasks.append(task)                  # cancelled by stop()
        task.add_done_callback(self._tasks.remove)

    # -------------------------------------------------------- object transfer
    async def _h_put_location(self, conn, data):
        oid = data["object_id"]
        # Pin PRIMARY copies (worker/driver-produced) in the store so LRU
        # eviction cannot silently drop the only copy — under memory
        # pressure new creates then fail into the writer-spill path instead
        # (reference: the raylet pins primary copies and spills them,
        # local_object_manager.cc; eviction only reclaims replicas).
        if data.get("primary", True) and oid not in self._primary_pins:
            if self.store.get(oid, timeout_ms=0) is not None:
                # hold the get-pin, drop the view; remember the size so the
                # spill loop can pick victims without touching the store
                self._primary_pins[oid] = int(data.get("size", 0))
        await self.controller.call("object_location_add", {
            "object_id": oid, "node_id": self.node_id.hex(),
            "size": data.get("size", 0)})
        return True

    async def _h_pull(self, conn, data):
        """Make the object local, climbing the alternate-path fetch
        ladder (reference: pull_manager.cc:442 TryToMakeObjectLocal +
        push_manager.cc chunked pushes): each directory copy gets
        bounded full-jitter retries; when every direct source fails but
        copies exist (asymmetric partition), the controller relays the
        object through a mutually-reachable peer; only then does the
        failure surface for lineage reconstruction.  Every rung taken
        is counted in ``ray_tpu_object_fetch_fallbacks_total{path}``."""
        from ..util import tracing
        oid = data["object_id"]
        timeout = data.get("timeout", 30.0)
        if self.store.contains(oid):
            if data.get("pin_primary"):
                # drain evacuation to a node already holding a replica:
                # primacy must still transfer or nothing pins the copy
                await self._h_put_location(
                    None, {"object_id": oid, "primary": True})
            return {"ok": True}
        lock = self._pull_locks.setdefault(oid, asyncio.Lock())
        async with lock:
            if self.store.contains(oid):
                if data.get("pin_primary"):
                    await self._h_put_location(
                        None, {"object_id": oid, "primary": True})
                return {"ok": True}
            deadline = time.monotonic() + timeout
            # Fast-fail when the directory has NO location anywhere (self
            # included): primary copies are pinned, so a directory with no
            # entry means the object is gone (evicted replica + dead node,
            # or freed) — report promptly so the owner's lineage
            # reconstruction starts instead of spinning out the timeout.
            no_loc_deadline = time.monotonic() + min(timeout, 5.0)
            t0 = time.time()
            attempted: List[str] = []
            failed_sources: Set[str] = set()
            relay_tried = False
            first_addr: Optional[str] = None

            async def _success(rung: Optional[str], size: int):
                # pin_primary: a drain evacuation hands PRIMARY
                # responsibility to us — pin the copy so LRU eviction
                # cannot drop what is now the sole copy
                await self._h_put_location(
                    None, {"object_id": oid,
                           "primary": bool(data.get("pin_primary")),
                           "size": size})
                if rung is not None:
                    rtm.FETCH_FALLBACKS.inc(tags={"path": rung})
                    tracing.record_span(
                        f"object_fetch_fallback::{oid.hex()[:12]}",
                        "object_fetch_fallback", t0, time.time(),
                        path=rung, attempts=len(attempted) + 1,
                        node_id=self.node_id.hex()[:12])
                return {"ok": True}

            while time.monotonic() < deadline:
                try:
                    info = await self.controller.call("object_locations_get", {
                        "object_id": oid,
                        "timeout": min(2.0, deadline - time.monotonic())})
                except rpc.RpcError as e:
                    return {"ok": False, "error": str(e)}
                pairs = [(a, n) for a, n in
                         zip(info["locations"],
                             info.get("node_ids", [None] * len(
                                 info["locations"])))
                         if a != self.address]
                addrs = [a for a, _ in pairs]
                if not addrs:
                    if self.store.contains(oid):
                        return {"ok": True}
                    if not info["locations"] \
                            and time.monotonic() > no_loc_deadline:
                        if attempted:
                            break  # sources died under us: ladder report
                        return {"ok": False,
                                "error": f"no locations for {oid.hex()}"}
                    await asyncio.sleep(GlobalConfig.pull_retry_interval_s / 5)
                    continue
                no_loc_deadline = time.monotonic() + min(timeout, 5.0)
                await self._admit_pull(int(info.get("size", 0)), deadline)
                for addr, nid in pairs:
                    if first_addr is None:
                        first_addr = addr
                    async with self._pull_sem:  # bound store churn
                        pulled, retried = await self._fetch_with_retry(
                            oid, addr, nid, deadline)
                    if pulled:
                        rung = "retry" if retried else None
                        if addr != first_addr or failed_sources:
                            rung = "alt_copy"
                        return await _success(rung,
                                              int(info.get("size", 0)))
                    failed_sources.add(addr)
                    if len(attempted) < 64:  # bound the failure report
                        attempted.append(
                            addr if nid is None else f"{addr}({nid[:8]})")
                    # Evicted replica left a stale directory entry: purge it
                    # so the no-location fast-fail above can fire.
                    if nid is not None and pulled is None:
                        try:
                            await self.controller.call(
                                "object_location_remove",
                                {"object_id": oid, "node_id": nid})
                        except rpc.RpcError:
                            pass
                if pairs and not relay_tried:
                    # every direct source failed this pass, but copies
                    # exist: ask the controller for a relay through a
                    # mutually-reachable peer (asymmetric A↛B partition)
                    relay_tried = True
                    try:
                        r = await self.controller.call("object_relay", {
                            "object_id": oid,
                            "node_id": self.node_id.hex(),
                            "timeout": min(
                                20.0, max(2.0,
                                          deadline - time.monotonic()))},
                            timeout=30)
                    except rpc.RpcError:
                        r = None
                    if r and r.get("ok"):
                        async with self._pull_sem:
                            pulled, _ = await self._fetch_with_retry(
                                oid, r["addr"], r["node_id"], deadline)
                        if pulled:
                            return await _success(
                                "relay", int(info.get("size", 0)))
                        attempted.append(f"relay via {r['addr']}")
                    elif r is not None:
                        attempted.append(
                            f"relay: {r.get('error', 'unavailable')}")
                await asyncio.sleep(GlobalConfig.pull_retry_interval_s / 5)
            # ladder exhausted — the owner's lineage reconstruction runs
            # next; surface every source we tried (ObjectFetchError text)
            if attempted:
                rtm.FETCH_FALLBACKS.inc(tags={"path": "lineage"})
                tracing.record_span(
                    f"object_fetch_fallback::{oid.hex()[:12]}",
                    "object_fetch_fallback", t0, time.time(),
                    path="lineage", attempts=len(attempted),
                    node_id=self.node_id.hex()[:12])
                return {"ok": False, "attempted": attempted,
                        "error": str(store_client.ObjectFetchError(
                            oid.hex(), attempted))}
            return {"ok": False,
                    "error": f"pull timeout for {oid.hex()}"}

    async def _make_room(self, nbytes: int) -> None:
        """Spill pinned primaries oldest-first until ``nbytes`` fits (or
        no spillable pins remain)."""
        while True:
            st = self.store.stats()
            if st["used_bytes"] + nbytes <= st["capacity_bytes"] * 0.95:
                return
            if not await self._spill_oldest_pin():
                return

    async def _spill_oldest_pin(self) -> bool:
        """Spill exactly one pinned primary (oldest spillable first);
        False when nothing could be spilled.  Known-small pins skip on
        their recorded size — no store round trip per skip."""
        min_bytes = GlobalConfig.spill_min_object_bytes
        for oid, size in list(self._primary_pins.items()):
            if 0 < size < min_bytes:
                continue
            try:
                if await self._spill_one(oid):
                    return True
            except Exception:
                traceback.print_exc(file=sys.stderr)
        return False

    async def _admit_pull(self, size: int, deadline: float) -> None:
        """Memory-pressure pull admission (reference:
        `pull_manager.cc:228` UpdatePullsBasedOnAvailableMemory — active
        pulls are limited to what fits in available memory).  When the
        incoming object would not fit without evicting live data, spill
        pinned primaries to make room first; if concurrent pulls are
        racing for the same space, wait briefly for them to settle.  The
        pull proceeds regardless at the deadline (the create-time
        make-room retry backstops it)."""
        if not size:
            return
        st = self.store.stats()
        if st["used_bytes"] + size <= st["capacity_bytes"] * 0.95:
            return
        await self._make_room(size)
        admit_deadline = min(deadline - 1.0, time.monotonic() + 2.0)
        while time.monotonic() < admit_deadline:
            st = self.store.stats()
            if st["used_bytes"] + size <= st["capacity_bytes"] * 0.95:
                return
            await asyncio.sleep(0.1)

    async def _peer(self, addr: str) -> rpc.Connection:
        conn = self._peer_conns.get(addr)
        if conn is None or conn.closed:
            host, port = addr.rsplit(":", 1)
            conn = await rpc.connect(host, int(port), retries=3)
            self._peer_conns[addr] = conn
        return conn

    async def _fetch_with_retry(self, oid: bytes, addr: str,
                                nid: Optional[str],
                                deadline: float) -> tuple:
        """Bounded full-jitter retries of ONE source — the first rung of
        the fetch ladder.  Returns ``(result, retried)`` where result is
        the ``_pull_from`` trivalent (True / None=absent / False)."""
        from ..util.backoff import ExponentialBackoff
        bo = ExponentialBackoff(base=0.05, cap=0.5)
        attempts = max(1, GlobalConfig.object_fetch_attempts)
        for attempt in range(attempts):
            res = await self._pull_from(oid, addr, nid)
            if res or res is None:
                return res, attempt > 0
            if attempt + 1 >= attempts:
                break
            delay = bo.next_delay()
            if time.monotonic() + delay >= deadline:
                break
            await asyncio.sleep(delay)
        return False, False

    def _crc_ok(self, oid: bytes, expect: int) -> bool:
        """Verify a freshly fetched local copy against the serving
        side's checksum; a mismatch drops the copy (the ladder refetches
        once, then lineage reconstruction takes over)."""
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            return False
        try:
            ok = store_client.crc32_of(view) == expect
        finally:
            del view
            self.store.release(oid)
        if not ok:
            print(f"CRC mismatch on fetched object {oid.hex()[:12]}; "
                  f"dropping the corrupt copy", file=sys.stderr, flush=True)
            try:
                self.store.delete(oid)
            except store_client.StoreError:
                pass
        return ok

    async def _pull_from(self, oid: bytes, addr: str,
                         nid: Optional[str] = None) -> Optional[bool]:
        """True = pulled; None = peer definitively lacks the object (caller
        may purge the stale directory entry); False = transient failure.
        The payload CRC from ``fetch_meta`` is verified on both transfer
        paths before the copy counts as pulled."""
        if fi.ACTIVE is not None:
            act = await fi.ACTIVE.async_point("object.transfer_fetch",
                                              oid.hex(), peer=nid or addr)
            if act is not None and act["action"] not in ("delay", "latency"):
                # injected severed transfer path (peer-directed: A→B
                # only, when the rule pins proc+peer)
                return False
        try:
            peer = await self._peer(addr)
            meta = await peer.call("fetch_meta", {"object_id": oid}, timeout=10)
            if not meta.get("exists"):
                return None
            crc = meta.get("crc32")
            # Fast path: the C++ object plane (transfer.cc) streams the
            # payload segment-to-segment with no Python on the data path.
            tport = meta.get("transfer_port")
            if tport:
                host = addr.rsplit(":", 1)[0]
                try:
                    ok = await asyncio.get_event_loop().run_in_executor(
                        None, lambda: self.store.fetch_retrying(
                            host, tport, oid, attempts=2))
                    if ok:
                        if crc is not None and not self._crc_ok(oid, crc):
                            return False
                        rtm.OBJECTS_PULLED.inc(tags=self._mnode)
                        rtm.BYTES_PULLED.inc(meta["size"],
                                             tags=self._mnode)
                        return True
                except store_client.StoreError:
                    pass  # fall back to the chunked RPC path
            size = meta["size"]
            # Pressure relief on demand (reference: the plasma create
            # queue triggers spilling): each StoreFullError spills one
            # more pinned primary and retries — byte accounting alone
            # isn't enough, the allocator needs a CONTIGUOUS hole, so
            # keep spilling until the create lands or pins run out.
            while True:
                try:
                    dest = self.store.create(oid, size)
                    break
                except store_client.ObjectExistsError:
                    return True
                except store_client.StoreFullError:
                    if not await self._spill_oldest_pin():
                        raise
            chunk = GlobalConfig.object_transfer_chunk_bytes
            try:
                off = 0
                while off < size:
                    n = min(chunk, size - off)
                    part = await peer.call("fetch", {"object_id": oid,
                                                     "offset": off, "size": n},
                                           timeout=30)
                    if part is None:
                        raise rpc.RpcError("remote object vanished mid-pull")
                    dest[off: off + len(part)] = part
                    off += len(part)
            except BaseException:
                del dest
                self.store.abort(oid)
                raise
            if crc is not None and store_client.crc32_of(dest) != crc:
                del dest
                self.store.abort(oid)
                print(f"CRC mismatch on chunked fetch of "
                      f"{oid.hex()[:12]} from {addr}; dropping it",
                      file=sys.stderr, flush=True)
                return False
            del dest
            self.store.seal(oid)
            rtm.OBJECTS_PULLED.inc(tags=self._mnode)
            rtm.BYTES_PULLED.inc(size, tags=self._mnode)
            return True
        except (rpc.RpcError, OSError):
            return False

    async def _h_fetch_meta(self, conn, data):
        oid = data["object_id"]
        if fi.ACTIVE is not None:
            act = fi.ACTIVE.point("object.fetch_meta", oid.hex())
            if act is not None and act["action"] == "evict":
                # Force-evict the local copy mid-pull: drop the primary
                # pin, the store copy, and our directory entry — the
                # puller sees a vanished replica and the owner's lineage
                # reconstruction path has to recover the object.
                if self._primary_pins.pop(oid, None) is not None:
                    self.store.release(oid)
                try:
                    self.store.delete(oid)
                except store_client.StoreError:
                    pass
                try:
                    await self.controller.call(
                        "object_location_remove",
                        {"object_id": oid, "node_id": self.node_id.hex()})
                except rpc.RpcError:
                    pass
                return {"exists": False}
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            return {"exists": False}
        try:
            # payload checksum: the puller verifies it on BOTH transfer
            # paths (native segment-to-segment and chunked RPC) — a
            # corrupted cross-node copy is refetched, never sealed
            return {"exists": True, "size": view.nbytes,
                    "crc32": store_client.crc32_of(view),
                    "transfer_port": self.transfer_port}
        finally:
            del view
            self.store.release(oid)

    async def _h_fetch(self, conn, data):
        oid = data["object_id"]
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            return None
        try:
            off, size = data["offset"], data["size"]
            return bytes(view[off: off + size])
        finally:
            del view
            self.store.release(oid)

    async def _h_free_local(self, conn, data):
        for oid in data["object_ids"]:
            if oid in self._spilling:
                # mid-spill: the spiller must not register a KV entry the
                # controller's sweep has already passed (leaked file)
                self._spill_tombstones.add(oid)
            if self._primary_pins.pop(oid, None) is not None:
                self.store.release(oid)
            try:
                self.store.delete(oid)
            except store_client.StoreError:
                pass
        return True

    # ---------------------------------------------------------------- info
    async def _h_node_info(self, conn, data):
        return {"node_id": self.node_id.hex(), "addr": self.address,
                "store_path": self.store_path,
                "total": self.total.to_dict(),
                "available": self.available.to_dict()}

    async def _h_stats(self, conn, data):
        return {"store": self.store.stats(),
                "workers": {w.worker_id.hex()[:8]: w.state
                            for w in self.workers.values()},
                "leases": len(self.leases),
                "available": self.available.to_dict(),
                "view_version": self.view_version,
                "cluster_view": {nid: v.to_wire()
                                 for nid, v in self.view.items()}}

    # ------------------------------------------------- task/node observability
    async def _h_task_state(self, conn, data):
        """Workers report task start/finish here (direct driver→worker
        pushes bypass the nodelet, so this notify is how the per-node task
        table — the reference's `ray list tasks` source — gets filled)."""
        self._apply_task_state(data["worker_id"], data)
        return True

    async def _h_task_state_batch(self, conn, data):
        """Batched form: workers coalesce start/finish events on a short
        timer so the observability path costs one RPC per flush, not two
        per task (noop tasks are cheaper than their own bookkeeping
        otherwise)."""
        wid = data["worker_id"]
        for event in data["events"]:
            self._apply_task_state(wid, event)
        return True

    def _apply_task_state(self, wid: bytes, data: dict) -> None:
        t = data.get("t") or time.time()
        if data["event"] == "start":
            self._running_tasks[wid] = {
                "name": data.get("name", "?"),
                "task_id": data.get("task_id", b"").hex()
                if data.get("task_id") else "",
                "start": t}
        else:
            run = self._running_tasks.pop(wid, None)
            name = data.get("name", "?")
            self._task_counts[name] = self._task_counts.get(name, 0) + 1
            rtm.TASKS_FINISHED.inc(tags=self._mnode)
            # latency breakdown: workers measure fetch/exec/put per task
            # and ship the durations on the finish event (their own
            # registries are never scraped — this nodelet's is)
            durs = data.get("durs")
            if durs:
                rtm.observe_task_durs(durs, self._mnode["node"])
            # bounded span log for the cluster timeline (reference: per-task
            # profile events -> GCS -> ray.timeline chrome dump,
            # core_worker/profiling.cc + _private/state.py:414)
            if run is not None:
                self._task_spans.append({
                    "name": name, "worker_id": wid.hex(),
                    "task_id": run.get("task_id", ""),
                    "start": run["start"], "end": t})

    async def _h_task_spans(self, conn, data):
        spans = list(self._task_spans)
        if data.get("clear"):
            self._task_spans.clear()
        return spans

    async def _h_metrics_text(self, conn, data):
        """Prometheus exposition of this nodelet's runtime metrics
        (reference: per-component stats exporters, metric_defs.cc).
        Gauges refresh at scrape time, so idle nodes pay nothing."""
        from .. import metrics
        rtm.snapshot_nodelet(self)
        return metrics.prometheus_text()

    async def _h_rpc_attribution(self, conn, data):
        """Per-op RPC dispatch attribution for THIS nodelet process
        (count / time-in-handler / latency quantiles / payload bytes)."""
        return {"proc": f"nodelet@{self.node_id.hex()[:8]}",
                "addr": self.address,
                "ops": rpc.attribution_rows(),
                "lanes": rpc.lane_stats(),
                "loop_lag": {
                    "ewma_ms": getattr(self, "_lag_ewma", 0.0) * 1e3,
                    "max_ms": getattr(self, "_lag_max", 0.0) * 1e3}}

    async def _h_serve_metrics(self, conn, data):
        """Serve-plane samples pushed by THIS node's worker processes
        (replica decode engines every serve_engine_metrics_interval_s;
        the serve controller after autoscale ticks).  Worker registries
        are never scraped, so folding the samples into the NODELET's
        registry — labeled by deployment/replica — is what puts
        per-deployment occupancy, waiting depth, and replica count into
        the metrics-history ring the autoscale loop and `ray-tpu top`
        read."""
        dep = str(data.get("deployment") or "?")
        rep = data.get("replica")
        if rep is not None:
            tags = {"deployment": dep, "replica": str(rep)}
            rtm.SERVE_ENGINE_OCCUPIED.set(
                float(data.get("occupied", 0)), tags)
            rtm.SERVE_ENGINE_WAITING.set(
                float(data.get("waiting", 0)), tags)
            rtm.SERVE_ENGINE_SLOTS.set(
                float(data.get("max_slots", 0)), tags)
            # prefix-cache counters travel CUMULATIVE (worker
            # registries are never scraped — this fold is what makes
            # hit rate visible cluster-wide); inc the positive delta,
            # and treat a shrink as an engine restart
            for key, metric in (
                    ("prefix_hits", rtm.SERVE_PREFIX_HITS),
                    ("prefix_tokens_reused",
                     rtm.SERVE_PREFIX_TOKENS_REUSED)):
                cur = data.get(key)
                if cur is None:
                    continue
                cur = int(cur)
                seen = (dep, str(rep), key)
                prev = self._serve_counter_seen.get(seen, 0)
                delta = cur - prev if cur >= prev else cur
                self._serve_counter_seen[seen] = cur
                if delta > 0:
                    metric.inc(delta, {"deployment": dep})
            # ---- data-plane flight instruments (PR-16) ----
            shapes = data.get("distinct_program_shapes")
            if shapes is not None:
                rtm.SERVE_PROGRAM_SHAPES.set(float(shapes), tags)
            tok = data.get("tokens")
            if tok is not None:
                tok = int(tok)
                seen = (dep, str(rep), "tokens")
                prev = self._serve_counter_seen.get(seen, 0)
                delta = tok - prev if tok >= prev else tok
                self._serve_counter_seen[seen] = tok
                if delta > 0:
                    rtm.SERVE_TOKENS.inc(delta, {"deployment": dep})
            self._fold_phase_totals(dep, str(rep),
                                    data.get("phase_totals"))
            compiled = self._fold_device_profile(
                dep, str(rep), data.get("device_profile"))
            if compiled:
                await self._note_compiles(dep, str(rep), compiled)
        # per-request latency samples (HTTP proxy pushes; no replica
        # key) — folded into the tenant-labeled SLO histograms, then
        # the p95 evaluator runs: latency only ever arrives HERE, so
        # evaluating at fold time needs no loop and is free when idle
        ttft = data.get("ttft_s")
        itl = data.get("itl_s")
        if ttft is not None or itl:
            tenant = self._tenant_label(str(data.get("tenant")
                                            or "anon"))
            htags = {"deployment": dep, "tenant": tenant}
            if ttft is not None:
                rtm.SERVE_TTFT.observe(float(ttft), htags)
                self._slo_note(dep, "ttft", (float(ttft),))
            if itl:
                vals = tuple(float(v) for v in itl)
                for v in vals:
                    rtm.SERVE_ITL.observe(v, htags)
                self._slo_note(dep, "itl", vals)
            await self._maybe_slo_eval(dep)
        if "replicas" in data:
            rtm.SERVE_DEPLOYMENT_REPLICAS.set(
                float(data["replicas"]), {"deployment": dep})
        for direction in ("up", "down"):
            n = data.get(f"decisions_{direction}")
            if n:
                rtm.SERVE_AUTOSCALE_DECISIONS.inc(
                    int(n), {"deployment": dep, "direction": direction})
        return True

    def _tenant_label(self, tenant: str) -> str:
        """Cardinality gate on the serve-histogram tenant label: the
        first `serve_tenant_label_max` distinct tenants keep their
        name; everyone after that is bucketed to ``other`` so a tenant
        enumeration can never blow up the registry series count."""
        if tenant in self._serve_tenants:
            return tenant
        cap = int(getattr(GlobalConfig, "serve_tenant_label_max", 16))
        if len(self._serve_tenants) < max(1, cap):
            self._serve_tenants.add(tenant)
            return tenant
        return "other"

    def _fold_phase_totals(self, dep: str, rep: str, phases) -> None:
        """Delta-fold an engine's cumulative phase seconds (queue /
        admission / prefill / decode_dispatch) into the per-deployment
        phase counter — the serve_breakdown table's source series."""
        if not phases:
            return
        for phase, cur in phases.items():
            try:
                cur = float(cur)
            except (TypeError, ValueError):
                continue
            seen = (dep, rep, f"phase:{phase}")
            prev = self._serve_counter_seen.get(seen, 0)
            delta = cur - prev if cur >= prev else cur
            self._serve_counter_seen[seen] = cur
            if delta > 0:
                rtm.SERVE_PHASE_SECONDS.inc(
                    delta, {"deployment": dep, "phase": str(phase)})

    def _fold_device_profile(self, dep: str, rep: str, rows) -> int:
        """Delta-fold a replica's cumulative dispatch-profiler snapshot
        (see util/device_profile.py) into the per-program device
        counters and the MFU gauge.  Returns the summed recompile delta
        — the compile-storm detector's input."""
        if not rows:
            return 0
        compiled = 0
        for row in rows:
            if not isinstance(row, dict):
                continue
            prog = str(row.get("program") or "?")
            ptags = {"program": prog, "deployment": dep}
            for key, metric, cast in (
                    ("dispatches", rtm.DEVICE_DISPATCHES, int),
                    ("device_s", rtm.DEVICE_SECONDS, float),
                    ("compile_s", rtm.DEVICE_COMPILE_SECONDS, float),
                    ("compiles", rtm.DEVICE_COMPILES, int)):
                cur = row.get(key)
                if cur is None:
                    continue
                cur = cast(cur)
                seen = (dep, rep, f"dp:{prog}:{key}")
                prev = self._serve_counter_seen.get(seen, 0)
                delta = cur - prev if cur >= prev else cur
                self._serve_counter_seen[seen] = cur
                if delta > 0:
                    metric.inc(delta, ptags)
                    if key == "compiles":
                        compiled += int(delta)
            mfu = row.get("mfu")
            if mfu is not None:
                rtm.MFU_RATIO.set(float(mfu), ptags)
        return compiled

    async def _note_compiles(self, dep: str, rep: str, n: int) -> None:
        """Compile-storm detector: recompiles per (deployment, replica)
        summed over a sliding window; past the threshold the controller
        captures a flight bundle (trigger ``compile_storm`` — rate-
        limited there like every auto trigger)."""
        thresh = int(getattr(GlobalConfig,
                             "serve_compile_storm_threshold", 8))
        if thresh <= 0:
            return
        win = float(getattr(GlobalConfig,
                            "serve_compile_storm_window_s", 30.0))
        now = time.monotonic()
        dq = self._compile_events.setdefault((dep, rep), deque())
        dq.append((now, int(n)))
        while dq and now - dq[0][0] > win:
            dq.popleft()
        total = sum(c for _, c in dq)
        if total < thresh:
            return
        dq.clear()    # one alert per accumulation window
        try:
            await self.controller.notify("debug_capture", {
                "trigger": "compile_storm",
                "reason": f"{total} recompiles in {win:.0f}s on "
                          f"{dep}/{rep}",
                "meta": {"deployment": dep, "replica": rep,
                         "compiles": total, "window_s": win}})
        except Exception:
            pass   # controller reconnecting; next window retries

    def _slo_note(self, dep: str, kind: str, vals) -> None:
        dq = self._slo_samples.setdefault((dep, kind),
                                          deque(maxlen=512))
        dq.extend(vals)

    async def _maybe_slo_eval(self, dep: str) -> None:
        """p95 TTFT/ITL SLO check over the retained raw-sample windows;
        disabled until `serve_slo_{ttft,itl}_p95_s` is set.  A breach
        fires the ``slo_breach`` flight-recorder trigger with the
        measured quantile in the bundle meta."""
        bounds = (
            ("ttft", float(getattr(GlobalConfig,
                                   "serve_slo_ttft_p95_s", 0.0))),
            ("itl", float(getattr(GlobalConfig,
                                  "serve_slo_itl_p95_s", 0.0))))
        if all(b <= 0 for _, b in bounds):
            return
        if fi.ACTIVE is not None:
            act = fi.ACTIVE.point("serve.slo_eval", dep)
            if act is not None:
                if act["action"] in ("delay", "latency"):
                    await asyncio.sleep(max(0.0, act["delay_s"]))
                else:
                    raise RuntimeError(
                        f"chaos: injected slo_eval failure for {dep}")
        min_n = max(1, int(getattr(GlobalConfig,
                                   "serve_slo_min_samples", 20)))
        for kind, bound in bounds:
            if bound <= 0:
                continue
            dq = self._slo_samples.get((dep, kind))
            if dq is None or len(dq) < min_n:
                continue
            vals = sorted(dq)
            p95 = vals[min(len(vals) - 1, int(0.95 * len(vals)))]
            if p95 <= bound:
                continue
            dq.clear()   # re-arm: breach needs min_n fresh samples
            try:
                await self.controller.notify("debug_capture", {
                    "trigger": "slo_breach",
                    "reason": f"{dep} p95 {kind} {p95 * 1e3:.1f}ms > "
                              f"bound {bound * 1e3:.1f}ms",
                    "meta": {"deployment": dep, "kind": kind,
                             "p95_s": round(p95, 6), "bound_s": bound,
                             "samples": len(vals)}})
            except Exception:
                pass

    async def _h_metrics_history(self, conn, data):
        """This nodelet's bounded metrics-history ring (fixed-interval
        counter deltas + gauges; core/metrics_history.py)."""
        rtm.snapshot_nodelet(self)
        return self.metrics_ring.to_wire(last=data.get("last"))

    async def _h_node_stats(self, conn, data):
        """Per-node deep stats (reference: dashboard/agent.py reporter +
        node module): worker table, running tasks, finished-task counts,
        object store usage, pins, transfer port."""
        workers = []
        for w in self.workers.values():
            ent = {"worker_id": w.worker_id.hex(), "state": w.state,
                   "pid": w.proc.pid,
                   "actor_id": w.actor_id.hex() if w.actor_id else None}
            run = self._running_tasks.get(w.worker_id)
            if run is not None:
                ent["running_task"] = dict(run)
            workers.append(ent)
        return {
            "node_id": self.node_id.hex(),
            "addr": self.address,
            "workers": workers,
            "running_tasks": [
                {"worker_id": wid.hex(), **info}
                for wid, info in self._running_tasks.items()],
            "task_counts": dict(self._task_counts),
            "store": self.store.stats(),
            "primary_pins": len(self._primary_pins),
            "oom_kills": getattr(self, "_oom_kills", 0),
            "memory_usage": self._memory_usage_fraction(),
            "event_loop_lag": {
                "ewma_ms": getattr(self, "_lag_ewma", 0.0) * 1000.0,
                "max_ms": getattr(self, "_lag_max", 0.0) * 1000.0},
            "transfer_port": self.transfer_port,
            "available": self.available.to_dict(),
            "total": self.total.to_dict(),
        }

    async def _h_tail_log(self, conn, data):
        """Tail a per-process log file from this node's session dir
        (reference: LogMonitor tailing /tmp/ray/session_*/logs,
        python/ray/_private/log_monitor.py:100)."""
        import glob
        name = data.get("name", "")
        if "/" in name or ".." in name:
            return {"error": "bad log name"}
        log_dir = os.path.join(self.session_dir, "logs")
        if not name:
            return {"files": sorted(os.path.basename(p) for p in
                                    glob.glob(os.path.join(log_dir, "*")))}
        path = os.path.join(log_dir, name)

        def _read_tail():
            with open(path, "rb") as f:
                f.seek(0, 2)
                size = f.tell()
                n = min(int(data.get("bytes", 65536)), size)
                f.seek(size - n)
                return {"data": f.read(n), "size": size}
        try:
            # off-loop: a 64 KB read from a cold page cache must not
            # stall heartbeats/leases (PR-13 loop-blocking lint)
            return await asyncio.to_thread(_read_tail)
        except OSError as e:
            return {"error": str(e)}

    async def _h_ping(self, conn, data):
        return "pong"
