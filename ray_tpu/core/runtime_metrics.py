"""Runtime self-metrics: the framework instruments itself.

Capability mirror of the reference's predefined metrics battery
(`src/ray/stats/metric_defs.cc:1` — ~90 scheduler/object-store/transport
gauges and counters every component exports).  Definitions live here in
one place; components bump the counters directly at natural sites
(spawn, death, lease grant, spill, ...) and `snapshot_<component>()`
refreshes the gauges from live state at scrape time.  Exposition rides
the existing Prometheus path (`ray_tpu/metrics.py`): the nodelet and
controller answer a `metrics_text` RPC with their process registries,
and `state.cluster_metrics_text()` / the dashboard's /metrics/cluster
serve the cluster-wide union.
"""

from __future__ import annotations

from typing import Any

from .. import metrics as m

# ---------------------------------------------------------------- counters

TASKS_FINISHED = m.Counter(
    "ray_tpu_tasks_finished_total",
    "Tasks finished on this node", ("node",))
WORKERS_SPAWNED = m.Counter(
    "ray_tpu_workers_spawned_total",
    "Worker processes spawned", ("node", "mode"))   # mode: fork | exec
WORKERS_DIED = m.Counter(
    "ray_tpu_workers_died_total",
    "Worker processes that exited", ("node",))
OOM_KILLS = m.Counter(
    "ray_tpu_oom_kills_total",
    "Workers killed by the memory monitor", ("node",))
TASK_DEATHS = m.Counter(
    "ray_tpu_task_deaths_total",
    "Worker deaths classified by the nodelet's death attributor, by "
    "typed cause (signal:<NAME> | oom_kill | exit:<code> | chaos_kill | "
    "node_death | unknown) — poison-shaped causes feed the controller's "
    "crash ledger, preemption-shaped ones retry freely",
    ("node", "cause"))
QUARANTINES = m.Counter(
    "ray_tpu_quarantines_total",
    "Poison quarantines imposed by the controller's crash ledger, by "
    "kind (task: a signature hit poison_task_threshold kills inside "
    "poison_window_s | actor: a crash-looping actor exhausted its "
    "rolling restart window on poison-shaped deaths)", ("kind",))
RECONSTRUCTION_DEDUP = m.Counter(
    "ray_tpu_reconstruction_dedup_total",
    "Lineage reconstruction requests that joined an already in-flight "
    "reconstruction of the same object instead of re-executing its "
    "producer again (owner-side storm governance)", ())
RECONSTRUCTION_EXECUTED = m.Counter(
    "ray_tpu_reconstruction_executed_total",
    "Lineage reconstructions that actually resubmitted the producing "
    "task (the re-execution amplification numerator against "
    "dedup_total)", ())
LEASES_GRANTED = m.Counter(
    "ray_tpu_scheduler_leases_granted_total",
    "Worker leases granted", ("node",))
LEASES_SPILLBACK = m.Counter(
    "ray_tpu_scheduler_spillbacks_total",
    "Lease requests redirected to a peer node", ("node",))
LEASES_INFEASIBLE = m.Counter(
    "ray_tpu_scheduler_infeasible_total",
    "Lease requests infeasible cluster-wide", ("node",))
OBJECTS_SPILLED = m.Counter(
    "ray_tpu_objects_spilled_total",
    "Objects spilled to external storage", ("node",))
BYTES_SPILLED = m.Counter(
    "ray_tpu_objects_spilled_bytes_total",
    "Bytes spilled to external storage", ("node",))
OBJECTS_RESTORED = m.Counter(
    "ray_tpu_objects_restored_total",
    "Spilled objects restored (driver-process restores only: worker "
    "registries are not scraped)", ("node",))
OBJECTS_PULLED = m.Counter(
    "ray_tpu_objects_pulled_total",
    "Objects pulled from peer nodes", ("node",))
BYTES_PULLED = m.Counter(
    "ray_tpu_objects_pulled_bytes_total",
    "Bytes pulled from peer nodes", ("node",))
HEARTBEATS = m.Counter(
    "ray_tpu_heartbeats_total",
    "Heartbeats sent to the controller", ("node",))
ACTORS_CREATED = m.Counter(
    "ray_tpu_actors_created_total",
    "Actor creations processed by the controller", ())
ACTORS_RESTARTED = m.Counter(
    "ray_tpu_actors_restarted_total",
    "Actor restarts orchestrated by the controller", ())
PUBSUB_MESSAGES = m.Counter(
    "ray_tpu_pubsub_messages_total",
    "Messages published on controller channels", ("channel",))
PUBSUB_DROPPED = m.Counter(
    "ray_tpu_pubsub_dropped_total",
    "Pubsub events dropped (oldest-first) because a subscriber's "
    "bounded buffer overflowed (pubsub_max_buffer); the subscriber is "
    "flagged for snapshot resync on its next flush", ("channel",))
RPC_LANE_DEPTH = m.Gauge(
    "ray_tpu_rpc_lane_depth",
    "Inbound RPC frames currently queued per priority lane "
    "(liveness | control | bulk) in this process", ("lane", "proc"))
RPC_LANE_QUEUED_BYTES = m.Gauge(
    "ray_tpu_rpc_lane_queued_bytes",
    "Payload bytes currently queued per RPC priority lane — the "
    "overload watermark evaluator's queued-bytes signal", ("lane", "proc"))
RPC_LANE_DISPATCHED = m.Counter(
    "ray_tpu_rpc_lane_dispatched_total",
    "RPC dispatches started per priority lane", ("lane", "proc"))
RPC_LANE_WAIT_SECONDS = m.Counter(
    "ray_tpu_rpc_lane_queue_wait_seconds_total",
    "Cumulative time RPC frames waited in their lane queue before "
    "dispatch started", ("lane", "proc"))
OVERLOAD_STATE = m.Gauge(
    "ray_tpu_overload_state",
    "Controller overload watermark state (0=normal 1=soft 2=brownout)",
    ())
OVERLOAD_SHED = m.Counter(
    "ray_tpu_overload_shed_total",
    "Bulk-lane ops shed with the typed retriable pushback under "
    "overload (brownout or a chaos-forced shed)", ("op",))
NODE_DRAINS = m.Counter(
    "ray_tpu_node_drains_total",
    "Graceful node drains by outcome (completed | deadline | error)",
    ("outcome",))
ACTORS_MIGRATED = m.Counter(
    "ray_tpu_actors_migrated_total",
    "Actors proactively migrated off draining nodes (no restart budget "
    "burned)", ())
OBJECTS_EVACUATED = m.Counter(
    "ray_tpu_objects_evacuated_total",
    "Sole-copy objects pushed to a peer during node drain", ("node",))
TRAIN_REPAIRS = m.Counter(
    "ray_tpu_train_repairs_total",
    "Elastic gang repairs after an unannounced worker/node death, by "
    "outcome (repaired: healthy ranks parked, dead ranks rescheduled, "
    "gang resumed from the peer-replicated snapshot | fallback: repair "
    "aborted, legacy full restart-from-disk taken)", ("outcome",))
TRAIN_LOST_STEPS = m.Counter(
    "ray_tpu_train_repair_lost_steps_total",
    "Train steps rewound by elastic repairs (last reported step minus "
    "the restored snapshot step; bounded by "
    "elastic snapshot_interval_steps per repair)", ())
SERVE_TOKENS = m.Counter(
    "ray_tpu_serve_tokens_total",
    "Tokens decoded by replica continuous-batching engines "
    "(decode_session.py); incremented in the replica's process AND "
    "delta-folded into the nodelet registry from engine "
    "`serve_metrics` pushes, so the cluster scrape carries it (the "
    "serve_breakdown table's per-token denominator)",
    ("deployment",))
SERVE_PREFILL_CHUNKS = m.Counter(
    "ray_tpu_serve_prefill_chunks_total",
    "Fixed-shape prefill chunk programs run by serve decode engines — "
    "chunked admission and failover resume share these programs, and "
    "each one is the most a joining session may stall live streams",
    ("deployment",))
SERVE_PREFIX_HITS = m.Counter(
    "ray_tpu_serve_prefix_hits_total",
    "Engine admissions seeded from a live slot's shared prompt prefix "
    "(serve/prefix_cache.py): the session prefilled only its unshared "
    "suffix instead of the whole prompt", ("deployment",))
SERVE_PREFIX_TOKENS_REUSED = m.Counter(
    "ray_tpu_serve_prefix_tokens_reused_total",
    "Prompt tokens whose prefill was skipped by shared-prefix KV reuse "
    "(copied out of a donor decode slot via models.cache_gather_slot)",
    ("deployment",))
SERVE_ENGINE_OCCUPIED = m.Gauge(
    "ray_tpu_serve_engine_occupied_slots",
    "Occupied decode slots per serve engine, folded into the NODELET's "
    "registry from replica `serve_metrics` pushes — the per-deployment "
    "occupancy series the autoscale loop trends via metrics history",
    ("deployment", "replica"))
SERVE_ENGINE_WAITING = m.Gauge(
    "ray_tpu_serve_engine_waiting_sessions",
    "Sessions waiting for a decode slot (admission queue + mid-prefill) "
    "per serve engine; nodelet-folded like occupied_slots — waiting "
    "depth trending up is the autoscaler's scale-up-before-shedding "
    "signal", ("deployment", "replica"))
SERVE_ENGINE_SLOTS = m.Gauge(
    "ray_tpu_serve_engine_max_slots",
    "Compiled decode-slot capacity per serve engine (DecodeEngineConfig"
    ".max_slots); capacity denominator of the autoscaler's utilization",
    ("deployment", "replica"))
SERVE_DEPLOYMENT_REPLICAS = m.Gauge(
    "ray_tpu_serve_deployment_replicas",
    "Serving replica count per deployment as pushed by the serve "
    "controller's autoscale loop — with the occupancy series, the "
    "replica-count-vs-load timeline of the autoscale bench",
    ("deployment",))
SERVE_AUTOSCALE_DECISIONS = m.Counter(
    "ray_tpu_serve_autoscale_decisions_total",
    "Applied serve autoscale decisions by direction (up | down); "
    "nodelet-folded from serve controller pushes so history/top see "
    "scale activity", ("deployment", "direction"))
# -- data-plane dispatch profiling (util/device_profile.py snapshots
# ride the replica's `serve_metrics` push; the nodelet folds cumulative
# deltas here so compile ledgers and MFU reach cluster scrape) ---------
DEVICE_DISPATCHES = m.Counter(
    "ray_tpu_device_dispatches_total",
    "Jitted-program dispatches by the data plane (decode step, prefill "
    "chunk, cache insert/gather), folded from replica "
    "dispatch-profiler snapshots", ("program", "deployment"))
DEVICE_SECONDS = m.Counter(
    "ray_tpu_device_seconds_total",
    "Estimated device seconds per jitted program (block-until-ready "
    "time sampled every Nth dispatch, extrapolated over all "
    "dispatches) — the MFU denominator and the decode roofline",
    ("program", "deployment"))
DEVICE_COMPILE_SECONDS = m.Counter(
    "ray_tpu_device_compile_seconds_total",
    "Wall seconds spent in first-seen-shape dispatches (XLA trace + "
    "compile) per jitted program — the compile ledger's cost column",
    ("program", "deployment"))
DEVICE_COMPILES = m.Counter(
    "ray_tpu_device_compiles_total",
    "Distinct argument shapes dispatched per jitted program (each one "
    "compiled a new executable); growth proportional to traffic "
    "instead of O(1) is a compile storm and fires the `compile_storm` "
    "flight-recorder trigger", ("program", "deployment"))
SERVE_PHASE_SECONDS = m.Counter(
    "ray_tpu_serve_phase_seconds_total",
    "Serve data-plane time by named phase (cold_start: lazy replica "
    "construction; queue: enqueue to first prefill chunk; admission: "
    "first token to decode slot; prefill: chunk program wall; "
    "decode_dispatch: decode/insert program wall) — the "
    "serve_breakdown attribution table's source",
    ("deployment", "phase"))
CONTROLLER_FAILOVERS = m.Counter(
    "ray_tpu_controller_failovers_total",
    "Controller leadership changes by outcome (promoted: a hot standby "
    "took leadership after the leader's lease lapsed | fenced: a "
    "deposed leader was epoch-fenced and stopped accepting writes)",
    ("outcome",))
SUSPECT_TRANSITIONS = m.Counter(
    "ray_tpu_node_suspect_transitions_total",
    "SUSPECT-quarantine exits by outcome (rejoined: the controller link "
    "healed inside the grace budget — actors and objects untouched, "
    "zero restarts | died: the grace ran out, or probing peers lost the "
    "node too, so the hard-death recovery path ran)", ("outcome",))
FETCH_FALLBACKS = m.Counter(
    "ray_tpu_object_fetch_fallbacks_total",
    "Cross-node object fetches that needed a ladder rung beyond the "
    "first direct attempt (retry: same source succeeded on a jittered "
    "retry | alt_copy: another directory copy served it | relay: a "
    "controller-picked mutually-reachable peer relayed it | lineage: "
    "every path failed and reconstruction is the answer)", ("path",))
# -- per-RPC attribution (folded from rpc.dispatch_stats at scrape /
# history-sample time; the raw table with latency quantiles is served by
# the `rpc_attribution` RPC and state.rpc_attribution()) ----------------
RPC_HANDLER_CALLS = m.Counter(
    "ray_tpu_rpc_handler_calls_total",
    "RPC dispatches handled, by op and serving process — the "
    "control-plane attribution table's count column", ("op", "proc"))
RPC_HANDLER_ERRORS = m.Counter(
    "ray_tpu_rpc_handler_errors_total",
    "RPC dispatches whose handler raised", ("op", "proc"))
RPC_HANDLER_SECONDS = m.Counter(
    "ray_tpu_rpc_handler_seconds_total",
    "Wall seconds spent inside RPC handlers (dispatch to reply sent), "
    "by op — where control-plane time actually goes", ("op", "proc"))
RPC_HANDLER_BYTES = m.Counter(
    "ray_tpu_rpc_handler_bytes_total",
    "Payload bytes through RPC handlers (direction: in = request "
    "frame, out = reply frame)", ("op", "proc", "direction"))
WAL_APPENDS = m.Counter(
    "ray_tpu_controller_wal_appends_total",
    "WAL records durably appended by this controller", ())
WAL_APPEND_SECONDS = m.Counter(
    "ray_tpu_controller_wal_append_seconds_total",
    "Wall seconds spent in WAL appends (pack + write + fsync) — "
    "divide by appends_total for the mean append cost", ())
WAL_FSYNC_SECONDS = m.Counter(
    "ray_tpu_controller_wal_fsync_seconds_total",
    "Wall seconds of the fsync share of WAL appends (the disk-bound "
    "floor under every mutating controller reply)", ())
WAL_ERRORS = m.Counter(
    "ray_tpu_controller_wal_errors_total",
    "WAL write failures by op (append | fsync: the FIRST one poisons "
    "the store and self-fences the leader — fsyncgate | snapshot: "
    "compaction failed and the WAL was kept)", ("op",))
STORAGE_FAULTS = m.Counter(
    "ray_tpu_storage_faults_total",
    "Storage faults absorbed by a degradation ladder, by site and "
    "outcome (retained: spill failed, object stayed in memory | "
    "backpressured: a put waited out a spill fault | missing / "
    "corrupt_dropped: a spill copy was unusable and the fetch ladder "
    "fell through | kept_previous: a checkpoint write failed, the last "
    "good one stands | shed: a best-effort incident write was dropped "
    "| leaked: a spill-file GC unlink failed)", ("site", "outcome"))
NODE_DISK_USED_FRAC = m.Gauge(
    "ray_tpu_node_disk_used_frac",
    "Used fraction of the filesystem under the node's spill root "
    "(statvfs, disk-health monitor cadence)", ("node",))
NODE_DISK_STATE = m.Gauge(
    "ray_tpu_node_disk_state",
    "Disk-health watermark state of the node's spill filesystem "
    "(0=ok, 1=low: spill-target selection avoids the node, 2=red: "
    "proactive spill stops and the disk_pressure trigger fires)",
    ("node",))
SCHED_WAVES = m.Counter(
    "ray_tpu_scheduler_waves_total",
    "Scheduler wake-up waves (lease-waiter cohort re-evaluations after "
    "resources freed or the view changed)", ("node",))
SERVE_SESSIONS_MIGRATED = m.Counter(
    "ray_tpu_serve_sessions_migrated_total",
    "Decode sessions re-admitted on a healthy replica by the proxy-side "
    "failover path (serve/failover.py), by trigger: replica_death "
    "(owner crashed / node died), drain (owner's replica evacuating), "
    "error (persistent request failure or a lost destructive "
    "next_chunk reply)", ("reason",))

# -------------------------------------------------- latency histograms
# Per-phase breakdown of a task's life, derived from the same lifecycle
# spans the cluster timeline draws (reference: the scheduler/transport
# latency battery of metric_defs.cc).  Scheduling + queue wait land in
# the nodelet/driver registries directly; fetch/exec/put are observed
# worker-side and reported to the nodelet on the finish event (worker
# registries are not scraped).

_LAT_BOUNDS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
               1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

SCHED_LATENCY = m.Histogram(
    "ray_tpu_task_scheduling_latency_seconds",
    "Lease request arrival to worker grant", _LAT_BOUNDS, ("node",))
QUEUE_WAIT = m.Histogram(
    "ray_tpu_task_queue_wait_seconds",
    "Task submit to dispatch at a leased worker", _LAT_BOUNDS, ("node",))
ARG_FETCH = m.Histogram(
    "ray_tpu_task_arg_fetch_seconds",
    "Argument resolution/object-store fetch time", _LAT_BOUNDS, ("node",))
EXEC_TIME = m.Histogram(
    "ray_tpu_task_exec_seconds",
    "User-code execution time", _LAT_BOUNDS, ("node",))
RESULT_PUT = m.Histogram(
    "ray_tpu_task_result_put_seconds",
    "Result serialization/store time", _LAT_BOUNDS, ("node",))
SERVE_DECODE_OCCUPANCY = m.Histogram(
    "ray_tpu_serve_decode_batch_occupancy",
    "Active decode slots per continuous-batching engine step — how full "
    "the batched decode program runs (the serve-vs-raw decode gap closes "
    "as this climbs toward max_slots)",
    (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0), ("deployment",))
SERVE_FAILOVER_LATENCY = m.Histogram(
    "ray_tpu_serve_session_failover_seconds",
    "Wall time of one decode-session failover: recovery trigger to the "
    "resumed session's first token on the new replica (the client-"
    "visible stall)",
    (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0), ("deployment",))
TRAIN_REPAIR_DURATION = m.Histogram(
    "ray_tpu_train_repair_seconds",
    "Wall time of one elastic gang repair: death detection to the gang "
    "training again at the snapshot step (recovery time; the elastic "
    "promise is seconds, not a full-restart rendezvous)",
    (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0), ("outcome",))
DRAIN_DURATION = m.Histogram(
    "ray_tpu_node_drain_duration_seconds",
    "Wall time of one node drain, start to deregister/fallback",
    (0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0),
    ("outcome",))
CONTROLLER_FAILOVER_DURATION = m.Histogram(
    "ray_tpu_controller_failover_seconds",
    "Control-plane outage of one leader failover: last contact with the "
    "dead leader to the standby serving as the new leader (bounded by "
    "ha_lease_timeout_s plus one state restore)",
    (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0), ())
SCHED_QUEUE_DEPTH_AT_GRANT = m.Histogram(
    "ray_tpu_scheduler_queue_depth_at_grant",
    "Lease requests waiting at this node at the moment one was granted "
    "— sustained depth under a wave is the admission backlog item 4's "
    "batching must drain",
    (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 1024.0),
    ("node",))
SERVE_TTFT = m.Histogram(
    "ray_tpu_serve_ttft_seconds",
    "Time to first token of one streamed decode request, measured at "
    "the HTTP proxy (request accepted to first token ready) and pushed "
    "to the nodelet per request; tenant from the request's `tenant` "
    "field / x-tenant header, default 'anon', cardinality-capped with "
    "overflow bucketed to 'other' — the per-tenant SLO series",
    (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
     30.0), ("deployment", "tenant"))
SERVE_ITL = m.Histogram(
    "ray_tpu_serve_itl_seconds",
    "Inter-token latency of streamed decode requests (gap between "
    "consecutive SSE token emissions at the proxy), nodelet-folded "
    "like ray_tpu_serve_ttft_seconds and labeled the same way",
    (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
     1.0, 5.0), ("deployment", "tenant"))
SCHED_WAVE_BATCH = m.Histogram(
    "ray_tpu_scheduler_wave_batch_size",
    "Lease waiters woken per scheduler wave (cohort size when freed "
    "resources / a view change re-ran admission)",
    (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 1024.0),
    ("node",))


def observe_task_durs(durs: dict, node: str) -> None:
    """Feed one finished task's worker-reported phase durations into the
    breakdown histograms (nodelet-side, at finish-event apply time)."""
    tags = {"node": node}
    for key, hist in (("fetch", ARG_FETCH), ("exec", EXEC_TIME),
                      ("put", RESULT_PUT)):
        v = durs.get(key)
        if v is not None:
            hist.observe(float(v), tags)


# ------------------------------------------------------------------ gauges

WORKER_POOL = m.Gauge(
    "ray_tpu_worker_pool_size",
    "Workers by state", ("node", "state"))
LEASE_WAITERS = m.Gauge(
    "ray_tpu_scheduler_lease_waiters",
    "Lease requests currently waiting", ("node",))
RUNNING_TASKS = m.Gauge(
    "ray_tpu_running_tasks",
    "Tasks executing right now", ("node",))
STORE_BYTES_USED = m.Gauge(
    "ray_tpu_object_store_bytes_used",
    "Object store bytes in use", ("node",))
STORE_CAPACITY = m.Gauge(
    "ray_tpu_object_store_capacity_bytes",
    "Object store capacity", ("node",))
STORE_OBJECTS = m.Gauge(
    "ray_tpu_object_store_objects",
    "Objects resident in the store", ("node",))
PRIMARY_PINS = m.Gauge(
    "ray_tpu_object_store_primary_pins",
    "Primary copies pinned against eviction", ("node",))
PG_RESERVED = m.Gauge(
    "ray_tpu_placement_group_bundles_reserved",
    "PG bundles holding resources on this node", ("node", "phase"))
VIEW_VERSION = m.Gauge(
    "ray_tpu_cluster_view_version",
    "Version of the resource view this node has applied", ("node",))
LOOP_LAG = m.Gauge(
    "ray_tpu_event_loop_lag_seconds",
    "EWMA of event-loop wakeup lag", ("node",))
NODES_ALIVE = m.Gauge(
    "ray_tpu_nodes_alive", "Nodes the controller sees alive", ())
ACTORS_BY_STATE = m.Gauge(
    "ray_tpu_actors", "Actors by lifecycle state", ("state",))
KV_KEYS = m.Gauge(
    "ray_tpu_internal_kv_keys", "Keys in the controller KV", ())
OBJECT_DIRECTORY = m.Gauge(
    "ray_tpu_object_directory_entries",
    "Objects tracked in the controller directory", ())
PEER_UNREACHABLE_PAIRS = m.Gauge(
    "ray_tpu_peer_unreachable_pairs",
    "Directed node pairs (src -> dst) whose peer-reachability probe "
    "freshly failed, per the controller's connectivity matrix — 0 in a "
    "healthy cluster; asymmetric links count once per broken direction",
    ())
WAL_REPLICATION_LAG = m.Gauge(
    "ray_tpu_controller_wal_replication_lag_records",
    "WAL records the hot-standby controller is behind the leader "
    "(0 with a healthy sync stream; grows while the replication stream "
    "is severed or the leader runs in degraded async mode)", ())
MFU_RATIO = m.Gauge(
    "ray_tpu_mfu_ratio",
    "Model-FLOPs-utilization estimate per jitted data-plane program "
    "(analytic FLOPs/token × tokens ÷ sampled device seconds ÷ peak "
    "FLOP/s), computed replica-side by the dispatch profiler and "
    "nodelet-folded; on CPU harnesses the peak is nominal, so treat "
    "the ratio as relative, not absolute", ("program", "deployment"))
SERVE_PROGRAM_SHAPES = m.Gauge(
    "ray_tpu_serve_program_shapes",
    "Distinct compiled program shapes a serve decode engine has "
    "dispatched (engine_stats program_shapes, finally at cluster "
    "scrape) — O(1) when healthy; growth with traffic is the "
    "compile-storm signature", ("deployment", "replica"))


# ------------------------------------------------------------- snapshots

# last-folded cumulative values per (metric, op, direction) — the rpc /
# WAL tables are cumulative, Counters only accept increments
_folded: dict = {}


def _fold(metric: "m.Counter", total: float, **tags: str) -> None:
    key = (metric.name,) + tuple(sorted(tags.items()))
    prev = _folded.get(key, 0.0)
    if total > prev:
        metric.inc(total - prev, tags=tags)
        _folded[key] = total


def fold_rpc_dispatch() -> None:
    """Fold this process's per-op RPC dispatch table (core/rpc.py) into
    the Prometheus counters — called at scrape and history-sample time
    by the controller and nodelets."""
    from ..util import tracing
    from . import rpc
    proc = tracing.proc_label()
    for op, st in rpc.dispatch_stats().items():
        _fold(RPC_HANDLER_CALLS, st["count"], op=op, proc=proc)
        if st["errors"]:
            _fold(RPC_HANDLER_ERRORS, st["errors"], op=op, proc=proc)
        _fold(RPC_HANDLER_SECONDS, st["total_s"], op=op, proc=proc)
        _fold(RPC_HANDLER_BYTES, st["bytes_in"], op=op, proc=proc,
              direction="in")
        _fold(RPC_HANDLER_BYTES, st["bytes_out"], op=op, proc=proc,
              direction="out")


def fold_rpc_lanes() -> None:
    """Fold this process's per-lane RPC queue table (core/rpc.py) into
    the Prometheus battery — gauges set directly, monotonic totals
    delta-folded like the dispatch table."""
    from ..util import tracing
    from . import rpc
    proc = tracing.proc_label()
    for lane, st in rpc.lane_stats().items():
        RPC_LANE_DEPTH.set(st["depth"], {"lane": lane, "proc": proc})
        RPC_LANE_QUEUED_BYTES.set(st["queued_bytes"],
                                  {"lane": lane, "proc": proc})
        _fold(RPC_LANE_DISPATCHED, st["dispatched"], lane=lane, proc=proc)
        _fold(RPC_LANE_WAIT_SECONDS, st["queued_s"], lane=lane, proc=proc)


def fold_wal_timing(pstore: Any) -> None:
    if pstore is None:
        return
    t = pstore.timing
    _fold(WAL_APPENDS, t["appends"])
    _fold(WAL_APPEND_SECONDS, t["append_s"])
    _fold(WAL_FSYNC_SECONDS, t["fsync_s"])
    for op in ("append", "fsync", "snapshot"):
        errs = t.get(f"{op}_errors", 0)
        if errs:
            _fold(WAL_ERRORS, errs, op=op)


def snapshot_nodelet(nl: Any) -> None:
    """Refresh nodelet gauges from live state (heartbeat cadence)."""
    nid = nl.node_id.hex()[:12]
    states = {"idle": 0, "leased": 0, "actor": 0, "starting": 0}
    for w in nl.workers.values():
        if w.state in states:
            states[w.state] += 1
    for st, count in states.items():
        WORKER_POOL.set(count, {"node": nid, "state": st})
    LEASE_WAITERS.set(nl._lease_waiters, {"node": nid})
    RUNNING_TASKS.set(len(nl._running_tasks), {"node": nid})
    VIEW_VERSION.set(nl.view_version, {"node": nid})
    PG_RESERVED.set(len(nl.pg_prepared), {"node": nid, "phase": "prepared"})
    PG_RESERVED.set(len(nl.pg_committed),
                    {"node": nid, "phase": "committed"})
    if nl.store is not None:
        try:
            info = nl.store.stats()
            STORE_BYTES_USED.set(info.get("used_bytes", 0), {"node": nid})
            STORE_CAPACITY.set(info.get("capacity_bytes", 0),
                               {"node": nid})
            STORE_OBJECTS.set(info.get("num_objects", 0), {"node": nid})
        except Exception:
            pass
    PRIMARY_PINS.set(len(nl._primary_pins), {"node": nid})
    LOOP_LAG.set(getattr(nl, "_lag_ewma", 0.0), {"node": nid})
    disk = getattr(nl, "disk_health", None)
    if disk:
        NODE_DISK_USED_FRAC.set(disk.get("used_frac", 0.0), {"node": nid})
        NODE_DISK_STATE.set(
            {"ok": 0, "low": 1, "red": 2}.get(disk.get("state"), 0),
            {"node": nid})
    fold_rpc_dispatch()
    fold_rpc_lanes()


def snapshot_controller(ctl: Any) -> None:
    """Refresh controller gauges from live state."""
    fold_rpc_dispatch()
    fold_rpc_lanes()
    fold_wal_timing(ctl.pstore)
    ovl = getattr(ctl, "overload", None)
    if ovl is not None:
        OVERLOAD_STATE.set(ovl.state_index())
    LOOP_LAG.set(getattr(ctl, "_lag_ewma", 0.0), {"node": "controller"})
    alive = sum(1 for r in ctl.nodes.values()
                if getattr(r.view, "alive", False))
    NODES_ALIVE.set(alive)
    by_state: dict = {}
    for a in ctl.actors.values():
        st = getattr(a, "state", "?")
        by_state[st] = by_state.get(st, 0) + 1
    for st, count in by_state.items():
        ACTORS_BY_STATE.set(count, {"state": st})
    KV_KEYS.set(sum(len(v) for v in ctl.kv.values()))
    OBJECT_DIRECTORY.set(len(ctl.object_dir))
    ha = getattr(ctl, "ha", None)
    if ha is not None:
        WAL_REPLICATION_LAG.set(ha.lag())
    reach = getattr(ctl, "reach", None)
    if reach is not None:
        PEER_UNREACHABLE_PAIRS.set(len(reach.unreachable_pairs()))
