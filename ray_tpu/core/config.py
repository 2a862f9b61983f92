"""Typed runtime flag registry.

Equivalent of the reference's RAY_CONFIG system
(/root/reference/src/ray/common/ray_config_def.h: 181 typed flags overridable
via env vars or an init-time JSON blob, propagated cluster-wide).  Here flags
are declared once, read from ``RAY_TPU_<NAME>`` environment variables, and the
resolved mapping is shipped to every node/worker at bootstrap so the whole
cluster sees one consistent configuration.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict


class _Flag:
    __slots__ = ("name", "type", "default", "doc")

    def __init__(self, name, type_, default, doc):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc


class Config:
    """Registry of typed flags with env-var and JSON overrides."""

    def __init__(self):
        self._flags: Dict[str, _Flag] = {}
        self._values: Dict[str, Any] = {}

    def define(self, name: str, type_, default, doc: str = ""):
        self._flags[name] = _Flag(name, type_, default, doc)
        env = os.environ.get(f"RAY_TPU_{name.upper()}")
        if env is not None:
            self._values[name] = self._parse(type_, env)
        else:
            self._values[name] = default

    @staticmethod
    def _parse(type_, text: str):
        if type_ is bool:
            return text.lower() in ("1", "true", "yes", "on")
        if type_ in (dict, list):
            return json.loads(text)
        return type_(text)

    def update(self, overrides: Dict[str, Any], export_env: bool = True):
        """Apply a JSON-style override dict (e.g. ``init(system_config=...)``).

        Overrides are also exported as ``RAY_TPU_<NAME>`` env vars so every
        process this one SPAWNS (controller, nodelets, workers) inherits
        them — the same-host half of the reference's cluster-wide config
        propagation (GetSystemConfig RPC, node_manager.proto:408)."""
        for k, v in overrides.items():
            if k not in self._flags:
                raise KeyError(f"Unknown config flag: {k}")
            f = self._flags[k]
            self._values[k] = self._parse(f.type, v) if isinstance(v, str) and f.type is not str else v
            if export_env:
                if isinstance(v, bool):
                    text = "1" if v else "0"
                elif isinstance(v, (dict, list)):
                    text = json.dumps(v)
                else:
                    text = str(v)
                os.environ[f"RAY_TPU_{k.upper()}"] = text

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._values)

    def load_snapshot(self, snap: Dict[str, Any]):
        self._values.update(snap)

    def __getattr__(self, name):
        try:
            return self.__dict__["_values"][name]
        except KeyError:
            raise AttributeError(name) from None

    def doc(self) -> str:
        lines = []
        for f in sorted(self._flags.values(), key=lambda f: f.name):
            lines.append(f"{f.name} ({f.type.__name__}, default={f.default!r}): {f.doc}")
        return "\n".join(lines)


GlobalConfig = Config()
_d = GlobalConfig.define

# --- core runtime -----------------------------------------------------------
_d("object_store_memory_mb", int, 2048, "Per-node shared-memory object store size.")
_d("max_direct_call_object_size", int, 100 * 1024,
   "Task returns at or below this many bytes ride the RPC reply into the "
   "caller's in-process memory store instead of the shared-memory store "
   "(reference: ray_config_def.h max_direct_call_object_size=100KiB).")
_d("object_transfer_chunk_bytes", int, 4 * 1024 * 1024,
   "Chunk size for node-to-node object push (reference: object_manager.proto).")
_d("worker_pool_initial_size", int, 2, "Workers prestarted per node.")
_d("worker_pool_max_size", int, 16,
   "Hard cap on TASK-serving workers per node (import-storm guard).  "
   "Workers dedicated to actors are counted separately under "
   "actor_workers_max: they never return to the pool, so counting them "
   "here would deadlock actor creation once the cap filled.")
_d("actor_workers_max", int, 4096,
   "Hard cap on actor-dedicated workers per node (reference analogue: "
   "unbounded actor workers; bounded here as an OS-process backstop).")
_d("worker_shutdown_grace_s", float, 2.0,
   "Seconds a stopping nodelet waits for SIGTERMed workers before "
   "SIGKILL.")
_d("worker_fork_server", bool, True,
   "Fork workers from a pre-warmed zygote process (~10ms) instead of "
   "exec'ing a fresh interpreter (~250ms import tax).  Falls back to "
   "exec automatically if the zygote dies.")
_d("actor_spawn_parallelism", int, 4,
   "Max worker processes concurrently forked for a burst of actor "
   "creations (Python import cost serializes on small hosts).")
_d("worker_lease_idle_seconds", float, 0.2,
   "Grace period a drained lease is held awaiting new same-key tasks before "
   "the worker (and its resources) return to the pool.  Short on purpose: "
   "the lease pins scheduler resources; warm reuse across bursts comes from "
   "the nodelet's idle worker pool, not from held leases.")
_d("heartbeat_interval_s", float, 0.5, "Nodelet -> controller resource report period.")
_d("node_death_timeout_s", float, 5.0,
   "Heartbeat silence after which the controller acts on a node: if "
   "probing peers still reach it the node becomes SUSPECT (quarantined, "
   "nothing killed), else it is declared dead.  This is the "
   "controller's heartbeat_timeout_s default (it was hardcoded at "
   "construction before the partition-tolerance layer).")
_d("suspect_grace_s", float, 15.0,
   "How long a SUSPECT node (controller link down, peers still reach "
   "it) may stay quarantined before it is declared dead anyway.  A "
   "link that heals inside this budget rejoins the node with its "
   "actors and objects untouched.")
_d("peer_probe_interval_s", float, 0.5,
   "Period of each nodelet's peer-reachability probe round (RPC port + "
   "object-transfer port of a few rotating peers); results piggyback "
   "on the next heartbeat and feed the controller's connectivity "
   "matrix.")
_d("peer_probe_fanout", int, 2,
   "Peers probed per probe round (rotating over the membership, so "
   "every pair is sampled within a few rounds).")
_d("peer_probe_timeout_s", float, 1.0,
   "Per-peer probe timeout; a probe that cannot complete inside this "
   "reports the peer unreachable for this round.")
_d("peer_reach_fresh_s", float, 2.5,
   "Freshness window of connectivity-matrix entries: a reachability "
   "report older than this no longer counts as evidence (suspect "
   "decisions and scheduling avoidance both read the matrix).")
_d("object_fetch_attempts", int, 3,
   "Bounded full-jitter retry attempts per source in the cross-node "
   "object fetch ladder (retry -> alternate directory copy -> "
   "controller-mediated relay -> lineage reconstruction).")
_d("task_retry_delay_s", float, 0.2, "Delay before resubmitting a failed task.")
_d("default_max_retries", int, 3, "Default retries for idempotent tasks.")
_d("actor_restart_delay_s", float, 0.2, "Delay before restarting a dead actor.")
_d("scheduler_spread_threshold", float, 0.5,
   "Hybrid policy: below this critical-resource utilization nodes score equal "
   "(pack); above it, weighted by utilization (spread). Mirrors the reference "
   "hybrid_scheduling_policy.h rationale.")
_d("scheduler_top_k_fraction", float, 0.2,
   "Randomize among this fraction of best-scoring nodes to avoid herding.")
_d("lease_request_timeout_s", float, 30.0, "Timeout for a worker lease grant.")
_d("actor_creation_timeout_s", float, 300.0,
   "How long method calls wait for a PENDING/RESTARTING actor to come up.")
_d("rpc_connect_retries", int, 60,
   "TCP connect retries at bootstrap/reconnect (capped exponential "
   "backoff with full jitter between attempts).")
_d("rpc_connect_backoff_cap_s", float, 0.5,
   "Cap for the full-jitter exponential backoff between TCP connect "
   "retries (base is the call's retry_delay, default 20ms).  Jitter "
   "keeps a restarted controller from eating a reconnect thundering-"
   "herd from every nodelet and driver at once.")
_d("pull_retry_interval_s", float, 0.5, "Retry period for remote object pulls.")
_d("usage_stats_enabled", bool, False,
   "Write a local JSON usage report under the session dir at shutdown "
   "(never leaves the machine; reference: _private/usage/usage_lib.py).")
_d("memory_monitor_interval_s", float, 1.0,
   "Node memory-pressure check period; 0 disables the monitor "
   "(reference: memory_monitor_refresh_ms).")
_d("memory_usage_threshold", float, 0.95,
   "Fraction of system memory above which the nodelet OOM-kills a worker "
   "(reference: memory_usage_threshold, worker_killing_policy.cc).")
_d("task_pipeline_depth", int, 8,
   "Max push_task RPCs in flight per leased worker; the worker still "
   "executes serially (one executor thread) so this only hides the "
   "submission round trip (reference: direct task transport pipelining).")
_d("task_pipeline_fast_ms", float, 10.0,
   "Pipeline a lease past depth 1 only when its completion-latency EWMA "
   "is under this; deep windows on slow tasks would serialize work that "
   "other leased workers could run in parallel.")
_d("max_pending_lease_requests", int, 10,
   "Free (not-yet-executing) lease loops per scheduling key — bounds the "
   "lease-request pipeline like the reference's "
   "max_pending_lease_requests_per_scheduling_category.")
_d("max_concurrent_pulls", int, 4,
   "Concurrent inbound object transfers per node — bounds store churn "
   "under memory pressure (reference: pull_manager.cc:228 prioritizes "
   "pulls against available memory).")
_d("inline_small_args_bytes", int, 64 * 1024,
   "Task args at or below this size are inlined into the task spec.")
_d("spill_storage_uri", str, "",
   "External spill storage: '' = session spill dir (filesystem); "
   "file:///path = explicit filesystem root; any other scheme (s3://, "
   "gs://) = smart_open-backed bucket shared by all hosts (reference: "
   "external_storage.py pluggable backends).")
_d("spill_threshold_frac", float, 0.80,
   "Store usage fraction above which the nodelet proactively spills "
   "pinned primary copies to external storage (reference: raylet "
   "LocalObjectManager spilling under memory pressure).")
_d("spill_low_water_frac", float, 0.60,
   "Proactive spilling stops once store usage drops below this fraction.")
_d("spill_min_object_bytes", int, 32 * 1024,
   "Primary copies smaller than this are never proactively spilled "
   "(reference: min_spilling_size batches small objects instead).")
_d("dashboard_agent", bool, True,
   "Launch a per-node dashboard agent process next to each nodelet "
   "(reference: dashboard/agent.py spawned by the raylet) serving OS "
   "stats + logs off the scheduler's critical path.  Agent death never "
   "affects the nodelet; the head falls back to nodelet scraping.")
_d("spill_check_interval_s", float, 0.5,
   "Nodelet store-pressure check period; 0 disables proactive spilling.")
_d("spill_backpressure_retries", int, 8,
   "Backpressure budget when a capacity-pressure spill hits a disk "
   "fault (ENOSPC/EIO): the put retries the store write this many "
   "times (the store may drain between attempts) before surfacing the "
   "typed retriable StorageDegradedError — never a task failure.")
_d("spill_backpressure_delay_s", float, 0.25,
   "Base delay between spill-backpressure retries (full jitter).")
_d("disk_monitor_interval_s", float, 1.0,
   "Nodelet disk-health check period (statvfs on the spill root, off "
   "the event loop); 0 disables the monitor.  State rides heartbeats "
   "into state.nodes() / ray-tpu status.")
_d("disk_low_water_frac", float, 0.85,
   "Disk usage fraction above which the node is flagged LOW: it stops "
   "being picked as a lease spill-target by peers (soft filter).")
_d("disk_red_frac", float, 0.95,
   "Disk usage fraction above which the node is RED: proactive spill "
   "stops (spilling would trade memory pressure for certain ENOSPC) "
   "and the controller fires the disk_pressure flight-recorder "
   "trigger.")
_d("log_to_driver", bool, True, "Forward worker stdout/stderr lines to the driver.")
_d("metrics_report_interval_s", float, 2.0, "Worker metric push period.")
_d("lineage_cache_size", int, 100000,
   "Task specs retained per driver for lineage reconstruction.")
_d("max_reconstruction_depth", int, 20,
   "Maximum recursion depth when reconstructing a chain of lost objects "
   "(reference: object_recovery_manager.h recursive recovery); "
   "exceeding it raises the typed ReconstructionDepthError carrying "
   "the oid lineage chain.")
_d("reconstruction_max_inflight", int, 8,
   "Concurrent lineage reconstruction re-executions per owner process "
   "(one driver owns its lineage, so for the common single-driver "
   "cluster this is the cluster-wide cap).  Excess _reconstruct calls "
   "wait for a slot; duplicates for the SAME object always dedupe onto "
   "one in-flight future regardless of this cap — together they keep "
   "one lost node from stampeding the scheduler with a re-execution "
   "storm.")

# --- blast-radius containment (crash ledger / quarantine) -------------------
_d("poison_task_threshold", int, 3,
   "Poison-shaped worker deaths (SIGSEGV family, oom_kill, clean "
   "nonzero exit) for ONE task signature within poison_window_s that "
   "quarantine the signature: further executions fail fast with the "
   "typed PoisonTaskError (evidence trail attached) instead of burning "
   "more workers.  0 disables task quarantine.")
_d("poison_window_s", float, 60.0,
   "Sliding window of the controller's crash ledger: only worker kills "
   "within this window count toward poison_task_threshold, so a task "
   "that crashes once a day never accumulates into a quarantine.")
_d("poison_quarantine_ttl_s", float, 300.0,
   "Seconds a poison quarantine (task signature or crash-looped actor) "
   "stands before it auto-expires and executions are allowed again; "
   "`ray-tpu quarantine clear` lifts it early.")
_d("actor_restart_backoff_base_s", float, 0.2,
   "Base of the full-jitter exponential backoff between actor restarts "
   "(attempt n waits uniform(0, min(cap, base*2^n)) measured over "
   "restarts inside actor_restart_window_s) — a crash-looping "
   "constructor no longer respawns workers back-to-back.")
_d("actor_restart_backoff_cap_s", float, 30.0,
   "Cap of the actor restart backoff envelope.")
_d("actor_restart_window_s", float, 600.0,
   "Rolling window of actor restart accounting: the max_restarts "
   "budget applies to restarts WITHIN this window (a long-lived actor "
   "crashing once a day keeps a full budget), and exhausting it on "
   "poison-shaped deaths parks the actor QUARANTINED instead of DEAD.")

# --- robustness / chaos -----------------------------------------------------
_d("chaos_plan", str, "",
   "JSON fault-injection plan (list of rules) armed at process start; "
   "'' disables the chaos layer entirely (zero-cost None check on hot "
   "paths).  Rule schema: util/fault_injection.py.  Runtime apply: "
   "`ray-tpu chaos apply plan.json` (controller KV + pubsub fan-out).")
_d("mp_pool_default_timeout_s", float, 600.0,
   "Default result timeout for util.multiprocessing Pool gets; raises "
   "the typed GetTimeoutError instead of hanging a pool on a result "
   "that will never arrive.")
_d("drain_timeout_s", float, 30.0,
   "Default deadline for a graceful node drain (lease stop, object "
   "evacuation, actor migration, in-flight task wait).  On overrun the "
   "controller falls back to the hard-death path — lineage/restart "
   "recovery is the safety net, not the plan.")
_d("drain_poll_interval_s", float, 0.2,
   "How often the drain orchestrator polls the draining nodelet for "
   "in-flight work while waiting for it to quiesce.")
_d("maintenance_poll_interval_s", float, 10.0,
   "Period of the autoscaler's maintenance-notice watcher "
   "(tpu_pod_provider.MaintenanceWatcher) between notice polls.")

# --- overload protection (core/overload.py, rpc lanes) ----------------------
_d("rpc_bulk_inflight", int, 64,
   "Per-connection cap on concurrently RUNNING bulk-lane dispatches "
   "(kv_put blobs, telemetry pushes); liveness/control dispatches are "
   "unbounded.  Excess bulk frames wait in the lane queue, where the "
   "overload watermarks can see (and shed) them.")
_d("kv_inline_max_bytes", int, 256 * 1024,
   "KV values above this size are diverted to the object-store path by "
   "writers (a small ref marker is stored in KV instead); readers "
   "follow the ref transparently.  Keeps function-table blobs and "
   "other large payloads off the controller's memory/WAL entirely.")
_d("flow_credit_window", int, 4096,
   "Submission credits granted per credit_request round under a NORMAL "
   "controller (soft overload grants a quarter window, brownout grants "
   "zero — clients buffer locally until recovery).")
_d("overload_soft_rss_mb", int, 0,
   "Controller-process RSS (MB) soft watermark: above it the overload "
   "state machine enters 'soft' (credits shrink, optional work slows). "
   "0 disables the RSS watermarks (queued-bytes watermarks still "
   "apply).")
_d("overload_hard_rss_mb", int, 0,
   "Controller-process RSS (MB) hard watermark: above it the state "
   "machine enters 'brownout' — bulk ops are shed with the typed "
   "retriable pushback and optional work stops.  0 disables.")
_d("overload_queued_soft_bytes", int, 64 * 1024 * 1024,
   "Bytes queued across this process's RPC lanes that trip the 'soft' "
   "overload state.  0 disables the queued-bytes watermarks.")
_d("overload_queued_hard_bytes", int, 256 * 1024 * 1024,
   "Queued-bytes hard watermark: 'brownout' — shed bulk, stop optional "
   "work, fire the `overload` flight-recorder trigger.  0 disables.")
_d("overload_eval_interval_s", float, 0.25,
   "Period of the controller's overload watermark evaluator (RSS read "
   "+ lane-table scan; recovery re-arms automatically on the same "
   "tick).")
_d("overload_shed_retry_after_s", float, 0.5,
   "Retry-After hint carried by shed replies; clients sleep roughly "
   "this (full jitter) before replaying a shed op.")
_d("pubsub_max_buffer", int, 4096,
   "Per-subscriber pubsub event-buffer bound.  Overflow drops the "
   "OLDEST event (counted in ray_tpu_pubsub_dropped_total) and flags "
   "the subscriber for snapshot resync instead of growing without "
   "bound under a slow consumer.")

# --- controller high availability (core/ha.py) ------------------------------
_d("ha_lease_timeout_s", float, 2.0,
   "A hot-standby controller promotes itself once it has heard nothing "
   "from the leader (lease renewals, replication traffic) for this "
   "long.  The client-visible control-plane outage on leader death is "
   "roughly this plus one reconnect round.")
_d("ha_lease_interval_s", float, 0.5,
   "Leader -> standby lease renewal period (piggybacks on replication "
   "traffic when there is any).")
_d("ha_repl_mode", str, "sync",
   "'sync': a controller mutation is acked to its caller only once the "
   "standby has durably appended it (sync_floor); degrades to bounded-"
   "lag async when the standby stalls past ha_sync_timeout_s.  "
   "'async': never gate replies on replication.")
_d("ha_sync_timeout_s", float, 1.0,
   "How long a sync-mode mutation reply waits for the standby's "
   "replication ack before the leader degrades to async mode (leader "
   "writes must never stall behind a sick standby).")
_d("ha_max_lag_records", int, 4096,
   "Replication records buffered for a lagging standby; past this the "
   "leader drops the incremental stream and resyncs the standby with a "
   "full snapshot.")
_d("ha_client_failover_timeout_s", float, 30.0,
   "Controller clients (drivers, serve routers, train executors) retry "
   "a failed controller call against the standby address list for up "
   "to this long before surfacing the error — in-flight ops replay "
   "transparently against the promoted leader inside this budget.")

# --- TPU / accelerator ------------------------------------------------------
_d("tpu_autodetect", bool, True, "Detect local TPU chips via JAX at node start.")
_d("tpu_detect_timeout_s", float, 120.0,
   "Time limit of the child process that opens the chip at node start; a "
   "probe that overruns it fails the node (core/accelerator.py).")
_d("tpu_chips_per_host_override", int, 0, "Force the advertised TPU chip count (0=auto).")
_d("tpu_topology_override", str, "", "Force the advertised slice topology, e.g. 'v5e-8'.")

# --- train ------------------------------------------------------------------
_d("train_default_checkpoint_keep", int, 2, "Checkpoints retained by CheckpointManager.")

# --- observability ----------------------------------------------------------
_d("task_spans_buffer_size", int, 5000,
   "Finished-task spans retained per nodelet for the cluster timeline.")
_d("trace_enabled", bool, True,
   "Record distributed task-lifecycle spans (submit/schedule/dequeue/"
   "fetch/exec/put) for the cluster timeline.")
_d("trace_buffer_size", int, 4096,
   "Chrome-trace lifecycle spans buffered per process: each span "
   "category keeps a quarter of it (the controller's copy has the same "
   "bound).")
_d("trace_flush_interval_s", float, 0.25,
   "Period of each process's span flush to the controller KV.")
_d("events_buffer_size", int, 1000,
   "Structured cluster events retained by the controller.")
_d("metrics_history_interval_s", float, 0.5,
   "Sampling period of the per-process metrics-history ring (controller "
   "and nodelets snapshot their own registries — counter deltas + "
   "gauges — on this cadence); 0 disables history sampling.")
_d("metrics_history_window", int, 240,
   "Samples retained in each process's metrics-history ring (bounded "
   "memory: window * interval is the look-back the autoscale loop and "
   "`ray-tpu top` can read — 2 minutes at the defaults).")
_d("flight_recorder_enabled", bool, True,
   "Capture an incident bundle (recent spans from every process, the "
   "metrics-history window, structured events, node snapshot) to "
   "flight_recorder_dir on SUSPECT transitions, controller failovers, "
   "drain deadline overruns, elastic repairs, and OOM kills.")
_d("flight_recorder_dir", str, "",
   "Directory incident bundles land in ('' = "
   "<tmpdir>/ray_tpu_incidents).  Each bundle is one subdirectory "
   "named <unix-ms>_<trigger> holding meta/spans/metrics/events/nodes "
   "JSON files.")
_d("flight_recorder_keep", int, 20,
   "Incident bundles retained; the oldest are pruned past this count.")
_d("flight_recorder_min_interval_s", float, 5.0,
   "Per-trigger rate limit between automatic captures (a flapping link "
   "must not turn the recorder into its own incident); manual "
   "`ray-tpu debug capture` bypasses it.")
_d("device_profile_peak_flops", float, 0.0,
   "Per-device peak FLOP/s for the profiler's MFU denominator; 0 = "
   "auto (TPU spec-sheet table by device kind, nominal fallback on "
   "CPU — the CPU ratio is indicative, not a hardware truth).")
_d("serve_compile_storm_threshold", int, 8,
   "Recompiles per replica within serve_compile_storm_window_s that "
   "fire the `compile_storm` flight-recorder trigger (a steady engine "
   "compiles O(1) programs total; one-per-request shapes blow past "
   "this in seconds).  0 disables storm detection.")
_d("serve_compile_storm_window_s", float, 30.0,
   "Sliding window of the compile-storm detector (nodelet-side, over "
   "the folded compile-ledger deltas).")
_d("serve_slo_ttft_p95_s", float, 0.0,
   "p95 TTFT bound: the nodelet's SLO evaluator fires the `slo_breach` "
   "flight-recorder trigger when the recent p95 of "
   "ray_tpu_serve_ttft_seconds exceeds this.  0 disables (default: "
   "tier-1 runs must not self-trigger).")
_d("serve_slo_itl_p95_s", float, 0.0,
   "p95 inter-token-latency bound for the `slo_breach` trigger "
   "(evaluated like serve_slo_ttft_p95_s).  0 disables.")
_d("serve_slo_min_samples", int, 20,
   "Requests (TTFT) / tokens (ITL) the SLO evaluator needs in its "
   "window before judging a p95 — a one-request blip is not a breach.")
_d("serve_tenant_label_max", int, 16,
   "Distinct tenant label values admitted into the serve TTFT/ITL "
   "histograms per nodelet; overflow tenants are bucketed as 'other' "
   "so an open tenant field cannot blow series cardinality.")
_d("metrics_lint_max_tags", int, 4,
   "`ray-tpu metrics lint` cardinality bound: a registered metric may "
   "declare at most this many label keys.")
_d("metrics_lint_max_series", int, 512,
   "`ray-tpu metrics lint` bound on live label-value combinations per "
   "metric (exposition-time check; a per-task or per-object label "
   "would blow this within minutes).")
_d("pubsub_coalesce_s", float, 0.01,
   "Controller publish loop batches events arriving within this window "
   "into one push per subscriber (reference: pubsub batched long-poll).")
_d("worker_register_timeout_s", float, 20.0,
   "A spawned worker must register within this long or the reap loop "
   "kills and replaces it.  Without the bound, ONE hung spawn (fork "
   "wedged in imports, exec stalled under load) counts as 'starting' "
   "forever and the spawn throttle never starts another worker — "
   "permanently wedging actor creation on that node.")
_d("actor_worker_startup_timeout_s", float, 30.0,
   "How long an actor start waits for a pooled worker to come up before "
   "failing the placement.")

# --- serve ------------------------------------------------------------------
_d("serve_default_max_concurrent_queries", int, 100,
   "Per-replica in-flight cap used by the router.")
_d("serve_http_host", str, "127.0.0.1", "HTTP proxy bind host.")
_d("serve_http_port", int, 8000, "HTTP proxy bind port.")
_d("serve_request_timeout_s", float, 60.0,
   "End-to-end timeout for one proxied HTTP request (replica execution "
   "included).")
_d("serve_stream_chunk_tokens", int, 16,
   "SSE decode streaming drains up to this many buffered tokens per "
   "`next_chunk` router round trip (continuous-batching engine lane) — "
   "transport amortizes over N tokens instead of one RPC per token.")
_d("serve_backoff_base_s", float, 0.01,
   "Base of the full-jitter exponential backoff the Serve router uses "
   "while every replica is saturated, and between replica-failure "
   "retry attempts in call_with_retry.")
_d("serve_backoff_cap_s", float, 0.2,
   "Cap of the Serve router/handle retry backoff.")
_d("serve_session_failover_attempts", int, 6,
   "Minimum resume attempts a failed decode stream makes (teacher-"
   "forced prefix prefill on a healthy replica) before the failure "
   "may surface to the client as an in-band SSE error.")
_d("serve_session_failover_timeout_s", float, 30.0,
   "Wall-clock budget for decode-stream resume retries: fast "
   "rejections (every replica still shedding while a replacement "
   "boots) keep retrying under backoff until this elapses, even after "
   "serve_session_failover_attempts tries.")
_d("serve_session_migration_timeout_s", float, 30.0,
   "How long the serve controller waits for live decode sessions to "
   "migrate off a draining replica before stopping it anyway (the "
   "proxy-side failover path then covers any stragglers).")
_d("serve_autoscale_interval_s", float, 1.0,
   "Cadence of the serve controller's autoscale loop (occupancy-trend "
   "policy over metrics history; serve/autoscaler.py).  Ticks ride "
   "router metric reports, snapshot polls, and the HTTP proxies' "
   "periodic nudge, throttled to this interval; <= 0 disables the "
   "loop (deployments keep their static replica counts).")
_d("serve_engine_metrics_interval_s", float, 0.5,
   "How often a replica's decode engine pushes occupancy/waiting/"
   "prefix-cache samples to its nodelet (gauges labeled by deployment "
   "and replica, so `state.metrics_history` serves per-deployment "
   "series to the autoscaler and `ray-tpu top`).")
_d("serve_replica_boot_ewma_alpha", float, 0.3,
   "EWMA weight of the newest observed replica boot time (start -> "
   "ALIVE).  The smoothed boot time becomes the Retry-After on typed "
   "503s shed while a scale-up is in flight, so clients re-arrive "
   "right as capacity lands instead of on the generic backoff floor.")
_d("serve_gang_ready_timeout_s", float, 300.0,
   "How long gang-replica bring-up may take (PG + N actors + "
   "jax.distributed rendezvous + model load) before the replica is "
   "declared failed.")
_d("serve_gang_stall_timeout_s", float, 600.0,
   "Gang follower stall window: with nothing executing and no sequence "
   "progress for this long, the member declares a leader fan-out gap.")
