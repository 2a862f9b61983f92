"""State observability API.

Capability mirror of the reference's state API (`ray list actors/tasks/...`,
`python/ray/experimental/state/api.py:112,729,1269`, aggregator
`dashboard/state_aggregator.py`) — reads cluster state from the controller.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .api import _ensure_initialized


def list_nodes() -> List[Dict[str, Any]]:
    """Node membership rows.  Each row carries ``state`` (ALIVE |
    SUSPECT | DRAINING | DEAD), a ``health`` dict (heartbeat age plus
    the heartbeat-timeout / suspect-grace / probe-fanout knobs in
    force), ``unreachable_peers`` when the node reported severed links,
    and, while a drain or suspect quarantine is in progress, its
    progress (``drain`` dict / ``suspect_for_s`` + ``peers_reaching``)."""
    return _ensure_initialized().controller.call("list_nodes")


def nodes() -> List[Dict[str, Any]]:
    """Alias of :func:`list_nodes` (reference naming: state.nodes)."""
    return list_nodes()


def list_actors() -> List[Dict[str, Any]]:
    return _ensure_initialized().controller.call("list_actors")


def actors() -> List[Dict[str, Any]]:
    """Alias of :func:`list_actors` (reference naming: state.actors).

    Rows carry restart/containment columns: ``num_restarts`` (lifetime
    restart count) and ``quarantined`` (True once the controller has
    crash-loop-quarantined the actor; callers get a typed
    ``ActorQuarantinedError`` instead of endless restarts).
    """
    return list_actors()


def quarantine_list() -> List[Dict[str, Any]]:
    """Poison-task / crash-loop quarantine records (evidence trails)."""
    return _ensure_initialized().controller.call("quarantine_list")


def list_placement_groups() -> List[Dict[str, Any]]:
    return _ensure_initialized().controller.call("list_placement_groups")


def list_jobs() -> List[Dict[str, Any]]:
    from . import jobs
    return jobs.list_jobs()


def summarize_actors() -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for a in list_actors():
        key = a.get("state", "UNKNOWN")
        counts[key] = counts.get(key, 0) + 1
    return counts


def summarize_nodes() -> Dict[str, Any]:
    ns = list_nodes()
    return {
        "total": len(ns),
        "alive": sum(1 for n in ns if n.get("alive")),
        "suspect": sum(1 for n in ns if n.get("state") == "SUSPECT"),
        "draining": sum(1 for n in ns if n.get("state") == "DRAINING"),
        "unreachable_pairs": sorted(
            (n["id"][:12], dst[:12]) for n in ns
            for dst in n.get("unreachable_peers", ())),
        "resources": {
            k: sum(n["total"].get(k, 0) for n in ns if n.get("alive"))
            for n in ns for k in n.get("total", {})
        } if ns else {},
    }


def cluster_summary() -> Dict[str, Any]:
    return {
        "nodes": summarize_nodes(),
        "actors": summarize_actors(),
        "placement_groups": len(list_placement_groups()),
        "tasks": summarize_tasks(),
    }


def list_controllers() -> List[Dict[str, Any]]:
    """One row per controller process this driver knows about (the
    leader plus its hot standbys — core/ha.py): role, epoch, and — for
    the leader — WAL replication mode/lag.  Dead or unreachable
    controllers are reported as such rather than omitted."""
    from .core import rpc as rpc_mod
    core = _ensure_initialized()
    eps = []
    try:
        eps = core.controller.endpoints()
    except Exception:
        pass
    if not eps:
        eps = rpc_mod.parse_endpoints(core.controller_addr)
    rows = []
    for host, port in eps:
        addr = f"{host}:{port}"
        try:
            conn = core.lt.run(rpc_mod.connect(host, port, retries=1))
            try:
                st = core.lt.run(conn.call("ha_status", {}, timeout=5))
            finally:
                core.lt.run(conn.close())
            rows.append({"addr": addr, **(st or {})})
        except Exception as e:
            rows.append({"addr": addr, "role": "unreachable",
                         "error": str(e)})
    return rows


def cluster_info() -> Dict[str, Any]:
    """Control-plane + membership overview: a row for EVERY controller
    (leader and standby, with epoch and replication lag) plus the node
    table — the `ray-tpu controller status` data source."""
    return {"controllers": list_controllers(), "nodes": list_nodes()}


# -------------------------------------------------- per-node deep state
def _node_call(addr: str, method: str, data: Optional[dict] = None,
               timeout: float = 10.0):
    """One RPC to a nodelet (the aggregator role of the reference's
    dashboard/state_aggregator.py querying per-node agents).  Connections
    are pooled on the core (dashboards poll every couple of seconds — no
    per-poll connect/teardown churn); a dead conn is dropped and redialed
    once."""
    from .core import rpc as rpc_mod
    core = _ensure_initialized()
    lock = core._state_conns_lock
    pool = core._state_conns
    host, port = addr.rsplit(":", 1)
    for attempt in (0, 1):
        with lock:
            conn = pool.get(addr)
        if conn is None or conn.closed:
            conn = core.lt.run(rpc_mod.connect(host, int(port), retries=3))
            with lock:
                stale = pool.get(addr)
                if stale is not None and stale is not conn \
                        and not stale.closed:
                    # lost a dial race: keep the winner, close ours
                    core.lt.run(conn.close())
                    conn = stale
                else:
                    pool[addr] = conn
        try:
            return core.lt.run(conn.call(method, data or {},
                                         timeout=timeout))
        except TimeoutError:
            # A slow reply proves nothing about the transport — the conn
            # is shared; closing it would kill other threads' in-flight
            # calls (and TimeoutError IS an OSError on py3.11+, so it
            # must be excluded from the broken-transport handling below).
            raise
        except (rpc_mod.RpcError, OSError):
            with lock:
                if pool.get(addr) is conn:
                    pool.pop(addr, None)
            try:
                core.lt.run(conn.close())  # drop the fd, not just the ref
            except Exception:
                pass
            if attempt:
                raise


def cluster_metrics_text() -> str:
    """Prometheus exposition aggregated cluster-wide: this process's
    registry + the controller's + every alive nodelet's (reference: the
    ~90-metric runtime battery of metric_defs.cc, exported per
    component; here one scrape endpoint serves the union)."""
    from . import metrics
    parts = [metrics.prometheus_text()]
    core = _ensure_initialized()
    try:
        parts.append(core.controller.call("metrics_text", timeout=10.0))
    except Exception:
        pass
    for n in list_nodes():
        if not n.get("alive"):
            continue
        try:
            parts.append(_node_call(n["addr"], "metrics_text"))
        except Exception:
            continue
    # de-duplicate HELP/TYPE headers repeated across process registries
    seen: set = set()
    out: List[str] = []
    for part in parts:
        for line in (part or "").splitlines():
            if line.startswith("#"):
                if line in seen:
                    continue
                seen.add(line)
            elif line in seen:
                continue   # identical sample from an earlier registry
            else:
                seen.add(line)
            out.append(line)
    return "\n".join(out) + "\n"


def metrics_history(name: Optional[str] = None,
                    last: Optional[int] = None,
                    deployment: Optional[str] = None,
                    kind: str = "counters") -> Dict[str, Any]:
    """Cluster-wide metrics history: each server process's bounded ring
    of fixed-interval samples (counter deltas + gauges;
    core/metrics_history.py), keyed by process label.  With ``name``,
    a ``series`` view extracts that one metric family per process —
    the signal source the serve autoscale loop and ``ray-tpu top``
    read.  ``deployment`` filters the series to samples carrying that
    ``deployment=`` label (serve engine occupancy/waiting pushes are
    labeled per deployment and replica), so per-deployment series come
    back without client-side regex over the merged rings; ``kind``
    picks "counters" or "gauges" (serve engine samples are gauges)."""
    from .core import metrics_history as mh
    core = _ensure_initialized()
    procs: Dict[str, Any] = {}
    try:
        procs["controller"] = core.controller.call(
            "metrics_history", {"last": last}, timeout=10.0)
    except Exception:
        pass
    for n in list_nodes():
        if not n.get("alive"):
            continue
        try:
            r = _node_call(n["addr"], "metrics_history", {"last": last})
            procs[r.get("label") or f"nodelet@{n['id'][:8]}"] = r
        except Exception:
            continue
    out: Dict[str, Any] = {
        "interval_s": next((p.get("interval_s") for p in procs.values()),
                           None),
        "processes": procs,
    }
    if name:
        labels = {"deployment": deployment} if deployment else None
        out["series"] = {
            label: mh.series(p.get("samples", []), name, kind=kind,
                             labels=labels)
            for label, p in procs.items()}
    return out


def _prom_samples(text: str) -> Dict[str, list]:
    """Parse Prometheus exposition text into name -> [(tags, value)].
    Minimal by design: our own exposition format (metrics.py) — one
    sample per line, ``label="value"`` pairs, no escapes."""
    import re
    line_re = re.compile(
        r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{([^}]*)\})?\s+(\S+)$")
    tag_re = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="([^"]*)"')
    out: Dict[str, list] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = line_re.match(line)
        if m is None:
            continue
        try:
            val = float(m.group(3))
        except ValueError:
            continue
        tags = dict(tag_re.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((tags, val))
    return out


#: serve_breakdown's named phases, in pipeline order
SERVE_PHASES = ("cold_start", "queue", "admission", "prefill",
                "decode_dispatch", "stream_drain")


def serve_breakdown() -> Dict[str, Any]:
    """Per-deployment serve time attribution: where does a served
    millisecond-per-token actually go?  Reads the cluster scrape
    (`cluster_metrics_text`) and reduces the data-plane flight
    instruments — engine phase counters, proxy TTFT/ITL histograms,
    token counters, per-program MFU — to one table per deployment:

    * ``phases_s`` / ``ms_per_token``: cold_start (lazy replica
      construction — model init and first compiles land inside the
      first request's TTFT), queue (enqueue -> first prefill chunk),
      admission (first token -> decode slot), prefill (chunk program
      wall), decode_dispatch (decode/draft/verify/insert program
      wall), stream_drain (client-observed inter-token time not
      explained by decode dispatch: queue depth + RPC + SSE);
    * ``coverage``: attributed seconds over client-measured seconds
      (TTFT sum + ITL sum) — the honesty metric.  Healthy is >= 0.9:
      the engine-side marks explain at least 90% of what clients
      actually waited; a gap means an uninstrumented phase;
    * ``engine_s``: the engine's other cumulative seconds, outside the
      coverage sum because they overlap the phases above: the engine
      thread's own phases (schedule, admit_host, dispatch, readback,
      publish) and the per-request sums first_token and prefill_tail;
      and what the host's turn waited for: lock_wait (the engine
      thread's waits for the engine's lock), schedule_cpu (the
      thread's own CPU seconds of schedule: wall less CPU is time it
      stood runnable and did not run), long_read (step reads of 250 ms
      or more), gc and late_wakeup (the replica process's garbage
      collections and its watch thread's late wake-ups,
      `tracing.host_totals`);
    * ``mfu``: per-program model-FLOPs-utilization gauges.

    Surfaces: `ray-tpu top` breakdown panel, ``/api/serve/breakdown``."""
    samples = _prom_samples(cluster_metrics_text())
    per: Dict[str, Dict[str, Any]] = {}

    def acc(dep: str) -> Dict[str, Any]:
        return per.setdefault(dep, {
            "phases_s": dict.fromkeys(SERVE_PHASES, 0.0),
            "tokens": 0.0, "requests": 0.0, "engine_s": {},
            "ttft_s": 0.0, "itl_s": 0.0, "mfu": {}})

    for tags, v in samples.get("ray_tpu_serve_phase_seconds_total", ()):
        a = acc(tags.get("deployment", "?"))
        ph = tags.get("phase", "")
        if ph in a["phases_s"]:
            a["phases_s"][ph] += v
        else:
            # the engine thread's own phases and the per-request sums
            # that overlap the pipeline phases: shown, not attributed
            a["engine_s"][ph] = a["engine_s"].get(ph, 0.0) + v
    for tags, v in samples.get("ray_tpu_serve_tokens_total", ()):
        acc(tags.get("deployment", "?"))["tokens"] += v
    for name, key in (("ray_tpu_serve_ttft_seconds_sum", "ttft_s"),
                      ("ray_tpu_serve_itl_seconds_sum", "itl_s")):
        for tags, v in samples.get(name, ()):
            acc(tags.get("deployment", "?"))[key] += v
    for tags, v in samples.get("ray_tpu_serve_ttft_seconds_count", ()):
        acc(tags.get("deployment", "?"))["requests"] += v
    for tags, v in samples.get("ray_tpu_mfu_ratio", ()):
        acc(tags.get("deployment", "?"))["mfu"][
            tags.get("program", "?")] = v

    deployments: Dict[str, Any] = {}
    for dep, a in sorted(per.items()):
        ph = a["phases_s"]
        # inter-token time clients saw but decode dispatch does not
        # explain: slot queueing, chunk RPC, SSE write — the drain tail
        ph["stream_drain"] = max(0.0, a["itl_s"]
                                 - ph["decode_dispatch"])
        measured = a["ttft_s"] + a["itl_s"]
        attributed = sum(ph.values())
        tokens = a["tokens"]
        deployments[dep] = {
            "tokens": int(tokens),
            "requests": int(a["requests"]),
            "measured_s": round(measured, 6),
            "attributed_s": round(attributed, 6),
            "coverage": (round(attributed / measured, 4)
                         if measured > 0 else None),
            "phases_s": {k: round(v, 6) for k, v in ph.items()},
            "ms_per_token": {
                k: (round(v / tokens * 1e3, 4) if tokens else None)
                for k, v in ph.items()},
            "engine_s": {k: round(v, 6)
                         for k, v in sorted(a["engine_s"].items())},
            "mfu": {k: round(v, 4) for k, v in sorted(a["mfu"].items())},
        }
    return {"phases": list(SERVE_PHASES), "deployments": deployments}


def rpc_attribution() -> Dict[str, Any]:
    """Per-RPC control-plane attribution: for the controller and every
    alive nodelet, the per-op dispatch table (count, errors, total
    handler seconds, avg/p50/p99/max latency, payload bytes — sorted by
    total time), plus WAL append/fsync timing and asyncio loop lag.
    The 'where does control-plane time go' view."""
    core = _ensure_initialized()
    out: Dict[str, Any] = {"nodes": {}}
    try:
        out["controller"] = core.controller.call("rpc_attribution", {},
                                                 timeout=10.0)
    except Exception as e:
        out["controller"] = {"error": str(e)}
    for n in list_nodes():
        if not n.get("alive"):
            continue
        try:
            out["nodes"][n["id"][:12]] = _node_call(n["addr"],
                                                    "rpc_attribution")
        except Exception:
            continue
    return out


def top_rpc_ops(k: int = 3) -> List[Dict[str, Any]]:
    """The controller's top-``k`` RPC handlers by total handler time."""
    attr = rpc_attribution().get("controller") or {}
    return list(attr.get("ops") or [])[:k]


def debug_capture(reason: str = "") -> Dict[str, Any]:
    """Capture a flight-recorder bundle NOW (manual grab; bypasses the
    per-trigger rate limit).  Returns {"ok", "path"}."""
    return _ensure_initialized().controller.call(
        "debug_capture", {"trigger": "manual", "reason": reason},
        timeout=30.0)


def node_stats(node_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Deep per-node stats: worker tables, running tasks, store usage
    (reference: dashboard reporter/agent per-node stats)."""
    out = []
    for n in list_nodes():
        if not n.get("alive"):
            continue
        if node_id is not None and n["id"] != node_id:
            continue
        try:
            out.append(_node_call(n["addr"], "node_stats"))
        except Exception as e:
            out.append({"node_id": n["id"], "error": str(e)})
    return out


# ------------------------------------------------ dashboard agents
def _agent_fresh(info: Dict[str, Any]) -> bool:
    """A registration is live if its heartbeat is recent — a SIGKILLed
    agent never deregisters, so the 'ts' it refreshes every beat is the
    liveness signal (3 missed beats + slack = dead)."""
    import time as _time
    hb = float(info.get("heartbeat_s", 2.0))
    return _time.time() - float(info.get("ts", 0)) < 3.0 * hb + 2.0


def list_agents(include_stale: bool = False) -> Dict[str, Dict[str, Any]]:
    """Per-node dashboard agents registered in controller KV
    (reference: the head's per-node agent table, dashboard/head.py
    node-agent discovery through the GCS)."""
    import json as _json

    from .dashboard.agent import AGENT_KV_NS, AGENT_KV_PREFIX
    core = _ensure_initialized()
    keys = core.controller.call("kv_keys", {"ns": AGENT_KV_NS,
                                            "prefix": AGENT_KV_PREFIX})
    out = {}
    for key in keys:
        raw = core.controller.call("kv_get", {"ns": AGENT_KV_NS,
                                              "key": key})
        if raw is None:
            continue
        info = _json.loads(raw)
        if include_stale or _agent_fresh(info):
            out[key[len(AGENT_KV_PREFIX):]] = info
    return out


def agent_stats(node_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """OS-level node stats served by the per-node agents, falling back
    to the nodelet scrape path for nodes whose agent is dead or absent
    — logs/stats stay served either way (the reference's head degrades
    the same direction when an agent is unreachable)."""
    agents = list_agents()
    out = []
    for n in list_nodes():
        if not n.get("alive"):
            continue
        if node_id is not None and n["id"] != node_id:
            continue
        agent = agents.get(n["id"])
        if agent is not None:
            try:
                out.append(_node_call(agent["addr"], "agent_stats",
                                      timeout=5.0))
                continue
            except Exception:
                pass    # dead agent: fall through to the nodelet
        try:
            stats = _node_call(n["addr"], "node_stats")
            stats["agent"] = "fallback:nodelet"
            out.append(stats)
        except Exception as e:
            out.append({"node_id": n["id"], "error": str(e)})
    return out


# ---------------------------------------------------- cluster timeline
def _trace_span_events() -> List[Dict[str, Any]]:
    """Every process's flushed lifecycle spans, as the controller holds
    them (one bounded ring per process, retained after the process
    exits).  The driver's own batch is flushed synchronously first so a
    dump taken right after a burst is complete."""
    from .util import tracing
    core = _ensure_initialized()
    batch = tracing.flush_batch()
    if batch is not None:
        try:
            core.controller.call("trace_append", batch)
        except Exception:
            tracing.mark_dirty()
    return list(core.controller.call("trace_dump", {}))


def _node_task_span_events() -> List[Dict[str, Any]]:
    """Legacy per-node finished-task spans (nodelet ``task_spans``
    buffers) as Chrome events — still the only source for tasks whose
    worker died mid-flight (``interrupted`` spans)."""
    events: List[Dict[str, Any]] = []
    try:
        for n in list_nodes():
            if not n.get("alive"):
                continue
            for sp in _node_call(n["addr"], "task_spans"):
                events.append({
                    "name": sp["name"], "cat": "task", "ph": "X",
                    "ts": sp["start"] * 1e6,
                    "dur": max(0.0, (sp["end"] - sp["start"])) * 1e6,
                    "pid": "node:" + n["id"][:8],
                    "tid": "worker:" + sp["worker_id"][:8],
                    "args": {"task_id": sp.get("task_id", ""),
                             "interrupted": sp.get("interrupted", False)},
                })
    except Exception:
        pass
    return events


def _clock_offsets() -> Dict[str, float]:
    """node-id-prefix (8 hex) → estimated wall-clock offset in seconds
    (node − controller), from the heartbeat RTT-midpoint estimates the
    controller folds into its node rows."""
    try:
        return {n["id"][:8]: float(n.get("clock_offset_s") or 0.0)
                for n in list_nodes()}
    except Exception:
        return {}


def apply_clock_offsets(events: List[Dict[str, Any]],
                        offsets: Dict[str, float]) -> None:
    """Shift each span onto the CONTROLLER clock in place: a span's pid
    names its process ("kind@<node8>" lifecycle spans, "node:<node8>"
    legacy task spans); subtracting that node's offset re-aligns
    cross-host spans into causal order (a follower whose clock runs
    100ms ahead otherwise renders its exec span before the submit that
    caused it)."""
    if not offsets:
        return
    for e in events:
        pid = str(e.get("pid") or "")
        node8 = ""
        if "@" in pid:
            node8 = pid.rsplit("@", 1)[1][:8]
        elif pid.startswith("node:"):
            node8 = pid[5:][:8]
        off = offsets.get(node8)
        if off:
            e["ts"] = e.get("ts", 0) - off * 1e6


def timeline() -> Dict[str, Any]:
    """Cluster-wide task timeline as a Chrome-trace dict (reference:
    `ray timeline` / chrome_tracing_dump, _private/state.py:414).

    Merges every process's lifecycle spans (submit → schedule → dequeue
    → fetch → exec → put, plus serve/train workload spans) with the
    legacy per-node finished-task spans, ordered by timestamp with
    per-process pid/tid attribution, re-aligned onto the controller
    clock via the heartbeat-estimated per-host offsets.  The returned
    dict serializes directly to a file loadable in
    https://ui.perfetto.dev or chrome://tracing."""
    from .util import tracing
    events = _trace_span_events() + _node_task_span_events()
    apply_clock_offsets(events, _clock_offsets())
    events.sort(key=lambda e: e.get("ts", 0))
    return tracing.chrome_trace(events)


def list_tasks() -> List[Dict[str, Any]]:
    """RUNNING tasks cluster-wide with node attribution (reference:
    `ray list tasks`, experimental/state/api.py)."""
    tasks = []
    for ns in node_stats():
        for t in ns.get("running_tasks", []):
            tasks.append({**t, "node_id": ns.get("node_id")})
    return tasks


def summarize_tasks() -> Dict[str, Any]:
    """Finished-task counts by function + currently running count
    (reference: `ray summary tasks`, state/api.py:1269)."""
    counts: Dict[str, int] = {}
    running = 0
    for ns in node_stats():
        running += len(ns.get("running_tasks", []))
        for name, n in ns.get("task_counts", {}).items():
            counts[name] = counts.get(name, 0) + n
    return {"finished_by_func": counts, "running": running}


def list_events(severity: Optional[str] = None,
                limit: int = 200) -> List[Dict[str, Any]]:
    """Structured cluster events, newest last (reference: the event
    framework, src/ray/util/event.h + dashboard/modules/event)."""
    return _ensure_initialized().controller.call(
        "list_events", {"severity": severity, "limit": limit})


def report_event(message: str, *, severity: str = "INFO",
                 source: str = "user", **meta) -> None:
    """Emit a user event into the cluster event log."""
    _ensure_initialized().controller.call(
        "report_event", {"severity": severity, "source": source,
                         "message": message, "meta": meta})


def list_objects() -> List[Dict[str, Any]]:
    """Cluster object table: size, locations, borrow holders, deferred
    frees (reference: `ray list objects`)."""
    return _ensure_initialized().controller.call("list_objects", {})


def memory_summary() -> Dict[str, Any]:
    """`ray memory`-style dump: object table + outstanding borrows +
    per-node store usage (reference: python/ray/_private/internal_api.py
    memory_summary)."""
    core = _ensure_initialized()
    stores = {}
    for ns in node_stats():
        if "store" in ns:
            stores[ns["node_id"]] = {**ns["store"],
                                     "primary_pins": ns.get("primary_pins")}
    return {
        "objects": list_objects(),
        "refs": core.controller.call("ref_counts", {}),
        "stores": stores,
    }


def _agent_for_addr(addr: str) -> Optional[str]:
    """Agent address for a nodelet address, if a live agent registered.
    ONE kv_get for the addressed node — not a full agent-table scan per
    log poll."""
    import json as _json

    from .dashboard.agent import AGENT_KV_NS, AGENT_KV_PREFIX
    try:
        node_id = next((n["id"] for n in list_nodes()
                        if n["addr"] == addr), None)
        if node_id is None:
            return None
        raw = _ensure_initialized().controller.call(
            "kv_get", {"ns": AGENT_KV_NS,
                       "key": AGENT_KV_PREFIX + node_id})
        if raw is None:
            return None
        info = _json.loads(raw)
        return info["addr"] if _agent_fresh(info) else None
    except Exception:
        return None


def list_logs(node_addr: Optional[str] = None) -> List[str]:
    """Per-process log files on a node's session dir (reference:
    LogMonitor's file set, `ray logs`) — served by the node's dashboard
    agent when one is alive, by the nodelet otherwise."""
    nodes = list_nodes()
    addr = node_addr or next(
        (n["addr"] for n in nodes if n.get("alive")), None)
    if addr is None:
        return []
    agent_addr = _agent_for_addr(addr)
    if agent_addr is not None:
        try:
            return _node_call(agent_addr, "list_logs",
                              timeout=5.0).get("files", [])
        except Exception:
            pass
    return _node_call(addr, "tail_log", {}).get("files", [])


def tail_log(name: str, node_addr: Optional[str] = None,
             nbytes: int = 65536) -> bytes:
    """Tail one per-process log file (reference: `ray logs <file>`) —
    agent-served with nodelet fallback, like :func:`list_logs`."""
    nodes = list_nodes()
    addr = node_addr or next(
        (n["addr"] for n in nodes if n.get("alive")), None)
    if addr is None:
        raise RuntimeError("no alive node")
    agent_addr = _agent_for_addr(addr)
    if agent_addr is not None:
        try:
            r = _node_call(agent_addr, "tail_log",
                           {"name": name, "bytes": nbytes}, timeout=5.0)
            if "error" not in r:
                return r["data"]
        except Exception:
            pass
    r = _node_call(addr, "tail_log", {"name": name, "bytes": nbytes})
    if "error" in r:
        raise RuntimeError(r["error"])
    return r["data"]
