"""Command-line interface.

Capability mirror of the reference's CLI
(`python/ray/scripts/scripts.py:529,974,...` — start/stop/status/list/
submit/logs/timeline/microbenchmark).  Usage: ``python -m ray_tpu.scripts.cli
<command>`` (or the ``ray-tpu`` alias once on PATH).

Cluster address plumbing: ``start --head`` writes
``/tmp/ray_tpu_head.json`` (controller + nodelet address); client commands
read it, or take ``--address host:port``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_HEAD_FILE = os.path.join(tempfile.gettempdir(), "ray_tpu_head.json")


def _connect(args) -> None:
    import ray_tpu
    if getattr(args, "address", None):
        ray_tpu.init(address=args.address)
        return
    if os.path.exists(_HEAD_FILE):
        with open(_HEAD_FILE) as f:
            head = json.load(f)
        os.environ["RAY_TPU_SESSION_DIR"] = head["session_dir"]
        ray_tpu.init(address=head["controller"],
                     nodelet_addr=head["nodelet"])
        return
    ray_tpu.init()


def cmd_start(args) -> None:
    from ray_tpu.core import node as node_mod
    if not args.head and not args.address:
        sys.exit("either --head or --address required")
    if args.head:
        session_dir = node_mod.new_session_dir()
        _, controller_addr = node_mod.start_controller(session_dir)
        resources = {"CPU": float(args.num_cpus)}
        if args.num_tpus:
            resources["TPU"] = float(args.num_tpus)
        _, nodelet_addr, node_id, _ = node_mod.start_nodelet(
            session_dir, controller_addr, resources,
            args.object_store_memory)
        with open(_HEAD_FILE, "w") as f:
            json.dump({"controller": controller_addr,
                       "nodelet": nodelet_addr,
                       "session_dir": session_dir}, f)
        print(f"head started: controller={controller_addr} "
              f"nodelet={nodelet_addr}")
        print(f"connect with: ray_tpu.init(address={controller_addr!r})")
    else:
        with open(_HEAD_FILE) as f:
            head = json.load(f)
        resources = {"CPU": float(args.num_cpus)}
        if args.num_tpus:
            resources["TPU"] = float(args.num_tpus)
        _, addr, node_id, _ = node_mod.start_nodelet(
            head["session_dir"], args.address or head["controller"],
            resources, args.object_store_memory)
        print(f"node {node_id} joined at {addr}")


def cmd_stop(args) -> None:
    import signal
    import subprocess
    # kill controller/nodelet/worker processes of the local session
    out = subprocess.run(
        ["pkill", "-f", "ray_tpu.core.(controller|nodelet|worker)_main"],
        capture_output=True)
    if os.path.exists(_HEAD_FILE):
        os.unlink(_HEAD_FILE)
    print("stopped" if out.returncode in (0, 1) else "pkill failed")


def cmd_status(args) -> None:
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    summary = state.cluster_summary()
    print(json.dumps(summary, indent=2, default=str))
    # per-node health table: alive|suspect|draining|dead state plus the
    # failure-detection knobs in force (heartbeat timeout, suspect
    # grace, probe fanout) and any severed peer links
    rows = state.list_nodes()
    if rows:
        h = (rows[0].get("health") or {})
        print(f"\nheartbeat_timeout_s={h.get('heartbeat_timeout_s', '-')} "
              f"suspect_grace_s={h.get('suspect_grace_s', '-')} "
              f"peer_probe_fanout={h.get('peer_probe_fanout', '-')}")
        print(f"{'NODE':<14} {'STATE':<9} {'HB_AGE':>7}  DETAIL")
        for n in rows:
            detail = ""
            if n.get("state") == "SUSPECT":
                detail = (f"suspect_for={n.get('suspect_for_s', '?')}s "
                          f"peers_reaching="
                          f"{[p[:8] for p in n.get('peers_reaching', [])]}")
            if n.get("unreachable_peers"):
                detail += (" cannot_reach="
                           f"{[p[:8] for p in n['unreachable_peers']]}")
            drain = n.get("drain")
            if drain:
                detail += f" drain={drain.get('phase', '?')}"
            if n.get("disk", "ok") != "ok":
                detail += (f" disk={n['disk']}"
                           f"({n.get('disk_used_frac', '?')} used)")
            hb = (n.get("health") or {}).get("heartbeat_age_s", "-")
            print(f"{n['id'][:12]:<14} {n.get('state', '?'):<9} "
                  f"{hb:>7}  {detail}")
    # per-actor restart/containment table: lifetime restart count plus
    # whether the crash-loop governor has quarantined the actor
    acts = state.actors()
    if acts:
        print(f"\n{'ACTOR':<14} {'CLASS':<18} {'STATE':<12} "
              f"{'RESTARTS':>8}  {'QUARANTINED'}")
        for a in acts:
            aid = a.get("actor_id")
            aid = aid.hex()[:12] if isinstance(aid, bytes) else str(aid)[:12]
            print(f"{aid:<14} {str(a.get('class_name', ''))[:18]:<18} "
                  f"{a.get('state', '?'):<12} "
                  f"{a.get('num_restarts', 0):>8}  "
                  f"{'yes' if a.get('quarantined') else 'no'}")
    q = state.quarantine_list()
    if q:
        print(f"\n{len(q)} quarantined signature(s) — "
              "see `ray-tpu quarantine list`")
    ray_tpu.shutdown()


def cmd_up(args) -> None:
    from ray_tpu.autoscaler import launcher
    state = launcher.up(args.config)
    print(f"cluster {state['cluster_name']!r} up: "
          f"controller={state['controller']} "
          f"workers={len(state['provider_nodes'])}")
    print(f"connect with: ray_tpu.init(address={state['controller']!r}, "
          f"nodelet_addr={state['nodelet']!r})")


def cmd_down(args) -> None:
    from ray_tpu.autoscaler import launcher
    state = launcher.down(args.cluster)
    print(f"cluster {state['cluster_name']!r} terminated "
          f"({len(state.get('pids', []))} processes)")


def cmd_exec(args) -> None:
    from ray_tpu.autoscaler import launcher
    # a single quoted argument is a SHELL command (ray exec semantics);
    # multiple arguments are an exact argv
    cmd = args.command[0] if len(args.command) == 1 else args.command
    sys.exit(launcher.exec_cmd(args.cluster, cmd))


def cmd_attach(args) -> None:
    """Interactive shell with the cluster's env exported (local form of
    `ray attach`)."""
    from ray_tpu.autoscaler import launcher
    sys.exit(launcher.exec_cmd(args.cluster,
                               [os.environ.get("SHELL", "/bin/bash")]))


def cmd_serve_status(args) -> None:
    """Application-level status of the running Serve instance
    (reference: `serve status` CLI)."""
    import ray_tpu
    from ray_tpu.serve import schema
    _connect(args)
    print(json.dumps(schema.status(), indent=2, default=str))
    ray_tpu.shutdown()


def cmd_serve_deploy(args) -> None:
    """Deploy a declarative YAML config (reference: `serve deploy`)."""
    import ray_tpu
    from ray_tpu.serve import schema
    _connect(args)
    handles = schema.apply_config(args.config_file)
    print(f"deployed {len(handles)} application(s): "
          f"{', '.join(handles)}")
    ray_tpu.shutdown()


def cmd_serve_config(args) -> None:
    """The config last applied via serve deploy (reference:
    `serve config`)."""
    import ray_tpu
    from ray_tpu.serve import schema
    _connect(args)
    cfg = schema.get_deployed_config()
    print(json.dumps(cfg, indent=2, default=str) if cfg else "{}")
    ray_tpu.shutdown()


def cmd_list(args) -> None:
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    fn = {"nodes": state.list_nodes, "actors": state.list_actors,
          "placement-groups": state.list_placement_groups,
          "jobs": state.list_jobs, "tasks": state.list_tasks,
          "objects": state.list_objects}[args.kind]
    print(json.dumps(fn(), indent=2, default=str))
    ray_tpu.shutdown()


def cmd_submit(args) -> None:
    import ray_tpu
    from ray_tpu import jobs
    _connect(args)
    job_id = jobs.submit_job(" ".join(args.entrypoint))
    print(f"submitted {job_id}")
    if args.wait:
        status = jobs.wait_job(job_id, timeout_s=args.timeout)
        print(jobs.get_job_logs(job_id), end="")
        print(f"job {job_id}: {status}")
        ray_tpu.shutdown()
        sys.exit(0 if status == jobs.SUCCEEDED else 1)
    ray_tpu.shutdown()


def cmd_logs(args) -> None:
    import ray_tpu
    from ray_tpu import jobs
    _connect(args)
    print(jobs.get_job_logs(args.job_id), end="")
    ray_tpu.shutdown()


def cmd_stack(args) -> None:
    """Dump Python stacks of every local runtime process (reference:
    `ray stack`, scripts.py:1712 via py-spy): SIGUSR1 makes each process
    write all thread stacks to its session log; this prints them."""
    import glob
    import signal
    import subprocess
    import time as _time

    patterns = ("ray_tpu.core.controller_main", "ray_tpu.core.nodelet_main",
                "ray_tpu.core.worker_main")
    signalled = 0
    for pat in patterns:
        out = subprocess.run(["pkill", "-USR1", "-f", pat],
                             capture_output=True)
        signalled += 1 if out.returncode == 0 else 0
    _time.sleep(1.0)
    from ..core.node import sessions_base
    base = sessions_base()
    sessions = sorted(glob.glob(os.path.join(base, "session_*")),
                      key=os.path.getmtime)
    if not sessions:
        print("no sessions found")
        return
    logdir = os.path.join(sessions[-1], "logs")
    for f in sorted(glob.glob(os.path.join(logdir, "*"))):
        try:
            with open(f, "rb") as fh:
                data = fh.read()[-20000:]
        except OSError:
            continue
        if b"Thread 0x" in data:
            print(f"==== {os.path.basename(f)}")
            tail = data[data.rfind(b"Thread 0x"):]
            sys.stdout.write(tail.decode(errors="replace"))
    print(f"(signalled {signalled} process groups; stacks from {logdir})")


def cmd_memory(args) -> None:
    """`ray memory` equivalent: object table + borrows + store usage."""
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    print(json.dumps(state.memory_summary(), indent=2, default=str))
    ray_tpu.shutdown()


def cmd_taillog(args) -> None:
    """Tail a per-process log file from a node's session dir."""
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    if not args.name:
        for f in state.list_logs(args.node):
            print(f)
    else:
        sys.stdout.buffer.write(state.tail_log(args.name, args.node,
                                               args.bytes))
    ray_tpu.shutdown()


def cmd_timeline(args) -> None:
    """Dump the cluster-wide task timeline (lifecycle spans from every
    process, merged by the controller) as Chrome-trace JSON; with
    ``--session-dir``, the timeline of a session that is no longer up,
    merged from the span files its processes left under
    ``<dir>/spans/`` when they exited.  A ``program:compiled`` span
    (category ``setup``) marks each op map the compile ledger left in
    ``<dir>/programs/``: what places a profiler trace's device ops in
    the model (README, Observability)."""
    path = args.output or "timeline.json"
    if args.session_dir:
        from ray_tpu.util import tracing
        dump = tracing.chrome_trace(
            tracing.read_span_files(args.session_dir))
        with open(path, "w") as f:
            json.dump(dump, f)
        spans = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
        print(f"{len(spans)} spans -> {path} "
              f"(from {args.session_dir}/spans)")
        return
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    dump = state.timeline()
    with open(path, "w") as f:
        json.dump(dump, f)
    spans = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
    print(f"{len(spans)} spans -> {path} "
          f"(open in https://ui.perfetto.dev or chrome://tracing)")
    ray_tpu.shutdown()


def cmd_drain(args) -> None:
    """Gracefully drain a node ahead of planned maintenance: stop new
    work, evacuate sole-copy objects, migrate actors, wait for in-flight
    tasks, then cleanly deregister.  On deadline overrun the node takes
    the hard-death recovery path."""
    import ray_tpu
    from ray_tpu.core.config import GlobalConfig
    from ray_tpu.core.driver import get_global_core
    _connect(args)
    try:
        core = get_global_core()
        nodes = core.controller.call("list_nodes", {}, timeout=10)
        matches = [n for n in nodes
                   if n["id"].startswith(args.node_id) and n.get("alive")]
        if len(matches) != 1:
            sys.exit(f"node id {args.node_id!r} matches "
                     f"{len(matches)} alive nodes "
                     f"({[n['id'][:12] for n in matches]})")
        node_id = matches[0]["id"]
        timeout = args.timeout or GlobalConfig.drain_timeout_s
        print(f"draining {node_id[:12]}... (budget {timeout:g}s)")
        reply = core.controller.call(
            "drain_node", {"node_id": node_id, "timeout_s": timeout,
                           "wait": True}, timeout=timeout + 60)
        print(json.dumps(reply, indent=2, default=str))
        if reply.get("outcome") != "completed":
            sys.exit(1)
    finally:
        ray_tpu.shutdown()


def cmd_controller(args) -> None:
    """Control-plane HA status: one row per controller (leader + hot
    standbys) with role, epoch, and WAL replication mode/lag — the
    operator's view of core/ha.py."""
    import ray_tpu
    from ray_tpu import state
    if args.op != "status":
        sys.exit(f"unknown controller op {args.op!r}")
    _connect(args)
    try:
        rows = state.list_controllers()
        print(f"{'ROLE':<12} {'ADDR':<22} {'EPOCH':>5}  "
              f"{'REPL':<6} {'LAG':>5}  DETAIL")
        for r in rows:
            repl = r.get("repl") or {}
            detail = ""
            if r.get("role") == "leader":
                detail = (f"acked={repl.get('acked', '-')} "
                          f"seq={repl.get('seq', '-')}"
                          + (" DEGRADED" if repl.get("degraded") else ""))
            elif r.get("role") == "standby":
                detail = (f"lease_age={r.get('lease_age_s', '-')}s "
                          f"applied_seq={r.get('applied_seq', '-')}")
            elif r.get("error"):
                detail = r["error"][:60]
            print(f"{r.get('role', '?'):<12} {r.get('addr', '?'):<22} "
                  f"{r.get('epoch', '-'):>5}  "
                  f"{repl.get('mode', '-'):<6} "
                  f"{repl.get('lag', '-'):>5}  {detail}")
        if not any(r.get("role") == "leader" for r in rows):
            sys.exit("no controller currently claims leadership")
    finally:
        ray_tpu.shutdown()


def cmd_quarantine(args) -> None:
    """Poison-task / crash-loop quarantine control: list the quarantined
    signatures with their evidence trails (which nodes the signature
    killed workers on, and why), or clear one signature — or all — to
    let the work run again immediately instead of waiting out the TTL."""
    import ray_tpu
    from ray_tpu.core.driver import get_global_core
    _connect(args)
    try:
        core = get_global_core()
        if args.op == "list":
            rows = core.controller.call("quarantine_list", {}, timeout=10)
            if not rows:
                print("no quarantined signatures")
                return
            now = time.time()
            print(f"{'SIGNATURE':<40} {'KIND':<12} {'TTL':>6}  EVIDENCE")
            for r in rows:
                ttl = max(0.0, float(r.get("until", 0.0)) - now)
                ev = r.get("evidence") or []
                nodes = sorted({str(h.get("node", "?"))[:8] for h in ev})
                causes = sorted({str(h.get("cause", {}).get("kind", "?"))
                                 if isinstance(h.get("cause"), dict)
                                 else str(h.get("cause", "?")) for h in ev})
                print(f"{str(r.get('sig', '?'))[:40]:<40} "
                      f"{str(r.get('kind', '?')):<12} {ttl:>5.0f}s  "
                      f"{len(ev)} kills on {nodes} ({','.join(causes)})")
        elif args.op == "clear":
            data = {"sig": args.sig} if args.sig else {}
            reply = core.controller.call("quarantine_clear", data,
                                         timeout=10)
            cleared = reply.get("cleared") or []
            if not cleared:
                print("nothing to clear" if not args.sig
                      else f"{args.sig!r} is not quarantined")
            for sig in cleared:
                print(f"cleared {sig}")
        else:
            sys.exit(f"unknown quarantine op {args.op!r}")
    finally:
        ray_tpu.shutdown()


def _load_chaos_plan(path):
    if not path:
        sys.exit("chaos needs a JSON plan file for this operation")
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            sys.exit(f"{path}: not valid JSON: {e}")


def cmd_chaos(args) -> None:
    """Fault-injection (chaos) plan control: apply a JSON plan file
    cluster-wide (controller KV + pubsub fan-out), clear it, show the
    current plan + this process's injection counts, or validate a plan
    file offline (no cluster needed) — a typoed site or bad matcher
    otherwise fails SILENTLY by never firing."""
    import ray_tpu
    from ray_tpu import chaos
    from ray_tpu.util import fault_injection as fi
    if args.op == "validate":
        plan = _load_chaos_plan(args.plan)
        issues = fi.validate_plan(plan)
        if issues:
            for issue in issues:
                print(f"ERROR: {issue}")
            sys.exit(f"{args.plan}: {len(issues)} issue(s) — this plan "
                     f"would misfire or never fire")
        n = len(plan)
        print(f"{args.plan}: OK ({n} rule(s), all sites/matchers valid)")
        return
    _connect(args)
    try:
        if args.op == "apply":
            plan = _load_chaos_plan(args.plan)
            issues = fi.validate_plan(plan)
            if issues:
                for issue in issues:
                    print(f"ERROR: {issue}")
                sys.exit("refusing to apply a plan that would misfire; "
                         "fix it or dry-run with `ray-tpu chaos "
                         "validate`")
            n = chaos.apply(plan)
            print(f"chaos plan applied: {n} rule(s)")
        elif args.op == "clear":
            chaos.clear()
            print("chaos plan cleared")
        else:
            print(json.dumps(chaos.status(), indent=2, default=str))
    finally:
        ray_tpu.shutdown()


def _fmt_rate(v) -> str:
    return f"{v:,.1f}" if isinstance(v, float) else str(v)


def render_top(nodes, history, attr, top_k: int = 10,
               breakdown=None) -> str:
    """One frame of the `ray-tpu top` terminal view (pure function of
    the state-API payloads, so it is unit-testable offline).
    ``breakdown`` is the optional `state.serve_breakdown()` table —
    per-deployment ms/token attribution with coverage and MFU."""
    from ray_tpu.core import metrics_history as mh
    lines = []
    alive = sum(1 for n in nodes if n.get("alive"))
    lines.append(
        f"ray-tpu top — {time.strftime('%H:%M:%S')}  nodes: "
        f"{len(nodes)} total / {alive} alive / "
        f"{sum(1 for n in nodes if n.get('state') == 'SUSPECT')} suspect"
        f" / {sum(1 for n in nodes if n.get('state') == 'DRAINING')}"
        f" draining")
    # per-node rates out of each nodelet's metrics-history ring
    interval = history.get("interval_s") or 1.0
    lines.append(f"{'NODE':<14} {'STATE':<9} {'TASKS/S':>9} "
                 f"{'GRANTS/S':>9} {'HB_AGE':>7} {'LAG_MS':>7} "
                 f"{'CLK_OFF_MS':>10}")
    for n in nodes:
        label = f"nodelet@{n['id'][:8]}"
        samples = (history.get("processes", {})
                   .get(label, {}) or {}).get("samples", [])
        win = samples[-20:]
        # n samples cover (n-1) intervals of deltas
        span_s = max(interval, (len(win) - 1) * interval)

        def rate(name):
            tot = sum(s["delta"] for s in mh.series(win, name))
            return tot / span_s
        lag = next((s["value"] for s in reversed(
            mh.series(win, "ray_tpu_event_loop_lag_seconds", "gauges"))),
            0.0)
        hb = (n.get("health") or {}).get("heartbeat_age_s", "-")
        lines.append(
            f"{n['id'][:12]:<14} {n.get('state', '?'):<9} "
            f"{_fmt_rate(rate('ray_tpu_tasks_finished_total')):>9} "
            f"{_fmt_rate(rate('ray_tpu_scheduler_leases_granted_total')):>9} "
            f"{hb:>7} {lag * 1e3:>7.1f} "
            f"{float(n.get('clock_offset_s') or 0.0) * 1e3:>10.1f}")
    # serve fleet (engine + serve-controller pushes folded into the
    # nodelet rings): per-deployment replica count and slot pressure —
    # the autoscaler's own view of the world
    dep_rep, dep_eng = {}, {}
    for proc in (history.get("processes") or {}).values():
        samples = (proc or {}).get("samples", [])
        for pt in mh.series(samples, "ray_tpu_serve_deployment_replicas",
                            "gauges"):
            dep = mh.parse_labels(pt["key"]).get("deployment", "?")
            dep_rep[dep] = pt["value"]          # time-ordered: last wins
        for fam, field in (("ray_tpu_serve_engine_occupied_slots", 0),
                           ("ray_tpu_serve_engine_max_slots", 1),
                           ("ray_tpu_serve_engine_waiting_sessions", 2)):
            for pt in mh.series(samples, fam, "gauges"):
                lb = mh.parse_labels(pt["key"])
                key = (lb.get("deployment", "?"), lb.get("replica", "?"))
                dep_eng.setdefault(key, [0.0, 0.0, 0.0])[field] = \
                    pt["value"]
    if dep_rep or dep_eng:
        lines.append("")
        lines.append(f"SERVE — {'DEPLOYMENT':<18} {'REPLICAS':>8} "
                     f"{'OCC/SLOTS':>10} {'WAITING':>8}")
        deps = sorted(set(dep_rep) | {d for d, _ in dep_eng})
        for dep in deps:
            occ = sum(v[0] for (d, _), v in dep_eng.items() if d == dep)
            slots = sum(v[1] for (d, _), v in dep_eng.items() if d == dep)
            wait = sum(v[2] for (d, _), v in dep_eng.items() if d == dep)
            reps = dep_rep.get(dep)
            lines.append(
                f"        {dep:<18} "
                f"{('%d' % reps) if reps is not None else '-':>8} "
                f"{'%g/%g' % (occ, slots):>10} {wait:>8g}")
    # serve data-plane breakdown: where a served ms/token goes (engine
    # phase counters + proxy latency histograms, state.serve_breakdown)
    if breakdown and breakdown.get("deployments"):
        phases = list(breakdown.get("phases") or ())
        lines.append("")
        lines.append("SERVE BREAKDOWN — ms/token by phase "
                     "(COV = attributed / client-measured time)")
        hdr = " ".join(f"{p[:9].upper():>9}" for p in phases)
        lines.append(f"{'DEPLOYMENT':<18} {'TOKENS':>8} {hdr} "
                     f"{'COV':>5} {'MFU':>6}")
        for dep, row in sorted(breakdown["deployments"].items()):
            mpt = row.get("ms_per_token") or {}
            cells = " ".join(
                f"{('%.2f' % mpt[p]) if mpt.get(p) is not None else '-':>9}"
                for p in phases)
            cov = row.get("coverage")
            mfu = row.get("mfu") or {}
            peak_mfu = max(mfu.values()) if mfu else None
            lines.append(
                f"{dep:<18} {row.get('tokens', 0):>8} {cells} "
                f"{('%.0f%%' % (cov * 100)) if cov is not None else '-':>5}"
                f" {('%.3f' % peak_mfu) if peak_mfu is not None else '-':>6}")
            eng = row.get("engine_s") or {}
            if eng:
                lines.append("  engine thread s: " + "  ".join(
                    f"{k}={v:.3f}" for k, v in eng.items()))
    ctl = attr.get("controller") or {}
    ops = list(ctl.get("ops") or [])[:top_k]
    lines.append("")
    lines.append(f"CONTROLLER RPC — top {len(ops)} handlers by total "
                 f"handler time")
    lines.append(f"{'OP':<26} {'CALLS':>9} {'ERR':>5} {'TOTAL_S':>9} "
                 f"{'AVG_MS':>8} {'P99_MS':>8} {'IN_KB':>9} {'OUT_KB':>9}")
    for r in ops:
        lines.append(
            f"{r['op']:<26} {r['count']:>9} {r['errors']:>5} "
            f"{r['total_s']:>9.3f} {r['avg_ms']:>8.3f} "
            f"{r['p99_ms']:>8.3f} {r['bytes_in'] / 1024:>9.1f} "
            f"{r['bytes_out'] / 1024:>9.1f}")
    wal = ctl.get("wal")
    if wal and wal.get("appends"):
        lines.append(
            f"WAL: {wal['appends']} appends, "
            f"avg {wal['append_s'] / wal['appends'] * 1e3:.2f} ms "
            f"(fsync {wal['fsync_s'] / wal['appends'] * 1e3:.2f} ms), "
            f"max {wal['append_max_s'] * 1e3:.2f} ms")
    lag = ctl.get("loop_lag") or {}
    lines.append(f"controller loop lag: "
                 f"ewma {lag.get('ewma_ms', 0):.2f} ms / "
                 f"max {lag.get('max_ms', 0):.2f} ms")
    return "\n".join(lines)


def cmd_top(args) -> None:
    """Live terminal view over the metrics-history rings + per-RPC
    attribution (reference: `ray status`'s periodic refresh + the
    dashboard's machine view, as a terminal loop)."""
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    try:
        n = 0
        while True:
            try:
                bd = state.serve_breakdown()
            except Exception:
                bd = None   # no serve plane up: panel just stays off
            frame = render_top(state.list_nodes(),
                               state.metrics_history(last=60),
                               state.rpc_attribution(),
                               breakdown=bd)
            if not args.once:
                print("\033[2J\033[H", end="")
            print(frame, flush=True)
            n += 1
            if args.once or (args.iterations and n >= args.iterations):
                break
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    finally:
        ray_tpu.shutdown()


def cmd_debug(args) -> None:
    """Flight-recorder control: `capture` grabs an incident bundle NOW
    (manual grabs bypass the per-trigger rate limit); `list` shows the
    bundles already on disk under flight_recorder_dir."""
    from ray_tpu.core import flight_recorder as fr
    if args.op == "list":
        base = fr.recorder_dir()
        bundles = fr.list_bundles(base)
        print(f"{len(bundles)} bundle(s) in {base}")
        for b in bundles:
            print(f"  {b}")
        return
    import ray_tpu
    from ray_tpu import state
    _connect(args)
    try:
        reply = state.debug_capture(args.reason or "manual CLI capture")
        if not reply.get("ok"):
            sys.exit(f"capture failed: {reply.get('error')}")
        print(f"bundle captured: {reply['path']}")
    finally:
        ray_tpu.shutdown()


def cmd_metrics(args) -> None:
    """Metrics tooling: `lint` checks every metric the runtime battery
    registers — HELP/TYPE present, names legal/unique/prefixed,
    counters `*_total`, label sets under the cardinality bounds — so a
    new metric cannot silently break exposition (sibling of `chaos
    validate`; offline, no cluster needed)."""
    if args.op != "lint":
        sys.exit(f"unknown metrics op {args.op!r}")
    # register the full runtime battery in this process, then lint it
    import ray_tpu  # noqa: F401  (registers core metrics on import)
    import ray_tpu.core.runtime_metrics  # noqa: F401
    from ray_tpu import metrics
    issues = metrics.lint_registry()
    if issues:
        for issue in issues:
            print(f"ERROR: {issue}")
        sys.exit(f"{len(issues)} metric issue(s) — exposition or "
                 f"cardinality would break silently")
    with metrics._lock:
        n = len(metrics._registry)
    print(f"OK: {n} registered metric(s), all HELP/TYPE/naming/"
          f"cardinality checks clean")


def cmd_lint(args) -> None:
    """Framework-invariant static analysis (offline, no cluster): the
    eight AST rules of ray_tpu/devtools/lint — loop-blocking calls in
    async bodies, thread/shared-state races, chaos-site drift, WAL-op
    replay coverage, RPC surface consistency, RPC payload contracts,
    lock-order cycles, WAL replay determinism — checked against the
    committed baseline.  Exits non-zero on any NEW finding, a baseline
    entry missing its reason, or a STALE baseline entry."""
    import ray_tpu
    from ray_tpu.devtools.lint import engine as lint_engine

    if args.root:
        package_dir = os.path.abspath(args.root)
    else:
        package_dir = os.path.dirname(os.path.abspath(ray_tpu.__file__))
    repo_root = os.path.dirname(package_dir)
    evidence = []
    tests_dir = os.path.join(repo_root, "tests")
    if os.path.isdir(tests_dir):
        evidence.append(tests_dir)
    baseline = args.baseline
    if args.no_baseline:
        baseline = ""
    elif args.root and baseline is None:
        # linting a foreign tree: only use a baseline it carries itself
        cand = lint_engine.default_baseline_path(package_dir)
        baseline = cand if os.path.exists(cand) else ""
    only_rel = None
    if args.changed and not args.update_baseline:
        only_rel = _git_changed_rels(repo_root, package_dir)
        if only_rel is None:
            print("lint --changed: not a git tree (or git failed) — "
                  "running the full scan")
        elif not only_rel:
            print("lint --changed: no changed files under the package "
                  "— nothing to report (cross-file registries still "
                  "validated)")
    res = lint_engine.run_lint(package_dir, baseline_path=baseline,
                               evidence_dirs=evidence,
                               only_rel=only_rel)
    if args.update_baseline:
        path = baseline or lint_engine.default_baseline_path(package_dir)
        counts = lint_engine.update_baseline(path, res)
        print(f"baseline regenerated at {path}: {counts['kept']} "
              f"entr(ies) kept their reason, {counts['new']} NEW with "
              f"an empty reason, {counts['dropped']} stale dropped")
        if counts["new"]:
            print("fill in every empty reason before committing — "
                  "`ray-tpu lint` fails on reasonless entries")
        return
    if args.json:
        print(json.dumps(res.to_json(), indent=2))
    else:
        print(lint_engine.render_text(res, verbose=args.verbose))
    if not res.ok:
        sys.exit(f"{len(res.findings)} new lint finding(s) + "
                 f"{len(res.baseline_errors)} baseline issue(s) + "
                 f"{len(res.stale_baseline)} stale entr(ies) — fix "
                 f"them, suppress with `# rtpu: allow[<rule>]`, or "
                 f"baseline them WITH a reason")


def _git_changed_rels(repo_root, package_dir):
    """Package-relative paths of files git considers changed (worktree
    + index vs HEAD, plus untracked).  None when git is unavailable."""
    import subprocess
    changed = set()
    for argv in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            out = subprocess.run(argv, cwd=repo_root,
                                 capture_output=True, text=True,
                                 timeout=15)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if out.returncode != 0:
            return None
        changed.update(ln.strip() for ln in out.stdout.splitlines()
                       if ln.strip())
    prefix = os.path.relpath(package_dir, repo_root)
    prefix = "" if prefix == "." else prefix.replace(os.sep, "/") + "/"
    rels = set()
    for path in changed:
        p = path.replace(os.sep, "/")
        if prefix and not p.startswith(prefix):
            continue
        rels.add(p[len(prefix):])
    return rels


def cmd_microbenchmark(args) -> None:
    import ray_tpu
    from ray_tpu.microbenchmark import run_microbenchmarks
    ray_tpu.init(num_cpus=args.num_cpus)
    results = run_microbenchmarks(min_time=args.min_time,
                                  include_serve=True)
    for k, v in results.items():
        print(f"{k}: {v:,.1f}")
    ray_tpu.shutdown()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="ray-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start head or join a cluster")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address")
    sp.add_argument("--num-cpus", type=float, default=os.cpu_count() or 4)
    sp.add_argument("--num-tpus", type=float, default=0)
    sp.add_argument("--object-store-memory", type=int,
                    default=256 * 1024 * 1024)
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("stop", help="stop local cluster processes")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("status", help="cluster summary")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser("serve-status", help="Serve deployment table")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_serve_status)

    sp = sub.add_parser("serve-deploy",
                        help="deploy a declarative Serve YAML config")
    sp.add_argument("config_file")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_serve_deploy)

    sp = sub.add_parser("serve-config",
                        help="show the last config applied via serve-deploy")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_serve_config)

    sp = sub.add_parser("up", help="launch a cluster from a YAML config")
    sp.add_argument("config")
    sp.set_defaults(fn=cmd_up)

    sp = sub.add_parser("down", help="terminate a launched cluster")
    sp.add_argument("cluster", help="cluster name or its YAML config")
    sp.set_defaults(fn=cmd_down)

    sp = sub.add_parser("exec", help="run a command against a cluster")
    sp.add_argument("cluster")
    sp.add_argument("command", nargs="+")
    sp.set_defaults(fn=cmd_exec)

    sp = sub.add_parser("attach", help="shell with the cluster env")
    sp.add_argument("cluster")
    sp.set_defaults(fn=cmd_attach)

    sp = sub.add_parser("list", help="list cluster state")
    sp.add_argument("kind", choices=["nodes", "actors",
                                     "placement-groups", "jobs",
                                     "tasks", "objects"])
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("submit", help="submit a job entrypoint")
    sp.add_argument("--address")
    sp.add_argument("--wait", action="store_true")
    sp.add_argument("--timeout", type=float, default=300.0)
    sp.add_argument("entrypoint", nargs=argparse.REMAINDER)
    sp.set_defaults(fn=cmd_submit)

    sp = sub.add_parser("logs", help="fetch job logs")
    sp.add_argument("job_id")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_logs)

    sp = sub.add_parser("stack", help="dump stacks of runtime processes")
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser("memory", help="object/ref memory dump")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_memory)

    sp = sub.add_parser("taillog", help="list/tail per-process log files")
    sp.add_argument("name", nargs="?", default="")
    sp.add_argument("--node", help="node address host:port")
    sp.add_argument("--bytes", type=int, default=65536)
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_taillog)

    sp = sub.add_parser("timeline",
                        help="dump the cluster task timeline as a "
                             "chrome trace (Perfetto-loadable)")
    sp.add_argument("--address")
    sp.add_argument("--session-dir",
                    help="merge the span files of a finished (or crashed) "
                         "session instead of asking a live cluster")
    sp.add_argument("-o", "--output")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser("drain",
                        help="gracefully drain a node (phased "
                             "evacuation for planned maintenance)")
    sp.add_argument("node_id", help="node id (hex, prefix ok)")
    sp.add_argument("--timeout", type=float, default=None,
                    help="graceful budget in seconds before the "
                         "hard-death fallback (default: "
                         "drain_timeout_s config)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_drain)

    sp = sub.add_parser("controller",
                        help="control-plane HA status "
                             "(leader/standby/epoch/replication lag)")
    sp.add_argument("op", choices=["status"])
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_controller)

    sp = sub.add_parser("quarantine",
                        help="poison-task / crash-loop quarantine "
                             "(list evidence trails, clear signatures)")
    sp.add_argument("op", choices=["list", "clear"])
    sp.add_argument("sig", nargs="?",
                    help="signature to clear (e.g. task:train_step or "
                         "actor:Worker:<id>); omit to clear ALL")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_quarantine)

    sp = sub.add_parser("chaos",
                        help="fault-injection plan control "
                             "(apply/clear/status/validate)")
    sp.add_argument("op", choices=["apply", "clear", "status",
                                   "validate"])
    sp.add_argument("plan", nargs="?",
                    help="JSON plan file (for apply/validate); rule "
                         "schema in ray_tpu/util/fault_injection.py. "
                         "`validate` lints offline — unknown sites, "
                         "bad regexes, conflicting once rules — so a "
                         "plan that would silently never fire fails "
                         "fast")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_chaos)

    sp = sub.add_parser("top",
                        help="live cluster view: per-node task/lease "
                             "rates from the metrics-history rings + "
                             "top RPC handlers by handler time")
    sp.add_argument("--address")
    sp.add_argument("--interval", type=float, default=2.0)
    sp.add_argument("--iterations", type=int, default=0,
                    help="stop after N frames (0 = until Ctrl-C)")
    sp.add_argument("--once", action="store_true",
                    help="print one frame and exit (no screen clear)")
    sp.set_defaults(fn=cmd_top)

    sp = sub.add_parser("debug",
                        help="flight recorder: capture an incident "
                             "bundle now, or list bundles on disk")
    sp.add_argument("op", choices=["capture", "list"])
    sp.add_argument("--reason", default="")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_debug)

    sp = sub.add_parser("metrics",
                        help="metrics tooling (lint: offline HELP/TYPE/"
                             "naming/cardinality check of the "
                             "registered battery)")
    sp.add_argument("op", choices=["lint"])
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser("lint",
                        help="static analysis of the package source: "
                             "loop-blocking, thread-race, chaos-site/"
                             "WAL-op/RPC-surface drift, RPC payload "
                             "contracts, lock-order cycles, WAL replay "
                             "determinism (offline; non-zero exit on "
                             "new findings)")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable report (includes per-rule "
                         "timing)")
    sp.add_argument("--verbose", action="store_true",
                    help="also list baselined findings")
    sp.add_argument("--baseline", default=None,
                    help="baseline file (default: the committed "
                         "ray_tpu/devtools/lint/baseline.json)")
    sp.add_argument("--no-baseline", action="store_true",
                    help="report every finding, grandfathered or not")
    sp.add_argument("--root",
                    help="lint this package dir instead of the "
                         "installed ray_tpu (tests, fixture trees)")
    sp.add_argument("--changed", action="store_true",
                    help="report only findings anchored in "
                         "git-changed files (cross-file rules still "
                         "scan the whole tree); pre-commit fast path")
    sp.add_argument("--update-baseline", action="store_true",
                    help="regenerate the baseline in place: existing "
                         "reasons kept, new findings added with an "
                         "EMPTY reason that must be filled before "
                         "commit, stale entries dropped")
    sp.set_defaults(fn=cmd_lint)

    sp = sub.add_parser("microbenchmark", help="core op throughput")
    sp.add_argument("--num-cpus", type=float, default=4)
    sp.add_argument("--min-time", type=float, default=1.0)
    sp.set_defaults(fn=cmd_microbenchmark)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
