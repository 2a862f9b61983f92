"""Replica actor: hosts one copy of the user callable.

Capability mirror of the reference's `RayServeReplica`
(`serve/_private/replica.py:250,494`) — wraps the deployment's
class/function, counts in-flight queries, supports `reconfigure`
(user_config hot update) and async handlers.  Runs with
``max_concurrency > 1`` so `@serve.batch` queues can fill.
"""

from __future__ import annotations

import asyncio
import dataclasses
import inspect
import threading
import time
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class ReplicaContext:
    """What serve.get_replica_context() returns inside a replica
    (reference: serve/context.py ReplicaContext)."""
    deployment: str
    replica_tag: str


#: set by ServeReplica.__init__ in the replica's worker process
_replica_context: Optional[ReplicaContext] = None


def get_replica_context() -> ReplicaContext:
    if _replica_context is None:
        raise RuntimeError(
            "get_replica_context() may only be called inside a Serve "
            "replica (deployment __init__ or request handler)")
    return _replica_context


class ServeReplica:
    def __init__(self, deployment_name: str, replica_id: str,
                 callable_blob: bytes, init_args: tuple,
                 init_kwargs: Dict[str, Any], user_config: Any):
        from ..core.serialization import loads_function
        self.deployment_name = deployment_name
        self.replica_id = replica_id
        # replica context (reference: serve.get_replica_context()) —
        # set BEFORE user __init__ runs so constructors can read it
        global _replica_context
        _replica_context = ReplicaContext(deployment_name, replica_id)
        t0 = time.perf_counter()
        fc = loads_function(callable_blob)
        # a replica on the TPU platform reserved the chip to use it: open
        # it here, as a span of its own, not inside the user's first
        # program; then the deployment's own constructor is entered
        from ..core import accelerator
        from ..core.worker_runtime import mark_actor_init
        accelerator.open_reserved_chip()
        mark_actor_init(deployment=deployment_name, replica=replica_id)
        if inspect.isclass(fc):
            self._callable = fc(*init_args, **init_kwargs)
            self._is_function = False
        else:
            self._callable = fc
            self._is_function = True
        self._num_ongoing = 0
        self._lock = threading.Lock()
        self._total = 0
        if user_config is not None:
            self.reconfigure(user_config)
        # cold-start attribution (serve_breakdown's `cold_start`
        # phase): replicas construct lazily, so worker acquisition plus
        # the user constructor — model init, first jit compiles — sit
        # inside the first request's client-measured TTFT.  Without
        # this one-shot push that time is unattributable and the
        # coverage bar reads a cold cluster as an instrumentation gap.
        # The constructor runs AS an actor task, so its spec's
        # submit_time extends the phase back to the controller-side
        # creation submit (covering scheduling/spawn wait too).
        dt = time.perf_counter() - t0
        try:
            from ..core.worker_runtime import (current_task_spec,
                                               current_worker_runtime)
            spec = current_task_spec()
            if spec is not None and getattr(spec, "submit_time", 0):
                dt = max(dt, time.time() - spec.submit_time)
            rt = current_worker_runtime()
            if rt is not None and rt._loop is not None:
                asyncio.run_coroutine_threadsafe(
                    rt.nodelet.notify("serve_metrics", {
                        "deployment": deployment_name,
                        "replica": replica_id,
                        "phase_totals": {"cold_start": round(dt, 6)}}),
                    rt._loop)
        except Exception:
            pass

    def reconfigure(self, user_config: Any) -> bool:
        target = self._callable
        if not self._is_function and hasattr(target, "reconfigure"):
            target.reconfigure(user_config)
        return True

    def _trace_args(self) -> Dict[str, Any]:
        """Span attribution for the request being handled: the replica's
        identity plus the actor-task spec's trace id, so serve spans
        join the same timeline as the task-lifecycle spans."""
        tr = {"deployment": self.deployment_name,
              "replica": self.replica_id}
        from ..core.worker_runtime import current_task_spec
        spec = current_task_spec()
        if spec is not None:
            tr["task_id"] = spec.task_id.hex()
            tr["trace"] = spec.trace_id
        return tr

    def _chaos_site(self, site: str) -> None:
        """Chaos-layer hook for serve scenarios: the replica dies
        mid-request (`crash`), fails the request (`error`), or stalls
        (`latency`) — the router/handle retry path must keep these
        invisible to callers."""
        from ..util import fault_injection as fi
        if fi.ACTIVE is None:
            return
        act = fi.ACTIVE.point(site, self.deployment_name)
        if act is None:
            return
        if act["action"] == "crash":
            import asyncio
            import os

            from ..core.worker_runtime import current_worker_runtime
            rt = current_worker_runtime()
            if act["once"]:
                # claim through the controller (exactly one replica
                # cluster-wide takes the hit); runs on an executor
                # thread, so hop onto the worker's event loop
                claimed = fi.local_claim(act["rule_id"])
                if rt is not None and rt._loop is not None:
                    try:
                        claimed = asyncio.run_coroutine_threadsafe(
                            rt._chaos_claim(act["rule_id"]),
                            rt._loop).result(5)
                    except Exception:
                        pass
                if not claimed:
                    return
            if rt is not None and rt._loop is not None:
                try:
                    asyncio.run_coroutine_threadsafe(
                        rt.nodelet.notify(
                            "chaos_injected",
                            {"site": site, "action": "crash"}),
                        rt._loop).result(2)
                except Exception:
                    pass
            os._exit(fi.CRASH_EXIT_CODE)
        if act["action"] in ("delay", "latency"):
            time.sleep(max(0.0, act["delay_s"]))
        elif act["action"] in ("error", "fail"):
            raise RuntimeError(
                f"chaos: injected {site} failure in "
                f"{self.deployment_name}/{self.replica_id}")

    def handle_request(self, args: tuple, kwargs: Dict[str, Any],
                       method: Optional[str] = None,
                       request_id: Optional[str] = None) -> Any:
        from ..core.worker_runtime import current_task_spec
        from ..util import tracing
        self._chaos_site("serve.request")
        tr = self._trace_args()
        if request_id:
            tr["rid"] = request_id     # the proxy's id of this request
        if args and isinstance(args[0], dict) \
                and isinstance(args[0].get("op"), str):
            # a protocol request (decode sessions: start / next_chunk /
            # end ...): which operation the spans below time
            tr["op"] = args[0]["op"]
        spec = current_task_spec()
        now = time.time()
        if spec is not None and spec.submit_time:
            # router assign -> replica start: the request's queue leg
            tracing.record_span(f"serve_queue::{self.deployment_name}",
                                "serve", spec.submit_time, now, **tr)
        with self._lock:
            self._num_ongoing += 1
            self._total += 1
        try:
            target = self._callable
            if not self._is_function and method:
                target = getattr(target, method)
            elif not self._is_function:
                target = target.__call__
            result = target(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = asyncio.run(result)
            return result
        finally:
            tracing.record_span(f"serve_exec::{self.deployment_name}",
                                "serve", now, time.time(), **tr)
            with self._lock:
                self._num_ongoing -= 1

    def metrics(self) -> Dict[str, Any]:
        with self._lock:
            out = {"replica_id": self.replica_id,
                   "num_ongoing": self._num_ongoing,
                   "total": self._total}
        # decode-session deployments expose their continuous-batching
        # engine's occupancy/queue counters (the callable convention:
        # any `engine_stats()` method merges into replica metrics, so
        # autoscalers/dashboards see slot pressure, not just RPC counts)
        target = self._callable
        if not self._is_function and hasattr(target, "engine_stats"):
            try:
                out["engine"] = target.engine_stats()
            except Exception:
                pass
        return out

    def health_check(self) -> bool:
        self._chaos_site("serve.health_check")
        target = self._callable
        if not self._is_function and hasattr(target, "check_health"):
            target.check_health()
        return True

    # ---------------------------------------------- decode-session drain
    def _my_engines(self):
        """Continuous-batching engines living in THIS replica's process
        (decode_session registers every engine in a process-wide set;
        filter by replica tag in case a worker ever hosts several)."""
        from .decode_session import _ENGINES
        return [eng for eng in list(_ENGINES)
                if getattr(eng, "_tag", None) in (self.replica_id,
                                                  "local")]

    def prepare_drain(self) -> int:
        """Replica is about to be stopped (node drain evacuation): put
        every decode engine into drain mode so live sessions hand
        themselves off — new starts shed with the typed 503, blocked
        `next_chunk` waits wake and deliver their buffered tokens with
        the ``migrating`` flag, and the proxy-side failover client
        re-admits each session on a healthy replica.  Returns the
        number of sessions awaiting handoff."""
        return sum(eng.begin_drain() for eng in self._my_engines())

    def drain_status(self) -> Dict[str, Any]:
        """Live-session count the controller polls before stopping a
        draining replica — zero means every stream has migrated (or
        ended) and the replica can die without dropping a session."""
        return {"live_sessions": sum(eng.live_sessions()
                                     for eng in self._my_engines())}
