"""Worker process runtime: the task execution loop.

The in-process half of the reference's core worker
(/root/reference/src/ray/core_worker/core_worker.cc ExecuteTask :2243 /
HandlePushTask :2648, with Python dispatch at _raylet.pyx:678).  A worker:

- serves ``push_task`` / ``create_actor`` / ``push_actor_task`` RPCs pushed
  *directly* by drivers and other workers (direct task transport — no nodelet
  round-trip on the hot path),
- resolves reference args from the node's shared-memory store (pulling
  remote objects via the nodelet),
- executes user code on executor threads so the RPC loop stays live,
- returns small results inline in the RPC reply and puts large ones into the
  shared-memory store (reference: max_direct_call_object_size split),
- for actors, keeps the live instance and executes methods in per-caller
  sequence order (transport/actor_scheduling_queue.cc semantics); with
  ``max_concurrency > 1`` methods run out-of-order on a thread pool.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import inspect
import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from .. import exceptions
from . import rpc, serialization
from .config import GlobalConfig


def _get_worker_core():
    """This worker's lazily-created CoreClient (None before user code first
    touches the API)."""
    from .driver import get_global_core
    return get_global_core()
from .object_store import client as store_client
import functools

from .task_spec import ARG_REF, ARG_VALUE, DYNAMIC_RETURNS, TaskSpec

FN_NAMESPACE = "fn"

# Armed fault-injection plan (util/fault_injection.py sets/clears this —
# importing ray_tpu.util at module scope here would cycle through the
# package __init__).  None == chaos disabled (one None check per site).
_chaos = None

# The spec of the task currently executing in this context (thread /
# asyncio task) — feeds `ray_tpu.get_runtime_context()` (reference:
# WorkerContext / ray.get_runtime_context).
import contextvars  # noqa: E402

_current_spec: "contextvars.ContextVar[Optional[TaskSpec]]" = \
    contextvars.ContextVar("ray_tpu_current_spec", default=None)
_runtime_singleton: Optional["WorkerRuntime"] = None


def current_task_spec() -> Optional[TaskSpec]:
    return _current_spec.get()


def mark_actor_init(**args: Any) -> None:
    """The ring span ``setup:actor_init``: this worker was handed its
    actor -> the code the actor exists for is entered (a Serve
    deployment's constructor, a train loop).  Called once by the entry
    point just before it calls that code; the class's unpickling, the
    runtime's own constructor and, where the worker holds the chip,
    ``setup:chip_open`` lie inside it."""
    rt = _runtime_singleton
    if rt is None or rt._actor_handed is None:
        return
    from ..util import tracing
    handed, rt._actor_handed = rt._actor_handed, None
    tracing.record_span("setup:actor_init", "setup", handed, time.time(),
                        worker_pid=os.getpid(), **args)


def current_worker_runtime() -> Optional["WorkerRuntime"]:
    return _runtime_singleton


class WorkerRuntime:
    def __init__(self, *, nodelet_addr: str, controller_addr: str,
                 store_path: str, node_id: str, worker_id: bytes,
                 session_dir: str):
        self.nodelet_addr = nodelet_addr
        self.controller_addr = controller_addr
        self.node_id = node_id
        self.worker_id = worker_id
        self.session_dir = session_dir
        self.store = store_client.StoreClient(store_path)
        self.server = rpc.RpcServer("127.0.0.1", 0)
        self.nodelet: Optional[rpc.Connection] = None
        self.controller: Optional[rpc.Connection] = None
        self.fn_cache: Dict[bytes, Any] = {}
        self.actor_instance: Any = None
        self.actor_id: Optional[bytes] = None
        self.actor_max_concurrency = 1
        self.executor = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        # Concurrency groups (reference: ConcurrencyGroupManager,
        # core_worker/transport/concurrency_group_manager.h): named method
        # groups each with their own executor (sync) + semaphore (async).
        self._group_pools: Dict[str, concurrent.futures.ThreadPoolExecutor] = {}
        self._group_sems: Dict[str, asyncio.Semaphore] = {}
        self._seq_state: Dict[int, Dict[str, Any]] = {}  # conn id -> ordering
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._pinned_args: set = set()
        self._dying = False
        for name in ("push_task", "create_actor", "push_actor_task", "ping",
                     "exit", "actor_checkpoint", "cancel_task",
                     "chaos_update"):
            self.server.register(name, getattr(self, "_h_" + name))
        self._running_threads: Dict[bytes, int] = {}   # task_id -> thread id
        self._running_aio: Dict[bytes, Any] = {}       # task_id -> aio task
        self._inflight: set = set()            # pushed, not yet replied
        self._cancel_requested: set = set()    # cancel seen pre-user-code
        # task start/finish observability events batch up and flush on a
        # short timer — two notify RPCs per task would otherwise cost more
        # than a noop task itself on the control-plane hot path
        self._ts_buf: List[Dict[str, Any]] = []
        self._ts_flush = asyncio.Event()
        # wall clock at which the actor-creation task reached this
        # worker, until `mark_actor_init` has made a span of it
        self._actor_handed: Optional[float] = None
        global _runtime_singleton
        _runtime_singleton = self

    # ------------------------------------------------------------------ setup
    async def start(self):
        self._loop = asyncio.get_event_loop()
        await self.server.start()
        host, port = self.nodelet_addr.rsplit(":", 1)
        # The nodelet pushes actor-creation tasks back over this connection,
        # so it shares the server's handler table.
        self.nodelet = await rpc.connect(host, int(port),
                                         handlers=dict(self.server.handlers),
                                         retries=GlobalConfig.rpc_connect_retries)
        self.controller, _ep, _st = await rpc.connect_leader(
            self.controller_addr, retries=GlobalConfig.rpc_connect_retries)
        # no "pid" on the wire: the nodelet owns the authoritative pid
        # from the spawn path (Popen / zygote fork reply) on every
        # worker it tracks
        reply = await self.nodelet.call("register_worker", {
            "worker_id": self.worker_id, "port": self.server.port})
        GlobalConfig.load_snapshot(reply.get("config", {}))
        from ..util import fault_injection as fi
        fi.maybe_arm_from_config()
        # nodelet died -> die, leaving the span file behind (an exit
        # already under way keeps its code)
        def nodelet_gone(conn):
            if not self._dying:
                from ..util import tracing
                tracing.write_span_file(self.session_dir)
                os._exit(1)
        self.nodelet.on_close = nodelet_gone
        asyncio.ensure_future(self._task_state_flusher())
        from ..util import tracing
        tracing.configure("worker", self.node_id)
        asyncio.ensure_future(self._trace_flush_loop())
        return self

    # ------------------------------------------------- task-state batching
    def _report_task_state(self, event: Dict[str, Any]) -> None:
        self._ts_buf.append(event)
        self._ts_flush.set()

    async def _task_state_flusher(self):
        """Event-driven: an IDLE worker parks here with ZERO timer wakeups
        (a thousand idle actors polling every 50 ms would saturate a small
        host by themselves); a busy worker flushes at most every 50 ms."""
        while not self._dying:
            await self._ts_flush.wait()
            await asyncio.sleep(0.05)   # coalesce a burst into one notify
            self._ts_flush.clear()
            if not self._ts_buf:
                continue
            buf, self._ts_buf = self._ts_buf, []
            try:
                await self.nodelet.notify(
                    "task_state_batch",
                    {"worker_id": self.worker_id, "events": buf})
            except Exception:
                pass  # observability only; never kill the worker for it

    async def _trace_flush_loop(self):
        """Ship the spans this worker recorded since the last tick to
        the controller (see util/tracing.py).  This worker's lazy
        CoreClient defers to us via claim_flusher."""
        from ..util import tracing
        if not tracing.claim_flusher():
            return
        while not self._dying:
            await asyncio.sleep(GlobalConfig.trace_flush_interval_s)
            if self.controller is not None and self.controller.closed:
                # controller restarted or a standby was promoted: it
                # holds no spans (they never go through the WAL) —
                # re-ship our FULL ring so the new leader's timeline
                # regains this process's history
                tracing.mark_dirty()
            batch = tracing.flush_batch()
            if batch is None:
                continue
            await tracing.flush_sent(
                lambda: self._ship_spans(batch, timeout=10))

    async def _ship_spans(self, batch: dict, timeout: float):
        conn = await self._controller_conn()
        return await conn.call("trace_append", batch, timeout=timeout)

    async def final_span_flush(self):
        """Last-gasp span flush on the way out: the flush loop ticks
        every trace_flush_interval_s, so up to one interval of spans
        (the task that was running when this worker was told to die)
        sits only in the local ring.  The controller RETAINS each
        exited process's spans, so flushing here is what makes a killed
        worker's last spans appear in state.timeline() (the span file
        that outlives the cluster is `request_exit`'s)."""
        from ..util import tracing
        try:
            batch = tracing.flush_batch()
            if batch is not None:
                await self._ship_spans(batch, timeout=2.0)
        except Exception:
            pass  # exiting anyway; observability must not block death

    async def _controller_conn(self) -> rpc.Connection:
        """Redial the controller when the connection dropped (it restarts
        at the same address, or a hot standby from the address list got
        promoted — core/ha.py; reference: GCS clients reconnecting
        through gcs_rpc_client).  Without this, every worker permanently
        lost its function table / KV / actor reporting after a
        controller restart — the chaos controller-kill scenario caught
        it."""
        if self.controller is None or self.controller.closed:
            self.controller, _ep, _st = await rpc.connect_leader(
                self.controller_addr,
                retries=GlobalConfig.rpc_connect_retries)
        return self.controller

    async def _h_chaos_update(self, conn, data):
        """Runtime fault-plan push, forwarded by our nodelet."""
        from ..util import fault_injection as fi
        plan = data.get("plan")
        if plan:
            fi.arm(plan)
        else:
            fi.disarm()
        return True

    async def _chaos_site(self, site: str, key: str) -> None:
        """Apply an armed rule at a worker execution site.  ``crash``
        exits the process (after a best-effort injection report to the
        nodelet — this registry dies with us and worker registries are
        never scraped anyway); ``once`` crashes are claimed through the
        controller so exactly one process cluster-wide takes the hit."""
        act = await _chaos.async_point(site, key)
        if act is None:
            return
        if act["action"] == "crash":
            from ..util import fault_injection as fi
            if act["once"] and not await self._chaos_claim(act["rule_id"]):
                return
            try:
                await self.nodelet.notify("chaos_injected",
                                          {"site": site, "action": "crash"})
            except Exception:
                pass
            os._exit(fi.CRASH_EXIT_CODE)
        if act["action"] in ("sigkill", "sigsegv", "sigabrt"):
            # die by REAL signal: unlike `crash` (reserved exit code)
            # or the lease-level kill_worker (pre-attributed chaos),
            # the nodelet's classifier sees a genuine signal death —
            # poison-shaped, counting toward quarantine.  That is the
            # point: this site exercises the containment machinery.
            import signal as _sig
            signo = {"sigkill": _sig.SIGKILL, "sigsegv": _sig.SIGSEGV,
                     "sigabrt": _sig.SIGABRT}[act["action"]]
            if act["once"] and not await self._chaos_claim(act["rule_id"]):
                return
            try:
                await self.nodelet.notify(
                    "chaos_injected", {"site": site,
                                       "action": act["action"]})
            except Exception:
                pass
            os.kill(os.getpid(), signo)
            await asyncio.sleep(5)  # SIGKILL delivery is not instant
        if act["action"] == "error":
            raise exceptions.RayTpuError(
                f"chaos: injected error at {site} ({key})")

    async def _chaos_claim(self, rule_id: str) -> bool:
        from ..util import fault_injection as fi
        try:
            conn = await self._controller_conn()
            return bool(await conn.call("chaos_claim", {"id": rule_id},
                                        timeout=5))
        except Exception:
            return fi.local_claim(rule_id)

    async def run_forever(self):
        await asyncio.Event().wait()   # `request_exit` ends the process

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.server.port}"

    # -------------------------------------------------------------- execution
    async def _resolve_args(self, spec: TaskSpec):
        """Returns (args, kwargs, views-to-release)."""
        flat: List[Any] = []
        views: List[bytes] = []
        for kind, payload in spec.args:
            if kind == ARG_VALUE:
                flat.append(serialization.deserialize(memoryview(payload)))
            else:
                oid = payload
                view = self.store.get(oid, timeout_ms=0)
                if view is not None and oid in self._pinned_args:
                    self.store.release(oid)  # one pin per object is enough
                if view is None:
                    spilled = await self._read_spilled(oid)
                    if spilled is not None:
                        value = serialization.deserialize(
                            memoryview(spilled))
                        if isinstance(value, _ErrorValue):
                            raise value.unwrap(spec.function_name)
                        flat.append(value)
                        continue
                    r = await self.nodelet.call("pull", {"object_id": oid},
                                                timeout=60)
                    if not r.get("ok"):
                        raise exceptions.ObjectLostError(oid.hex(), r.get("error", ""))
                    view = self.store.get(oid, timeout_ms=5000)
                    if view is None:
                        raise exceptions.ObjectLostError(oid.hex(), "pull raced eviction")
                self._pinned_args.add(oid)
                views.append(oid)
                value = serialization.deserialize(view)
                if isinstance(value, _ErrorValue):
                    raise value.unwrap(spec.function_name)
                flat.append(value)
        # Last element is the kwargs dict marker produced by the submitter.
        *args, kwargs = flat
        return args, kwargs, views

    async def _read_spilled(self, oid: bytes):
        from . import spill
        raw = await self._ctl_call_retry("kv_get", spill.kv_entry(oid))
        if not raw:
            return None
        return spill.read_file(raw.decode())

    async def _get_function(self, fid: bytes):
        fn = self.fn_cache.get(fid)
        if fn is None:
            blob = await self._ctl_call_retry(
                "kv_get", {"ns": FN_NAMESPACE, "key": fid})
            if blob is None:
                raise exceptions.RayTpuError(f"function {fid.hex()[:12]} not registered")
            from . import kvref
            if kvref.is_ref(blob):
                # big blob diverted off the control plane: the KV holds
                # only a marker, the payload rides the object plane
                try:
                    blob = await self._fetch_kvref(kvref.unpack(blob))
                except exceptions.ObjectLostError as e:
                    # the marker survived but its blob is gone (owner
                    # died, spill file corrupted/lost): typed + tagged
                    # so the driver re-registers from its cached blob
                    # and requeues instead of failing the task on an
                    # opaque KeyError
                    raise exceptions.FunctionUnavailableError(
                        fid.hex(), str(e)) from e
            fn = serialization.loads_function(blob)
            self.fn_cache[fid] = fn
        return fn

    async def _fetch_kvref(self, oid: bytes) -> bytes:
        """Materialize a KV ref marker's payload from the object plane
        (local shm hit, else nodelet pull)."""
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            r = await self.nodelet.call("pull", {"object_id": oid},
                                        timeout=60)
            if not r.get("ok"):
                raise exceptions.ObjectLostError(oid.hex(), r.get("error", ""))
            view = self.store.get(oid, timeout_ms=5000)
            if view is None:
                raise exceptions.ObjectLostError(oid.hex(),
                                                 "pull raced eviction")
        try:
            return serialization.deserialize(view)
        finally:
            self.store.release(oid)

    async def _ctl_call_retry(self, method: str, data, timeout: float = 30.0):
        """Controller call that rides out a controller restart/failover:
        an in-flight call dies with the leader's connection, which used
        to fail the TASK (function-table fetch racing a controller kill
        — the task errored with ConnectionLost instead of retrying
        against the restarted/promoted controller)."""
        deadline = time.monotonic() + \
            GlobalConfig.ha_client_failover_timeout_s
        while True:
            try:
                conn = await self._controller_conn()
                r = await conn.call(method, data, timeout=timeout)
                if type(r) is dict and r.get("_overload"):
                    # controller shedding bulk ops: honor Retry-After
                    ra = float(r.get("retry_after_s") or 1.0)
                    if time.monotonic() + ra > deadline:
                        raise exceptions.ControlPlaneOverloadError(
                            method, ra)
                    await asyncio.sleep(ra * rpc._jitter())
                    continue
                return r
            except (rpc.ConnectionLost, OSError):
                if time.monotonic() > deadline:
                    raise
                await asyncio.sleep(0.2)

    async def _store_returns(self, spec: TaskSpec, result: Any) -> List[dict]:
        nret = spec.num_returns
        # dynamic: result was already materialized into an
        # ObjectRefGenerator by _execute — ONE top-level return
        values = [result] if nret in (1, DYNAMIC_RETURNS) \
            else list(result)
        if nret > 1 and len(values) != nret:
            raise ValueError(f"task {spec.function_name} declared {nret} returns "
                             f"but produced {len(values)}")
        out = []
        for i, value in enumerate(values):
            oid = spec.return_ids()[i].binary()
            contained: List[bytes] = []
            parts = serialization.serialize(value, ref_collector=contained)
            size = serialization.serialized_size(parts)
            if contained:
                # Containment pin keyed on the return object: nested refs
                # stay alive until the caller frees the container
                # (reference_count.h "contained in owned object" edges).
                conn = await self._controller_conn()
                await conn.notify("ref_inc", {
                    "object_ids": contained, "holder": f"obj:{oid.hex()}"})
                # a nested ref whose value lives only in THIS worker's
                # private memory store (e.g. a small api.put here) must
                # be shared or the caller can never fetch it
                core = _get_worker_core()
                if core is not None:
                    for b in contained:
                        await self._loop.run_in_executor(
                            None, core._promote_to_plasma, b)
            if size <= GlobalConfig.max_direct_call_object_size:
                out.append({"inline": b"".join(bytes(p) for p in parts),
                            "contained": bool(contained)})
            else:
                for attempt in range(
                        GlobalConfig.spill_backpressure_retries + 1):
                    try:
                        self.store.put_parts(oid, parts)
                        # Bridge pin until the nodelet takes its primary pin —
                        # same LRU-race close as the driver put path: under
                        # store pressure an unpinned return value could be
                        # evicted before put_location pins it.
                        bridge = self.store.get(oid, timeout_ms=0) is not None
                        try:
                            await self.nodelet.call(
                                "put_location", {"object_id": oid, "size": size})
                        finally:
                            if bridge:
                                self.store.release(oid)
                        break
                    except store_client.StoreFullError:
                        from . import spill
                        try:
                            # off-loop: spilled returns can be arbitrarily
                            # large, and this loop also serves ping/cancel
                            # (PR-13 loop-blocking lint)
                            path = await asyncio.to_thread(
                                spill.write_object, oid, parts)
                        except OSError as e:
                            # store full AND spill disk faulting
                            # (ENOSPC/EIO): backpressure — wait for the
                            # store to drain or the disk to clear, then
                            # retry the in-memory put first.  Exhausted
                            # retries surface a TYPED retriable error,
                            # never a bare OSError task failure.
                            spill.count_fault(spill.SPILL_WRITE_SITE,
                                              "backpressured")
                            if attempt >= \
                                    GlobalConfig.spill_backpressure_retries:
                                raise exceptions.StorageDegradedError(
                                    f"return {oid.hex()[:12]}: store full "
                                    f"and spill failed: {e}",
                                    retry_after_s=GlobalConfig.
                                    spill_backpressure_delay_s) from e
                            await asyncio.sleep(
                                GlobalConfig.spill_backpressure_delay_s
                                * rpc._jitter())
                            continue
                        conn = await self._controller_conn()
                        await conn.call(
                            "kv_put", {**spill.kv_entry(oid),
                                       "value": path.encode()})
                        break
                out.append({"plasma": size, "contained": bool(contained)})
        return out

    def _run_user_code(self, fn, args, kwargs, task_id=None, spec=None):
        if task_id is not None:
            if task_id in self._cancel_requested:
                # cancelled while queued in the executor (before any
                # thread/aio registration existed to interrupt)
                raise exceptions.TaskCancelledError("task was cancelled")
            self._running_threads[task_id] = threading.get_ident()
        token = _current_spec.set(spec) if spec is not None else None
        try:
            return fn(*args, **kwargs)
        finally:
            if token is not None:
                _current_spec.reset(token)
            if task_id is not None:
                self._running_threads.pop(task_id, None)

    async def _h_cancel_task(self, conn, data):
        """In-band task cancellation (reference: CancelTask RPC +
        KillActor-style force).  Sync tasks get TaskCancelledError raised
        asynchronously in their thread; asyncio tasks are cancelled at
        their next await; tasks still queued worker-side trip the
        cancel-requested flag before user code starts; force exits the
        process (the driver converts the dead-worker error into the
        cancel).  A task NOT in flight here is a no-op — force must not
        kill a worker over a task that already finished."""
        tid = data["task_id"]
        if tid not in self._inflight:
            return False
        if data.get("force"):
            os._exit(1)
        self._cancel_requested.add(tid)
        aio = self._running_aio.get(tid)
        if aio is not None:
            aio.cancel()
            return True
        ident = self._running_threads.get(tid)
        if ident is not None:
            import ctypes
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(ident),
                ctypes.py_object(exceptions.TaskInterruptedByCancel))
        return True

    def _is_async(self, fn) -> bool:
        # async GENERATOR methods are async too (dynamic returns
        # dispatch them on the event-loop lane) — they must earn the
        # async-actor default concurrency cap like coroutines do
        return inspect.iscoroutinefunction(fn) \
            or inspect.isasyncgenfunction(fn) \
            or inspect.iscoroutinefunction(getattr(fn, "__call__",
                                                   None)) \
            or inspect.isasyncgenfunction(getattr(fn, "__call__",
                                                  None))

    async def _run_target(self, spec: TaskSpec, fn, args, kwargs):
        """Dispatch to the right execution lane.

        Async methods run NATIVELY on the worker's event loop (the role
        boost fibers play in the reference, core_worker/fiber.h) bounded by
        their concurrency-group semaphore; sync methods run on the group's
        thread pool.  Both lanes honor per-task runtime envs."""
        import inspect
        renv = spec.runtime_env
        tid = spec.task_id.binary()
        group = spec.concurrency_group or "_default"
        if self._is_async(fn):
            sem = self._group_sems.get(group) or self._group_sems.get(
                "_default")
            if sem is None:
                sem = self._group_sems["_default"] = asyncio.Semaphore(
                    max(1, self.actor_max_concurrency))
            async with sem:
                if tid in self._cancel_requested:
                    raise exceptions.TaskCancelledError(
                        "task was cancelled")  # cancelled behind the sem
                # cancel_task targets this handler task; the conversion
                # below keeps the cancellation in-band (error reply, not a
                # torn connection)
                self._running_aio[tid] = asyncio.current_task()
                token = _current_spec.set(spec)
                try:
                    if renv:
                        from . import runtime_env as _renv
                        with _renv.applied(renv):
                            return await fn(*args, **kwargs)
                    return await fn(*args, **kwargs)
                except asyncio.CancelledError:
                    cur = asyncio.current_task()
                    if hasattr(cur, "uncancel"):
                        cur.uncancel()
                    raise exceptions.TaskCancelledError(
                        f"task {spec.function_name} was cancelled") from None
                finally:
                    _current_spec.reset(token)
                    self._running_aio.pop(tid, None)
        pool = self._group_pools.get(group, self.executor)
        if renv:
            from . import runtime_env as _renv

            def run_in_env():
                with _renv.applied(renv):
                    return self._run_user_code(fn, args, kwargs,
                                               task_id=tid, spec=spec)

            result = await self._loop.run_in_executor(pool, run_in_env)
        else:
            result = await self._loop.run_in_executor(
                pool, self._run_user_code, fn, args, kwargs, tid, spec)
        if inspect.iscoroutine(result):
            result = await result  # sync wrapper returned a coroutine
        return result

    @staticmethod
    def _dynamic_wrapper(fn, fname: str):
        """num_returns="dynamic": exhaust the user's generator INSIDE
        the normal execution lane — the generator body must see the
        task's runtime_env, current-spec context, and cancellation
        registration, and must run on the executor thread, none of
        which hold once the lazily-evaluated generator escapes to the
        event loop.  Async functions keep their async dispatch: the
        wrapper mirrors the wrapped function's color."""
        def _listify(out):
            try:
                return list(iter(out))
            except TypeError:
                raise TypeError(
                    f"task {fname} declared num_returns='dynamic' but "
                    f"returned non-iterable "
                    f"{type(out).__name__}") from None

        if inspect.isasyncgenfunction(fn):
            @functools.wraps(fn)
            async def agen_wrapper(*args, **kwargs):
                return [item async for item in fn(*args, **kwargs)]
            return agen_wrapper
        if inspect.iscoroutinefunction(fn):
            @functools.wraps(fn)
            async def coro_wrapper(*args, **kwargs):
                return _listify(await fn(*args, **kwargs))
            return coro_wrapper

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return _listify(fn(*args, **kwargs))
        return wrapper

    async def _materialize_dynamic(self, spec: TaskSpec, values: list):
        """Store each already-evaluated yielded value as its own object
        via api.put (the existing nested-ref machinery owns promotion,
        containment pins, and borrows — reference: _raylet.pyx dynamic
        return generators) and return an ObjectRefGenerator as the
        single top-level value.  Puts are independent: they overlap on
        the loop's default pool (NOT self.executor — that is the user
        sync lane, where queuing behind a long user method could even
        deadlock a caller waiting on these results); gather preserves
        yield order."""
        from .. import api
        from .driver import ObjectRefGenerator
        refs = await asyncio.gather(*[
            self._loop.run_in_executor(None, api.put, item)
            for item in values])
        return ObjectRefGenerator(list(refs))

    async def _execute(self, spec: TaskSpec, fn,
                       durs: Optional[Dict[str, float]] = None) -> dict:
        # NB: store pins taken while resolving reference args are *not*
        # released after execution — deserialization is zero-copy, so user
        # code (e.g. an actor stashing an argument array) may alias store
        # memory indefinitely.  Pins are deduped per object and dropped only
        # when the worker exits (reference plasma has the same client-side
        # pin-while-mapped semantics).
        from ..util import tracing
        tr = {"task_id": spec.task_id.hex(), "trace": spec.trace_id}
        fname = spec.function_name
        try:
            if _chaos is not None:
                # signal-kill at execution start: a real signal death the
                # nodelet classifies as poison (feeds the crash ledger)
                await self._chaos_site("worker.exec_crash", fname)
            t0 = time.time()
            args, kwargs, _views = await self._resolve_args(spec)
            t1 = time.time()
            tracing.record_span(f"fetch::{fname}", "fetch", t0, t1, **tr)
            dynamic = spec.num_returns == DYNAMIC_RETURNS
            if dynamic:
                fn = self._dynamic_wrapper(fn, spec.function_name)
            result = await self._run_target(spec, fn, args, kwargs)
            t2 = time.time()
            tracing.record_span(f"exec::{fname}", "exec", t1, t2, **tr)
            if dynamic:
                result = await self._materialize_dynamic(spec, result)
            if _chaos is not None:
                # crash-BEFORE-put: the result never reached the store,
                # the caller's retry re-executes from scratch
                await self._chaos_site("worker.before_put", fname)
            returns = await self._store_returns(spec, result)
            if _chaos is not None:
                # crash-AFTER-put: the object exists but the reply is
                # lost — the retry must be idempotent against it
                await self._chaos_site("worker.after_put", fname)
            t3 = time.time()
            tracing.record_span(f"put::{fname}", "put", t2, t3, **tr)
            if durs is not None:
                durs.update(fetch=t1 - t0, exec=t2 - t1, put=t3 - t2)
            # Borrow barrier: refs deserialized during this task registered
            # borrows via fire-and-forget notifies on the worker-core's own
            # controller connection; the caller drops its argument pins the
            # moment it sees this reply, so those borrows must be visible at
            # the controller FIRST or its deferred-free gate races open
            # (reference ships borrower lists in the reply itself).
            core = _get_worker_core()
            if core is not None:
                await self._loop.run_in_executor(None, core.sync_borrows)
            return {"returns": returns}
        except Exception as e:
            tb = traceback.format_exc()
            try:
                pickled = serialization.dumps_function(e)
            except Exception:
                pickled = None
            return {"error": {"traceback": tb, "pickled": pickled,
                              "fname": spec.function_name}}

    # --------------------------------------------------------------- handlers
    async def _h_push_task(self, conn, data):
        if self._dying:
            return {"error": {"traceback": "worker is exiting", "pickled": None,
                              "fname": "", "dying": True}}
        spec = TaskSpec.from_wire(data["spec"])
        tid = spec.task_id.binary()
        # in-flight from the FIRST moment a cancel could name this task —
        # the function fetch below can take a while and a cancel arriving
        # during it must not be dropped
        self._inflight.add(tid)
        try:
            return await self._push_task_body(spec)
        finally:
            self._inflight.discard(tid)
            self._cancel_requested.discard(tid)

    async def _push_task_body(self, spec: TaskSpec):
        try:
            fn = await self._get_function(spec.function_id)
        except exceptions.FunctionUnavailableError:
            # the function's kvref blob is gone, not a user error: tag
            # the reply so the driver re-registers the function from its
            # cached blob and requeues (bounded) without burning the
            # task's retry budget
            return {"error": {"traceback": traceback.format_exc(),
                              "pickled": None, "fname": spec.function_name,
                              "fn_lost": spec.function_id.hex()}}
        except Exception:
            # Function-table / unpickling failures are user errors, not
            # transport errors: report in-band so the driver doesn't treat a
            # healthy worker as crashed.
            return {"error": {"traceback": traceback.format_exc(),
                              "pickled": None, "fname": spec.function_name}}
        # Task-state observability: the nodelet keeps the per-worker task
        # table the reference's core worker reports to the GCS
        # (task_manager / state API `ray list tasks`); pushes go direct
        # driver→worker, so the nodelet can't see them itself.
        self._report_task_state({"event": "start",
                                 "name": spec.function_name,
                                 "task_id": spec.task_id.binary(),
                                 "t": time.time()})
        durs: Dict[str, float] = {}
        try:
            tp = spec.d.get("otel")
            if tp:
                # execution span parented to the driver's submit span
                # (reference: _inject_tracing_into_execution); no-op
                # unless this worker registered a tracer provider
                from ..util import otel
                with otel.execute_span(spec.function_name, tp):
                    return await self._execute(spec, fn, durs)
            return await self._execute(spec, fn, durs)
        finally:
            self._report_task_state({"event": "finish",
                                     "name": spec.function_name,
                                     "durs": durs,
                                     "t": time.time()})

    async def _h_create_actor(self, conn, data):
        spec = TaskSpec.from_wire(data["spec"])
        self._actor_handed = time.time()
        try:
            cls = await self._get_function(spec.function_id)
            args, kwargs, _ = await self._resolve_args(spec)
            if spec.runtime_env:
                from . import runtime_env as _renv
                _renv.apply(spec.runtime_env)  # actor keeps env for life
            def construct():
                # the constructor runs AS the creation task: expose its
                # spec so __init__ bodies can read runtime context
                # (trace id, submit stamp — e.g. serve replicas
                # attribute their cold start from t_submit).  Executor
                # threads don't inherit the loop's contextvars, so set
                # and reset around the call.
                token = _current_spec.set(spec)
                try:
                    return cls(*args, **kwargs)
                finally:
                    _current_spec.reset(token)
            self.actor_instance = await self._loop.run_in_executor(
                self.executor, construct)
            self.actor_id = spec.actor_creation_id.binary()
            self.actor_max_concurrency = max(1, spec.max_concurrency)
            if self.actor_max_concurrency > 1:
                self.executor = concurrent.futures.ThreadPoolExecutor(
                    max_workers=self.actor_max_concurrency)
            # Async actors get real event-loop concurrency even without an
            # explicit max_concurrency (reference defaults async actors to
            # a large cap — fiber.h).
            has_async = any(
                self._is_async(getattr(self.actor_instance, m))
                for m in dir(self.actor_instance) if not m.startswith("_")
                and callable(getattr(self.actor_instance, m, None)))
            default_cap = self.actor_max_concurrency
            if has_async and spec.max_concurrency <= 1:
                default_cap = 100
            self._group_sems["_default"] = asyncio.Semaphore(default_cap)
            self.concurrency_groups = dict(spec.concurrency_groups)
            for gname, cap in self.concurrency_groups.items():
                self._group_pools[gname] = \
                    concurrent.futures.ThreadPoolExecutor(
                        max_workers=max(1, int(cap)))
                self._group_sems[gname] = asyncio.Semaphore(max(1, int(cap)))
            conn2 = await self._controller_conn()
            await conn2.call("actor_alive", {
                "actor_id": self.actor_id, "address": self.address,
                "worker_id": self.worker_id, "node_id": self.node_id})
            return {"ok": True}
        except Exception:
            return {"ok": False, "error": traceback.format_exc()}

    async def _h_push_actor_task(self, conn, data):
        """Execute an actor method in per-caller seq order."""
        spec = TaskSpec.from_wire(data["spec"])
        if self._dying:
            return {"error": {"traceback": "actor is exiting (killed)",
                              "pickled": None, "fname": spec.function_name,
                              "dying": True}}
        if self.actor_instance is None:
            return {"error": {"traceback": "actor instance not created",
                              "pickled": None, "fname": spec.function_name}}
        method = getattr(self.actor_instance, spec.function_name, None)
        if method is None:
            return {"error": {"traceback": f"no method {spec.function_name}",
                              "pickled": None, "fname": spec.function_name}}
        state = self._seq_state.setdefault(
            id(conn), {"next": 0, "waiters": {}})
        seq = spec.actor_seq
        # Per-caller FIFO applies to plain sync actors; async methods and
        # concurrency-group methods execute out of order up to their caps
        # (reference: ActorSchedulingQueue vs OutOfOrderActorSchedulingQueue
        # + fiber.h async actors).
        ordered = self.actor_max_concurrency == 1 \
            and not self._is_async(method) \
            and not spec.concurrency_group
        if ordered:
            # eligible once every earlier seq (ordered or not) has finished:
            # unordered completions advance "next" monotonically too
            while state["next"] < seq:
                ev = state["waiters"].setdefault(seq, asyncio.Event())
                await ev.wait()
                state["waiters"].pop(seq, None)
        try:
            self._report_task_state({
                "event": "start",
                "name": f"{type(self.actor_instance).__name__}."
                        f"{spec.function_name}",
                "task_id": spec.task_id.binary(), "t": time.time()})
            durs: Dict[str, float] = {}
            try:
                tp = spec.d.get("otel")
                if tp:
                    from ..util import otel
                    with otel.execute_span(spec.function_name, tp):
                        return await self._execute(spec, method, durs)
                return await self._execute(spec, method, durs)
            finally:
                self._report_task_state({
                    "event": "finish",
                    "name": f"{type(self.actor_instance).__name__}."
                            f"{spec.function_name}", "durs": durs,
                    "t": time.time()})
        finally:
            if state["next"] <= seq:
                state["next"] = seq + 1
            for s2 in list(state["waiters"]):
                if s2 <= state["next"]:
                    state["waiters"].pop(s2).set()

    async def _h_actor_checkpoint(self, conn, data):
        """Optional user hook: actors exposing __save__/__restore__."""
        if self.actor_instance is None or not hasattr(self.actor_instance, "__save__"):
            return None
        return serialization.serialize_to_bytes(self.actor_instance.__save__())

    async def _h_ping(self, conn, data):
        return "pong"

    async def _h_exit(self, conn, data):
        self._dying = True
        # Drain any batched task-state events (a finish sitting in the
        # 50 ms coalesce window would otherwise leave a stale "running"
        # row in the nodelet for the life of the cluster).
        if self._ts_buf:
            buf, self._ts_buf = self._ts_buf, []
            try:
                await self.nodelet.notify(
                    "task_state_batch",
                    {"worker_id": self.worker_id, "events": buf})
            except Exception:
                pass
        if self.actor_instance is not None and self.actor_id is not None:
            try:
                conn = await self._controller_conn()
                await conn.call("report_actor_death", {
                    "actor_id": self.actor_id, "reason": "ray_tpu.kill",
                    "intended": not data.get("restart", False)})
            except (rpc.RpcError, OSError):
                pass
        await self.final_span_flush()
        self.request_exit(0)
        return True

    def request_exit(self, code: int = 0) -> None:
        """Exit this worker by ``os._exit`` — no teardown to hang on a
        broken connection.  That holds for a worker with the chip open
        too: the chip is free for the next claimant once this process is
        gone, and the nodelet gives the ``TPU`` reservation back only
        after it has seen the process exit (`Nodelet._on_worker_death`)."""
        self._dying = True
        # the span file first, here and not on the loop: the hard exit
        # below does not wait for it, and a replica's ring takes longer
        # to write than the timer gives
        from ..util import tracing
        tracing.write_span_file(self.session_dir)
        # best-effort last span flush on the loop before the hard exit
        # below (the _h_exit path already awaited one; SIGTERM and crash
        # exits land here directly)
        if self._loop is not None and not self._loop.is_closed():
            try:
                asyncio.run_coroutine_threadsafe(self.final_span_flush(),
                                                 self._loop)
            except RuntimeError:
                pass
        t = threading.Timer(0.05, lambda: os._exit(code))
        t.daemon = True
        t.start()


class _ErrorValue:
    """A stored value representing a task failure; getting it re-raises."""

    def __init__(self, traceback_str: str, pickled: Optional[bytes], fname: str,
                 is_actor: bool = False, actor_down: bool = False):
        self.traceback_str = traceback_str
        self.pickled = pickled
        self.fname = fname
        self.is_actor = is_actor
        # the ACTOR (not the request) failed: killed mid-call, worker
        # crashed, creation gave up — surfaces as the TYPED
        # ActorDiedError so callers (e.g. the Serve router) can retry
        # on another replica without substring-sniffing messages
        self.actor_down = actor_down

    def unwrap(self, context_fname: str = "") -> Exception:
        cause = None
        if self.pickled is not None:
            try:
                cause = serialization.loads_function(self.pickled)
            except Exception:
                cause = None
        if isinstance(cause, exceptions.TaskCancelledError):
            return cause  # ray.cancel surfaces AS TaskCancelledError
        if isinstance(cause, (exceptions.PoisonTaskError,
                              exceptions.ReconstructionDepthError)):
            return cause  # containment errors surface typed, not wrapped
        if isinstance(cause, exceptions.ActorQuarantinedError):
            # subclasses ActorDiedError but carries the quarantine
            # evidence — must win over the generic actor_down path
            return cause
        if getattr(self, "actor_down", False):
            return exceptions.ActorDiedError("", self.traceback_str)
        cls = exceptions.ActorError if self.is_actor else exceptions.TaskError
        return cls(self.fname or context_fname, self.traceback_str, cause)
