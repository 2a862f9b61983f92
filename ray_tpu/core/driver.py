"""Core client: task/actor submission and object operations.

The Python equivalent of the reference's core worker + direct task transport
(/root/reference/src/ray/core_worker/core_worker.cc SubmitTask :1629 /
Get :1142 / Put :935; transport/direct_task_transport.cc lease pipelining).
One ``CoreClient`` lives in every driver *and* every worker process (workers
use it for nested ``remote()``/``get()`` calls), running its networking on a
dedicated event-loop thread.

Hot path: specs with the same scheduling key share worker leases — the driver
pushes tasks directly to leased workers over persistent connections, going
back to the nodelet only to acquire/return leases (reference: OnWorkerIdle,
direct_task_transport.cc:174).
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import os
import random
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from .. import exceptions
from . import rpc, runtime_metrics as rtm, serialization, spill
from .config import GlobalConfig
from .ids import ActorID, JobID, ObjectID, PlacementGroupID, TaskID, WorkerID
from .memory_store import IN_PLASMA, MemoryStore
from .object_store import client as store_client
from .task_spec import ARG_REF, ARG_VALUE, TaskSpec
from .worker_runtime import FN_NAMESPACE, _ErrorValue


class ObjectRefGenerator:
    """The value of a ``num_returns="dynamic"`` task's single return
    (reference: _raylet.pyx ObjectRefGenerator): an indexable,
    iterable sequence of ObjectRefs the WORKER minted at execution
    time, one per yielded item.  It is a plain container of refs, so
    the existing nested-ref machinery (containment pins, borrow
    registration on deserialize, plasma promotion) carries all of its
    lifetime semantics."""

    __slots__ = ("_refs",)

    def __init__(self, refs):
        self._refs = list(refs)

    def __iter__(self):
        return iter(self._refs)

    def __len__(self):
        return len(self._refs)

    def __getitem__(self, i):
        return self._refs[i]

    def __repr__(self):
        return f"ObjectRefGenerator({len(self._refs)} refs)"


class DeferredRefDecs:
    """GC-safe ref-release queue, shared by CoreClient and ClientCore.

    ObjectRef.__del__ may fire mid-allocation while its thread holds
    the owner's _ref_lock, so the GC path must never lock: it only
    appends here (atomic under the GIL).  Owners drain at entry points
    and from a periodic sweep — whose dispatch differs per owner (the
    driver sweeps on its IO loop, the client on a plain thread because
    its dec path BLOCKS on its own loop), so the sweep itself stays
    per-class."""

    def _init_deferred_decs(self) -> None:
        self._deferred_decs: list = []

    def _defer_remove_local_ref(self, oid: bytes) -> None:
        self._deferred_decs.append(oid)

    def _drain_deferred_decs(self) -> None:
        if not self._deferred_decs:     # hot path: every ObjectRef()
            return
        while True:
            try:
                oid = self._deferred_decs.pop()
            except IndexError:
                return
            try:
                self._remove_local_ref(oid)
            except Exception:
                # the old __del__ path swallowed dec errors too; one
                # failing dec must not kill the sweep or surface in an
                # unrelated caller's get()
                pass


class ObjectRef:
    """A handle to a (possibly pending) object (reference: ObjectRef in
    _raylet.pyx).  Dropping the last local reference releases the object."""

    __slots__ = ("_id", "_core", "__weakref__")

    def __init__(self, object_id: ObjectID, core: Optional["CoreClient"]):
        self._id = object_id
        self._core = core
        if core is not None:
            core._add_local_ref(object_id.binary())

    def binary(self) -> bytes:
        return self._id.binary()

    def hex(self) -> str:
        return self._id.hex()

    @property
    def id(self) -> ObjectID:
        return self._id

    def __reduce__(self):
        # Crossing a process boundary: the receiver resolves via the store.
        return (_deserialize_ref, (self._id.binary(),))

    def __del__(self):
        core = self._core
        if core is not None:
            try:
                # GC-safe path: __del__ can fire mid-allocation while
                # THIS thread holds core._ref_lock (observed as a
                # same-thread deadlock under memory pressure) — so the
                # GC path must never lock; the dec is queued and
                # applied at the next locked-free entry point
                core._defer_remove_local_ref(self._id.binary())
            except Exception:
                pass

    def __hash__(self):
        return hash(self._id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __repr__(self):
        return f"ObjectRef({self._id.hex()})"

    def future(self):
        """concurrent.futures.Future resolving to the object's value."""
        import concurrent.futures
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def _bg():
            try:
                fut.set_result(self._core.get([self], timeout=None)[0])
            except BaseException as e:
                fut.set_exception(e)
        threading.Thread(target=_bg, daemon=True).start()
        return fut


def _deserialize_ref(binary: bytes) -> "ObjectRef":
    core = get_global_core()
    if core is None:
        # Worker process deserializing a nested ref before its lazy core
        # exists: bring it up from the env context so the ref participates
        # in borrow counting (and so .get()/.future() work on it).
        try:
            from .. import api
            core = api._ensure_initialized()
        except Exception:
            core = None
    return ObjectRef(ObjectID(binary), core)


class _SchedulingKeyState:
    """Per-scheduling-key lease pool + task queue."""

    def __init__(self):
        self.queue: deque = deque()          # (spec, attempts_left)
        self.leases = 0                      # leases held or being acquired
        self.busy = 0                        # lease loops executing a task
        self.wakeup = asyncio.Event()
        # crash-site anti-affinity: node ids this key's workers recently
        # died on (from death-info evidence) — retries spread elsewhere
        self.avoid: set = set()


class _ActorState:
    def __init__(self, actor_id: bytes, class_name: str):
        self.actor_id = actor_id
        self.class_name = class_name
        self.conn: Optional[rpc.Connection] = None
        self.address: Optional[str] = None
        self.seq = 0
        self.lock: Optional[asyncio.Lock] = None
        self.dead_reason: Optional[str] = None
        self.quarantined = False   # crash-loop quarantine (typed error)


class CoreClient(DeferredRefDecs):
    def __init__(self, *, controller_addr: str, nodelet_addr: str,
                 store_path: str, node_id: str, session_dir: str,
                 job_id: Optional[JobID] = None, mode: str = "driver"):
        self.controller_addr = controller_addr
        self.nodelet_addr = nodelet_addr
        self.node_id = node_id
        self.session_dir = session_dir
        self.mode = mode
        self.job_id = job_id or JobID.from_int(os.getpid() & 0xFFFFFFFF)
        self.task_ctx = TaskID.for_driver(self.job_id)
        self.worker_id = WorkerID.from_random()
        self.memory_store = MemoryStore()
        self.store = store_client.StoreClient(store_path)
        self.lt = rpc.EventLoopThread(f"ray-tpu-{mode}-io")
        # node-membership listeners (serve routers evict dead/draining
        # replicas the moment the pubsub event lands, not at a poll TTL);
        # the handler is registered up front so it survives redials
        self._node_listeners: list = []
        self._node_sub_lock = threading.Lock()
        self._node_subscribed = False
        # HA-aware: the address may be a comma list (leader + hot
        # standbys); the client follows leadership and replays failed
        # calls against a promoted standby (core/ha.py)
        self.controller = rpc.BlockingClient.connect_ha(
            self.lt, controller_addr,
            handlers={"pub:logs": self._on_log,
                      "pub:nodes": self._on_nodes_pub},
            retries=GlobalConfig.rpc_connect_retries)
        self.controller.on_reconnect = self._on_controller_reconnect
        self.nodelet = rpc.BlockingClient.connect(
            self.lt, *_split(nodelet_addr),
            retries=GlobalConfig.rpc_connect_retries)
        self._put_index = 0
        self._fn_registered: set = set()
        # fid -> ObjectRef for function blobs diverted to the object
        # store (core/kvref.py): the owner must keep the payload alive
        self._fn_blob_refs: Dict[bytes, Any] = {}
        # fid -> raw serialized blob, kept for re-registration when a
        # worker reports the kvref payload lost (`fn_lost` replies)
        self._fn_blobs: Dict[bytes, bytes] = {}
        # tid -> count of fn_lost requeues (bounded: a blob that stays
        # lost after re-registration must not requeue forever)
        self._fn_requeues: Dict[bytes, int] = {}
        # credit-based submission flow control (core/overload.py): the
        # window refills via `credit_request` when it runs out
        self._credits = 0
        self._credit_lock = threading.Lock()
        self._ref_lock = threading.Lock()
        self._init_deferred_decs()
        # Submission coalescing: a burst of .remote() calls lands in
        # this queue and wakes the IO loop ONCE, not once per task —
        # run_coroutine_threadsafe costs ~100us each, which alone caps
        # a 10k-task burst at ~10k/s before any real work happens.
        self._submit_q: deque = deque()
        self._submit_scheduled = False
        self._submit_lock = threading.Lock()
        # oids shipped nested while their value was still pending: the
        # plasma promotion runs when the inline result arrives
        self._promote_on_arrival: set = set()
        self._local_refs: Dict[bytes, int] = {}
        self._owned: set = set()        # oids this process created (owner frees)
        self._plasma_oids: set = set()  # oids known to live in shared memory
        self._pinned: set = set()
        self._sched: Dict[tuple, _SchedulingKeyState] = {}
        self._actors: Dict[bytes, _ActorState] = {}
        self._worker_conns: Dict[str, rpc.Connection] = {}
        self._nodelet_conns: Dict[str, rpc.Connection] = {}
        self._closed = False
        self._lineage: "OrderedDict[bytes, TaskSpec]" = OrderedDict()
        self._spilled_paths: Dict[bytes, str] = {}
        self._containers: set = set()  # owned oids with contained-ref pins
        self._borrow_epoch = 0         # ref_incs issued (see sync_borrows)
        self._borrow_synced = 0
        self._extra_pins_map: Dict[bytes, List[bytes]] = {}  # in-flight nested pins
        self._value_finalizers: list = []  # detached at shutdown (segfault guard)
        self._state_conns: Dict[str, rpc.Connection] = {}  # state.py pool
        self._state_conns_lock = threading.Lock()
        self._cancelled: set = set()   # task_ids cancel() was called on
        self._task_sites: Dict[bytes, rpc.Connection] = {}  # running tasks
        self._spurious_requeues: Dict[bytes, int] = {}
        # Reconstruction-storm governance: concurrent _reconstruct calls
        # for the SAME oid collapse onto one in-flight future, and total
        # concurrent resubmissions are capped by the semaphore — an
        # evicted fan-out must not resubmit its producer N times.
        self._recon_lock = threading.Lock()
        self._recon_inflight: Dict[bytes, "cf.Future"] = {}
        self._recon_sem = threading.BoundedSemaphore(
            max(1, GlobalConfig.reconstruction_max_inflight))
        # Quarantine verdicts this driver has already seen, keyed by
        # function name: later submissions of the same signature fail
        # fast HERE, without racing the heartbeat that propagates the
        # verdict to nodelet lease checks (entries honor the TTL)
        self._poison_sigs: Dict[str, dict] = {}
        self.lt.spawn(self._deferred_dec_loop())
        if mode == "driver":
            # lifecycle-span identity + KV flush (worker processes flush
            # through their WorkerRuntime instead — claim_flusher dedupes)
            from ..util import tracing
            tracing.configure("driver", self.node_id)
            self.lt.spawn(self._trace_flush_loop())
            self.controller.call("register_job",
                                 {"job_id": self.job_id.binary(),
                                  "driver": f"pid-{os.getpid()}"})
        # chaos layer (env/config-armed; no-op when already armed, so a
        # worker's lazy CoreClient never resets live rule counters)
        from ..util import fault_injection
        fault_injection.maybe_arm_from_config()
        if mode == "driver" and fault_injection.ACTIVE is None:
            # a runtime-applied plan must cover drivers that connect
            # AFTER `chaos apply` too — they hold no chaos subscription,
            # so pull the KV copy once at boot
            try:
                plan = self.controller.call("chaos_plan", {}, timeout=10)
                if plan:
                    fault_injection.arm(plan)
            except Exception:
                pass

    # -------------------------------------------------------------- tracing
    async def _trace_flush_loop(self):
        """Ship the spans recorded since the last tick to the controller
        (see util/tracing.py)."""
        from ..util import tracing
        if not tracing.claim_flusher():
            return
        while not self._closed:
            await asyncio.sleep(GlobalConfig.trace_flush_interval_s)
            batch = tracing.flush_batch()
            if batch is None:
                continue
            await tracing.flush_sent(lambda: self.controller.conn.call(
                "trace_append", batch, timeout=10))

    def final_span_flush(self) -> None:
        """Whatever the flush loop hasn't shipped yet must reach the
        controller before this process's ring evaporates — the
        controller RETAINS exited processes' spans, so they stay in
        state.timeline() — and the ring goes to the session's span
        files, which outlive the cluster."""
        from ..util import tracing
        tracing.write_span_file(self.session_dir)
        try:
            batch = tracing.flush_batch()
            if batch is not None:
                self.controller.call("trace_append", batch, timeout=2)
        except Exception:
            pass

    def _stamp_submit(self, spec: TaskSpec) -> None:
        """Submit-time span + wall-clock stamp: downstream hops (driver
        dispatch, serve replicas) derive queue-wait from ``t_submit``."""
        from ..util import tracing
        now = time.time()
        spec.d["t_submit"] = now
        tracing.record_span(f"submit::{spec.function_name}", "driver",
                            now, now, task_id=spec.task_id.hex(),
                            trace=spec.trace_id)

    def _note_dispatch(self, spec: TaskSpec) -> None:
        """The task leaves the driver for a worker: dequeue span +
        queue-wait histogram (submit -> dispatch)."""
        from ..util import tracing
        t_sub = spec.submit_time
        if t_sub is None:
            return
        now = time.time()
        rtm.QUEUE_WAIT.observe(now - t_sub, tags={"node": self.node_id[:12]})
        tracing.record_span(f"dequeue::{spec.function_name}", "sched",
                            t_sub, now, task_id=spec.task_id.hex(),
                            trace=spec.trace_id)

    # ------------------------------------------------------------- refcounts
    async def _deferred_dec_loop(self):
        # the IO-loop sweep: _remove_local_ref here only fire-and-forget
        # spawns, so draining on the loop never blocks it
        while not self._closed:
            await asyncio.sleep(0.05)
            self._drain_deferred_decs()

    def _add_local_ref(self, oid: bytes):
        """Local count; a 0→1 transition on a *borrowed* oid additionally
        registers this process as a borrower with the controller (the
        distributed half of reference_count.h's borrower protocol — the
        owner's free is gated on these)."""
        self._drain_deferred_decs()
        with self._ref_lock:
            n = self._local_refs.get(oid, 0)
            self._local_refs[oid] = n + 1
            borrow = n == 0 and oid not in self._owned
        if borrow and not self._closed:
            self._notify_controller("ref_inc", {"object_ids": [oid]})

    def _notify_controller(self, method: str, data: dict):
        """Fire-and-forget controller notify; per-connection FIFO keeps
        inc/dec ordered."""
        if method == "ref_inc":
            self._borrow_epoch += 1
        try:
            self.lt.spawn(self.controller.conn.notify(method, data))
        except Exception:
            pass

    def sync_borrows(self):
        """Block until every borrow registered so far is visible at the
        controller.  A worker calls this BEFORE replying to a task: the
        caller releases its argument pins only after the reply, so the
        borrow→reply→release→free_request order makes the deferred-free
        gate race-free across connections (the reference achieves this by
        shipping borrower lists in the task reply itself —
        reference_count.h "borrowers" merge)."""
        epoch = self._borrow_epoch
        if epoch == self._borrow_synced or self._closed:
            return
        try:
            # ping rides the same FIFO connection as the ref_inc notifies;
            # the controller handles frames with a synchronous prefix in
            # arrival order, so the ping reply implies the incs applied.
            self.controller.call("ping", {}, timeout=10)
            self._borrow_synced = epoch
        except Exception:
            pass

    def _remove_local_ref(self, oid: bytes):
        if self._closed:
            return
        with self._ref_lock:
            n = self._local_refs.get(oid, 0) - 1
            if n > 0:
                self._local_refs[oid] = n
                return
            self._local_refs.pop(oid, None)
            owned = oid in self._owned
            self._owned.discard(oid)
            plasma = oid in self._plasma_oids
            self._plasma_oids.discard(oid)
            contained = oid in self._containers
            self._containers.discard(oid)
        self.memory_store.delete([oid])
        # NB: the shared-memory pin (self._pinned) is NOT dropped here — it is
        # tied to the lifetime of the deserialized value (weakref finalizer in
        # _get_plasma), because zero-copy numpy views alias store memory.
        if not owned:
            # Borrower letting go: the owner's deferred free may now run.
            self._notify_controller("ref_dec", {"object_ids": [oid]})
            return
        # Owner final release.  Spill storage is NOT reclaimed here: the
        # spill file may be the only copy and a borrower may still hold the
        # ref — the controller sweeps the file (via the spill KV namespace)
        # inside the borrow-gated free itself (_do_free).
        spilled_path = self._spilled_paths.pop(oid, None)
        self._lineage.pop(oid, None)  # deliberate: lineage dies with the ref
        if not (plasma or contained or spilled_path is not None):
            return  # inline-only, nothing pinned: nothing cluster-wide
        # Gated free: executes once no borrower (process or container) holds
        # the object (controller _h_free_request).
        self._notify_controller("free_request", {"object_ids": [oid]})

    # ------------------------------------------------------------------- put
    def put(self, value: Any, xlang: bool = False) -> ObjectRef:
        self._put_index += 1
        oid = ObjectID.for_put(self.task_ctx, self._put_index)
        contained: List[bytes] = []
        if xlang:
            # cross-language encoding (RTX1): readable by non-Python
            # workers; msgpack-typed values only (reference: the
            # cross-language serializer is likewise opt-in per object)
            parts = [memoryview(serialization.serialize_xlang(value))]
        else:
            parts = serialization.serialize(value, ref_collector=contained)
        size = serialization.serialized_size(parts)
        with self._ref_lock:
            self._owned.add(oid.binary())
        if contained:
            # Containment pin: refs inside the stored value stay alive until
            # this container is freed (reference: "contained in owned object"
            # edges of reference_count.h).
            with self._ref_lock:
                self._containers.add(oid.binary())
            self._notify_controller("ref_inc", {
                "object_ids": contained, "holder": f"obj:{oid.hex()}"})
            for b in contained:
                self._promote_to_plasma(b)  # readers fetch them directly
        if size <= GlobalConfig.max_direct_call_object_size:
            self.memory_store.put(oid.binary(), b"".join(bytes(p) for p in parts))
        else:
            try:
                self.store.put_parts(oid.binary(), parts)
                # Bridge pin: hold a get-pin only until the nodelet takes
                # its primary pin (put_location reply), closing the LRU
                # race without double-pinning — the nodelet must stay the
                # SOLE durable pinner so its spill loop can reclaim the
                # segment bytes (reference: the raylet, not the client,
                # pins primary copies; spilling reclaims them).
                bridge = self.store.get(oid.binary(), timeout_ms=0) is not None
                try:
                    self.nodelet.call("put_location",
                                      {"object_id": oid.binary(), "size": size})
                finally:
                    if bridge:
                        self.store.release(oid.binary())
                with self._ref_lock:
                    self._plasma_oids.add(oid.binary())
            except store_client.StoreFullError:
                # spill to external storage (reference: plasma → spill
                # workers → ExternalStorage; here the writer spills inline)
                path = self._spill_backpressured(oid.binary(), parts)
                self.controller.call(
                    "kv_put", {**spill.kv_entry(oid.binary()),
                               "value": path.encode()})
                self._spilled_paths[oid.binary()] = path
            self.memory_store.put_in_plasma_marker(oid.binary())
        return ObjectRef(oid, self)

    def _promote_to_plasma(self, oid: bytes) -> None:
        """Make a memory-store-only object fetchable by OTHER processes.

        Small put()/return values live only in the owner's private
        memory store; a ref to one that ships NESTED inside a container
        (task arg dict, DataIterator, put() payload) deserializes in a
        worker that has nowhere to fetch the value from — positional
        ARG_REFs dodge this via inline-at-resolve, nested refs cannot.
        Promotion mirrors put()'s plasma path: shm write, nodelet
        primary pin, plasma marker locally."""
        entry = self.memory_store.peek(oid)
        if entry is None:
            # value still pending (a nested ref to a running task's
            # return): promote when the inline result LANDS — see
            # _handle_task_reply — or the consumer could never fetch it
            with self._ref_lock:
                self._promote_on_arrival.add(oid)
            return
        if entry.value is IN_PLASMA or entry.is_exception \
                or self.store.contains(oid):
            return
        parts = [memoryview(entry.value)]
        size = len(entry.value)
        try:
            self.store.put_parts(oid, parts)
            bridge = self.store.get(oid, timeout_ms=0) is not None
            try:
                self.nodelet.call("put_location",
                                  {"object_id": oid, "size": size})
            finally:
                if bridge:
                    self.store.release(oid)
            with self._ref_lock:
                self._plasma_oids.add(oid)
        except store_client.StoreFullError:
            path = self._spill_backpressured(oid, parts)
            self.controller.call(
                "kv_put", {**spill.kv_entry(oid), "value": path.encode()})
            self._spilled_paths[oid] = path
        self.memory_store.put_in_plasma_marker(oid)

    def _spill_backpressured(self, oid: bytes, parts) -> str:
        """Writer-inline spill with put backpressure: a disk fault
        (ENOSPC/EIO) while the store is full waits and retries —
        a spill wave elsewhere may free space — and exhausts into the
        TYPED retriable StorageDegradedError, never a bare OSError."""
        for attempt in range(GlobalConfig.spill_backpressure_retries + 1):
            try:
                return spill.write_object(oid, parts)
            except OSError as e:
                spill.count_fault(spill.SPILL_WRITE_SITE, "backpressured")
                if attempt >= GlobalConfig.spill_backpressure_retries:
                    raise exceptions.StorageDegradedError(
                        f"put {oid.hex()[:12]}: store full and spill "
                        f"failed: {e}",
                        retry_after_s=GlobalConfig.
                        spill_backpressure_delay_s) from e
                time.sleep(GlobalConfig.spill_backpressure_delay_s
                           * rpc._jitter())

    # ------------------------------------------------------------------- get
    def get(self, refs: List[ObjectRef], timeout: Optional[float]) -> List[Any]:
        self._drain_deferred_decs()
        oids = [r.binary() for r in refs]
        # Revived refs (deserialized out of a container after the original
        # handle was released) have no memory-store entry — the release
        # deleted it — but the object itself still lives in a store / spill
        # (its free was deferred on the containment hold).  Re-establish the
        # plasma marker so the wait below doesn't block on an entry nothing
        # will ever re-put.  Fast pre-pass: local shm store only (no RPC on
        # the hot path); cluster-wide lookup runs only after a miss.
        for oid in dict.fromkeys(oids):
            if self.memory_store.peek(oid) is None and self.store.contains(oid):
                self.memory_store.put_in_plasma_marker(oid)
        # Wait in bounded slices so the cluster-wide revive lookup also runs
        # for timeout=None gets — a revived ref living on ANOTHER node has
        # no local entry and nothing will ever re-put one.  Only refs this
        # process does NOT own can need revival (owned returns/puts are
        # fulfilled by task replies / put markers), so the periodic RPC
        # check is bounded to the borrowed subset.
        deadline = None if timeout is None else time.monotonic() + timeout
        # Borrowed refs that already exist somewhere in the cluster must
        # resolve NOW, not after the first wait slice: a borrowed ref
        # never gets a local entry pushed to it, so without this pre-pass
        # every cross-node get of an existing object ate a full 5 s
        # first_slice before the revive loop looked at the directory
        # (measured: 64 MiB node-to-node fetch = 5.09 s wall, ~0.06 s of
        # it transfer).
        self._revive_borrowed(oids)  # zero RPCs when none borrowed+missing
        # timeout=0 must stay a non-blocking poll (0 is falsy: no `or`)
        first_slice = 5.0 if timeout is None else min(timeout, 5.0)
        entries = self.memory_store.get(oids, first_slice)
        while entries is None:
            revived = self._revive_borrowed(oids)
            remaining = None if deadline is None \
                else deadline - time.monotonic()
            if remaining is not None and remaining <= 0 and not revived:
                break
            step = 5.0 if remaining is None else max(0.1, min(remaining, 5.0))
            entries = self.memory_store.get(oids, step)
        if entries is None:
            raise exceptions.GetTimeoutError(
                f"get() timed out waiting for {len(oids)} objects")
        out = []
        for oid, entry in zip(oids, entries):
            if entry.is_exception:
                raise _as_exception(entry.value)
            if entry.value is IN_PLASMA:
                out.append(self._get_plasma(oid, timeout))
            else:
                value = serialization.deserialize(memoryview(entry.value))
                if isinstance(value, _ErrorValue):
                    raise value.unwrap()
                out.append(value)
        return out

    def _get_plasma(self, oid: bytes, timeout: Optional[float]) -> Any:
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            spilled = self._read_spilled(oid)
            if spilled is not None:
                value = serialization.deserialize(memoryview(spilled))
                if isinstance(value, _ErrorValue):
                    raise value.unwrap()
                return value
            r = self.nodelet.call("pull", {"object_id": oid,
                                           "timeout": timeout or 60.0},
                                  timeout=(timeout or 60.0) + 10)
            if not r.get("ok") and not self._reconstruct(oid, timeout):
                raise exceptions.ObjectLostError(oid.hex(), r.get("error", ""))
            view = self.store.get(oid, timeout_ms=10000)
            if view is None:
                raise exceptions.ObjectLostError(oid.hex(), "pull raced eviction")
        with self._ref_lock:
            already = oid in self._pinned
            self._pinned.add(oid)
        if already:
            self.store.release(oid)  # only hold one pin per object
        value = serialization.deserialize(view)
        if isinstance(value, _ErrorValue):
            raise value.unwrap()
        # The store pin guards the zero-copy views aliasing store memory; tie
        # its release to the *value's* lifetime when the value is
        # weakref-able, else keep it pinned for the client's lifetime.
        self._tie_pin_to_value(oid, value)
        return value

    def _read_spilled(self, oid: bytes) -> Optional[bytes]:
        path = self._spilled_paths.get(oid)
        if path is None:
            raw = self.controller.call("kv_get", spill.kv_entry(oid))
            if not raw:
                return None
            path = raw.decode()
        return spill.read_file(path)

    def _revive_borrowed(self, oids) -> bool:
        """Place plasma markers for borrowed refs whose objects already
        exist cluster-wide (directory/spill lookup).  Borrowed refs never
        get local entries pushed; without this, get()/wait() block their
        full first slice (or forever, for wait) on objects that are
        sitting in another node's store."""
        revived = False
        with self._ref_lock:
            borrowed = [o for o in dict.fromkeys(oids)
                        if o not in self._owned]
        for oid in borrowed:
            if self.memory_store.peek(oid) is None \
                    and self._object_available(oid):
                self.memory_store.put_in_plasma_marker(oid)
                revived = True
        return revived

    def _object_available(self, oid: bytes) -> bool:
        """Reachable without reconstruction: local memory/store, any node's
        store (controller directory), or spill storage."""
        if self.memory_store.peek(oid) is not None or self.store.contains(oid):
            return True
        try:
            locs = self.controller.call("object_locations_get",
                                        {"object_id": oid, "timeout": 0.05},
                                        timeout=5)
            if locs and locs.get("locations"):
                return True
        except Exception:
            pass
        try:
            if self.controller.call("kv_get", spill.kv_entry(oid)):
                return True
        except Exception:
            pass
        return False

    def _reconstruct(self, oid: bytes, timeout: Optional[float],
                     _depth: int = 0, _chain: tuple = ()) -> bool:
        """Multi-level lineage reconstruction (reference:
        `object_recovery_manager.h:96-106`): resubmit the task that created
        the lost object, first recursively reconstructing any of its
        argument objects that are themselves lost — so a chain a→b→c
        recovers end-to-end after the whole chain is evicted.

        Storm governance: concurrent callers for the same oid dedupe
        onto ONE in-flight reconstruction (the rest wait on its future),
        and crossing the lineage-depth ceiling raises the typed
        ``ReconstructionDepthError`` carrying the oid chain instead of
        collapsing into a generic ObjectLostError."""
        chain = _chain + (oid,)
        if _depth > GlobalConfig.max_reconstruction_depth:
            raise exceptions.ReconstructionDepthError(chain)
        with self._recon_lock:
            fut = self._recon_inflight.get(oid)
            owner = fut is None
            if owner:
                fut = cf.Future()
                self._recon_inflight[oid] = fut
        if not owner:
            rtm.RECONSTRUCTION_DEDUP.inc()
            try:
                return bool(fut.result(timeout=(timeout or 60.0) + 30.0))
            except cf.TimeoutError:
                return False
        try:
            ok = self._reconstruct_inner(oid, timeout, _depth, chain)
            fut.set_result(ok)
            return ok
        except BaseException as e:
            fut.set_exception(e)
            raise
        finally:
            with self._recon_lock:
                self._recon_inflight.pop(oid, None)

    def _reconstruct_inner(self, oid: bytes, timeout: Optional[float],
                           _depth: int, chain: tuple) -> bool:
        spec = self._lineage.get(oid)
        if spec is None:
            return False
        for arg_oid in {o.binary() if hasattr(o, "binary") else o
                        for o in spec.arg_ref_ids()}:
            if not self._object_available(arg_oid):
                if not self._reconstruct(arg_oid, timeout, _depth + 1,
                                         chain):
                    return False
        # Resubmission concurrency cap: recursion above runs OUTSIDE the
        # permit (a parent never holds one while a child waits), so deep
        # chains cannot deadlock the bounded pool.
        if not self._recon_sem.acquire(timeout=(timeout or 60.0)):
            return False
        try:
            rtm.RECONSTRUCTION_EXECUTED.inc()
            # The resubmitted task's reply releases one local ref per arg
            # (_handle_task_reply) — take those refs NOW or the user's own
            # handles get over-decremented (and freed) by the recovery.
            for arg_oid in spec.arg_ref_ids():
                self._add_local_ref(arg_oid.binary())
            self.lt.spawn(self._submit_pipeline(spec, spec.max_retries))
            deadline = time.monotonic() + (timeout or 60.0)
            while time.monotonic() < deadline:
                if self.store.contains(oid):
                    return True
                r = self.nodelet.call("pull", {"object_id": oid,
                                               "timeout": 1.0}, timeout=11)
                if r.get("ok"):
                    return True
                time.sleep(0.2)
            return False
        finally:
            self._recon_sem.release()

    def _tie_pin_to_value(self, oid: bytes, value: Any):
        import weakref

        def _unpin(oid=oid, store=self.store, pinned=self._pinned,
                   lock=self._ref_lock):
            with lock:
                if oid not in pinned:
                    return
                pinned.discard(oid)
            try:
                store.release(oid)
            except Exception:
                pass
        try:
            fin = weakref.finalize(value, _unpin)
        except TypeError:
            pass  # not weakref-able (int, tuple, ...): stay pinned
        else:
            # Track so shutdown() can detach before closing the store: a GC
            # run after close() must not re-enter the ctypes layer.
            self._value_finalizers.append(fin)
            if len(self._value_finalizers) > 256:
                self._value_finalizers = [
                    f for f in self._value_finalizers if f.alive]

    # ------------------------------------------------------------------ wait
    def wait(self, refs: List[ObjectRef], num_returns: int,
             timeout: Optional[float]) -> Tuple[List[ObjectRef], List[ObjectRef]]:
        oids = [r.binary() for r in refs]
        by_oid = {r.binary(): r for r in refs}
        deadline = None if timeout is None else time.monotonic() + timeout
        # Fast path first (zero RPCs): enough objects already ready
        # locally.  Only when that falls short does the borrowed-ref
        # revive run — same blindness as get() had: an object living
        # only on another node never gets a local entry pushed, so a
        # bare memory_store.wait would burn the full timeout (or block
        # forever) on refs that are long since ready cluster-wide.  The
        # revive repeats between bounded wait slices so borrowed objects
        # that materialize MID-wait are seen too.
        ready, not_ready = self.memory_store.wait(oids, num_returns, 0)
        while len(ready) < num_returns:
            self._revive_borrowed(oids)
            remaining = None if deadline is None \
                else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                ready, not_ready = self.memory_store.wait(
                    oids, num_returns, 0)
                break
            step = 5.0 if remaining is None \
                else max(0.05, min(remaining, 5.0))
            ready, not_ready = self.memory_store.wait(
                oids, num_returns, step)
        return [by_oid[o] for o in ready], [by_oid[o] for o in not_ready]

    # -------------------------------------------------------- task submission
    def _take_submit_credit(self) -> None:
        """Consume one submission credit, refilling the window from the
        controller when empty.  A zero grant means the controller is
        shedding load: buffer locally (sleep and re-ask with full-jitter
        backoff) until it recovers or the failover deadline passes, then
        surface the typed pushback."""
        if GlobalConfig.flow_credit_window <= 0:
            return  # flow control disabled
        with self._credit_lock:
            if self._credits > 0:
                self._credits -= 1
                return
        from ..util.backoff import ExponentialBackoff
        bo = ExponentialBackoff(base=0.05,
                                cap=GlobalConfig.rpc_connect_backoff_cap_s)
        deadline = time.monotonic() + \
            GlobalConfig.ha_client_failover_timeout_s
        while True:
            r = self.controller.call(
                "credit_request",
                {"want": GlobalConfig.flow_credit_window}, timeout=10)
            granted = int(r.get("credits", 0)) if isinstance(r, dict) else 0
            if granted > 0:
                with self._credit_lock:
                    self._credits += granted - 1
                return
            ra = float(r.get("retry_after_s", 0.5)) \
                if isinstance(r, dict) else 0.5
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise exceptions.ControlPlaneOverloadError("submit", ra)
            time.sleep(min(remaining,
                           ra * rpc._jitter() + bo.next_delay()))

    def register_function(self, fid: bytes, blob: bytes):
        if fid in self._fn_registered:
            return
        # keep the raw blob: if a worker later reports the kvref payload
        # lost (`fn_lost`), _reregister_function re-puts from this cache
        self._fn_blobs[fid] = blob
        self._register_function_inner(fid, blob, overwrite=False)
        self._fn_registered.add(fid)

    def _register_function_inner(self, fid: bytes, blob: bytes,
                                 overwrite: bool):
        value = blob
        if 0 < GlobalConfig.kv_inline_max_bytes < len(blob):
            # big function-table blob: divert the payload to the object
            # plane (local shm write + primary pin) and register only a
            # small ref marker in the control-plane KV — readers
            # (`_get_function`) follow the marker transparently
            from . import kvref
            ref = self.put(blob)
            self._promote_to_plasma(ref.binary())
            self._fn_blob_refs[fid] = ref   # owner keeps payload alive
            value = kvref.pack(ref.binary())
        self._take_submit_credit()
        self.controller.call("kv_put", {"ns": FN_NAMESPACE, "key": fid,
                                        "value": value,
                                        "overwrite": overwrite})

    def _reregister_function(self, fid: bytes) -> bool:
        """Re-publish a function whose kvref payload was lost (a worker
        reported ``fn_lost``): put a FRESH blob ref and overwrite the KV
        marker so the requeued task finds a live payload."""
        blob = self._fn_blobs.get(fid)
        if blob is None:
            return False
        self._register_function_inner(fid, blob, overwrite=True)
        return True

    def build_args(self, args: tuple, kwargs: dict):
        """Encode call arguments for a spec: ObjectRefs stay refs, small
        values inline, big values spill to the local store.  The trailing
        element is always the serialized kwargs dict.  Returns
        ``(encoded, temp_refs)`` — the caller must keep ``temp_refs`` alive
        until the spec's arg refs are pinned (submit_task does this).
        Refs *nested inside* inline arg values are pinned too (as temp
        refs re-bound to this core), so e.g. ``f.remote([ref1, ref2])``
        keeps the nested objects alive until the task lands."""
        encoded: List[Any] = []
        temp_refs: List[ObjectRef] = []
        nested: List[bytes] = []
        for a in args:
            encoded.append(self._encode_arg(a, temp_refs, nested))
        encoded.append(self._encode_arg(kwargs or {}, temp_refs, nested))
        for b in nested:
            temp_refs.append(ObjectRef(ObjectID(b), self))
            # the consumer deserializes this ref OUT of a container and
            # fetches it itself — the value must be shared, not private
            self._promote_to_plasma(b)
        return encoded, temp_refs

    def _encode_arg(self, value: Any, temp_refs: List["ObjectRef"],
                    nested: List[bytes]):
        if isinstance(value, ObjectRef):
            return [ARG_REF, value.binary()]
        parts = serialization.serialize(value, ref_collector=nested)
        size = serialization.serialized_size(parts)
        if size > GlobalConfig.inline_small_args_bytes:
            ref = self.put(value)
            temp_refs.append(ref)  # keep alive until submit pins it
            return [ARG_REF, ref.binary()]
        return [ARG_VALUE, b"".join(bytes(p) for p in parts)]

    def _stamp_trace_ctx(self, spec: TaskSpec) -> None:
        """OTel span injection (reference: tracing_helper.py:87
        _inject_tracing_into_task): when tracing is enabled, record a
        driver-side submit span and ship its W3C context in the spec so
        the worker's execution span parents across the process hop."""
        from ..util import otel
        if not otel.is_enabled():
            return
        with otel.submit_span(spec.function_name):
            tp = otel.inject_context()
        if tp:
            spec.d["otel"] = tp

    def submit_task(self, spec: TaskSpec,
                    temp_refs: Optional[List["ObjectRef"]] = None
                    ) -> List[ObjectRef]:
        self._take_submit_credit()
        self._stamp_trace_ctx(spec)
        self._stamp_submit(spec)
        with self._ref_lock:
            for oid in spec.return_ids():
                self._owned.add(oid.binary())
        refs = [ObjectRef(oid, self) for oid in spec.return_ids()]
        if spec.actor_creation_id is None and spec.actor_id is None:
            for oid in spec.return_ids():
                self._lineage[oid.binary()] = spec
            while len(self._lineage) > GlobalConfig.lineage_cache_size:
                self._lineage.popitem(last=False)
        for oid in spec.arg_ref_ids():
            self._add_local_ref(oid.binary())  # pin args until task completes
        # Nested/spilled-arg temporaries: hold a local ref until the task
        # completes (released with the arg pins in _handle_task_reply).
        extra = [r.binary() for r in (temp_refs or [])]
        if extra:
            for b in extra:
                self._add_local_ref(b)
            self._extra_pins_map[spec.task_id.binary()] = extra
        del temp_refs
        self._enqueue_submission(
            self._submit_pipeline(spec, spec.max_retries))
        return refs

    def _enqueue_submission(self, coro) -> None:
        """Queue a submission pipeline (a coroutine OBJECT — not started
        until the drain schedules it) for the next loop wakeup."""
        with self._submit_lock:
            self._submit_q.append(coro)
            if self._submit_scheduled:
                return   # a drain is already on its way
            self._submit_scheduled = True
        try:
            self.lt.loop.call_soon_threadsafe(self._drain_submissions)
        except BaseException:
            # scheduling failed (interrupt mid-call, closing loop): a
            # stuck-True flag would silently wedge EVERY future submit
            with self._submit_lock:
                self._submit_scheduled = False
            raise

    def _drain_submissions(self) -> None:
        """Runs ON the IO loop: start a pipeline per queued submission."""
        try:
            while True:
                with self._submit_lock:
                    if not self._submit_q:
                        self._submit_scheduled = False
                        return
                    batch = list(self._submit_q)
                    self._submit_q.clear()
                for coro in batch:
                    asyncio.ensure_future(coro)
        except BaseException:
            # keep the pump alive: clear the flag so the next enqueue
            # (or the reschedule below) wakes the loop again
            with self._submit_lock:
                self._submit_scheduled = bool(self._submit_q)
                resched = self._submit_scheduled
            if resched:
                self.lt.loop.call_soon(self._drain_submissions)
            raise

    async def _submit_pipeline(self, spec: TaskSpec, attempts_left: int):
        try:
            ok = await self._resolve_dependencies(spec)
            if not ok:
                return  # dependency failed; error already propagated
            key = spec.scheduling_key()
            state = self._sched.get(key)
            if state is None:
                state = self._sched[key] = _SchedulingKeyState()
            state.queue.append((spec, attempts_left))
            state.wakeup.set()
            self._maybe_grow_leases(key, state)
        except Exception as e:
            self._fail_task(spec, f"submission failed: {e!r}")

    async def _resolve_dependencies(self, spec: TaskSpec) -> bool:
        """Wait for owned in-memory args and inline them (reference:
        LocalDependencyResolver in direct_task_transport.cc)."""
        for i, arg in enumerate(spec.args):
            if arg[0] != ARG_REF:
                continue
            oid = arg[1]
            entry = self.memory_store.peek(oid)
            if entry is None:
                if self.store.contains(oid):
                    continue  # plasma object from another owner
                loop = asyncio.get_event_loop()
                entry_list = await loop.run_in_executor(
                    None, self.memory_store.get, [oid], 600.0)
                if entry_list is None:
                    self._fail_task(spec, f"dependency {oid.hex()[:16]} never "
                                          "became available")
                    return False
                entry = entry_list[0]
            if entry.value is IN_PLASMA:
                continue
            if entry.is_exception:
                self._propagate_error(spec, entry.value)
                return False
            value = serialization.deserialize(memoryview(entry.value))
            if isinstance(value, _ErrorValue):
                self._propagate_error(spec, value)
                return False
            spec.args[i] = [ARG_VALUE, entry.value]
            self._remove_local_ref(oid)  # inlined; drop the pin
        return True

    def _maybe_grow_leases(self, key: tuple, state: _SchedulingKeyState):
        """Pipelined lease requests: one lease per task AWAITING service.
        Free servers = leases - busy; a lease loop blocked inside a
        long-running push cannot drain the queue, so counting it as
        available deadlocks any workload where queued task B must run
        concurrently with in-flight task A (e.g. collective rendezvous —
        the reference avoids this by leasing per pending task,
        direct_task_transport.cc:325 RequestNewWorkerIfNeeded).  Free
        (non-busy) loops are capped like the reference's pending lease
        requests, so a burst of thousands of queued tasks doesn't storm
        the nodelet with lease RPCs."""
        free = state.leases - state.busy
        if free < len(state.queue) \
                and free < GlobalConfig.max_pending_lease_requests:
            state.leases += 1
            asyncio.ensure_future(self._lease_loop(key, state))

    async def _lease_loop(self, key: tuple, state: _SchedulingKeyState):
        """Acquire one lease and drain the queue through it."""
        try:
            while state.queue:
                spec0, _ = state.queue[0]
                grant = await self._acquire_lease(spec0, state)
                if grant is None:
                    while state.queue:
                        spec, _ = state.queue.popleft()
                        self._fail_task(spec, "could not lease a worker "
                                              "(infeasible or timeout)")
                    return
                if isinstance(grant, dict):
                    # the signature is quarantined as poison: fail the
                    # whole queue fast with the typed evidence trail
                    # instead of burning workers one retry at a time
                    while state.queue:
                        spec, _ = state.queue.popleft()
                        self._fail_poisoned(spec, grant["poisoned"])
                    return
                nodelet_conn, lease_id, worker_addr, worker_id = grant
                try:
                    await self._drain_through_worker(
                        state, worker_addr, nodelet_conn, worker_id)
                except rpc.RpcError:
                    # Worker vanished between grant and connect (crash
                    # window before the nodelet reaps it); re-lease.
                    self._worker_conns.pop(worker_addr, None)
                finally:
                    try:
                        await nodelet_conn.call("return_lease",
                                                {"lease_id": lease_id})
                    except rpc.RpcError:
                        pass
        finally:
            state.leases -= 1

    async def _acquire_lease(self, spec: TaskSpec,
                             state: Optional[_SchedulingKeyState] = None):
        rec = self._poison_sigs.get(spec.function_name)
        if rec is not None:
            if rec.get("until", 0.0) > time.time():
                return {"poisoned": rec}
            self._poison_sigs.pop(spec.function_name, None)
        addr = self.nodelet_addr
        deadline = time.monotonic() + GlobalConfig.lease_request_timeout_s
        while time.monotonic() < deadline:
            try:
                conn = await self._nodelet_conn(addr)
                reply = await conn.call(
                    "lease", {"spec": spec.to_wire(), "timeout": 5.0,
                              "avoid": sorted(state.avoid)
                              if state is not None else []},
                    timeout=20)
            except rpc.RpcError:
                # Target nodelet unreachable (e.g. died): fall back local.
                self._nodelet_conns.pop(addr, None)
                addr = self.nodelet_addr
                await asyncio.sleep(0.2)
                continue
            if reply.get("poisoned"):
                return {"poisoned": reply["poisoned"]}
            if reply.get("granted"):
                return (conn, reply["lease_id"], reply["worker_addr"],
                        reply["worker_id"])
            if reply.get("spillback"):
                addr = reply["spillback"]
                continue
            if reply.get("draining"):
                # the target is evacuating (planned departure) and no
                # peer fits yet: back off briefly and retry — replacement
                # capacity or the node's deregistration changes the view
                await asyncio.sleep(0.2)
                addr = self.nodelet_addr
                continue
            if reply.get("infeasible"):
                return None
            if reply.get("timeout"):
                # Busy, not infeasible: the cluster is saturated and the
                # task is queued work.  Waiting must not burn the deadline
                # (a 50k-task burst keeps every worker leased for minutes)
                # — the reference likewise queues feasible tasks forever.
                deadline = time.monotonic() + \
                    GlobalConfig.lease_request_timeout_s
                addr = self.nodelet_addr  # re-evaluate from local
                continue
            return None
        return None

    async def _drain_through_worker(self, state: _SchedulingKeyState,
                                    worker_addr: str,
                                    nodelet_conn=None,
                                    worker_id: Optional[bytes] = None):
        """Drain queued tasks through one leased worker, PIPELINED.

        Up to ``task_pipeline_depth`` push_task calls ride the connection
        concurrently; the worker executes them serially on its one
        executor thread (resource semantics hold — one task RUNS at a
        time), so pipelining only hides the per-push RPC round trip.
        Mirrors the reference's submission pipelining
        (direct_task_transport.cc in-flight pushes per lease).
        """
        conn = await self._worker_conn(worker_addr)
        max_depth = max(1, GlobalConfig.task_pipeline_depth)
        fast_s = GlobalConfig.task_pipeline_fast_ms / 1000.0
        idle_deadline = time.monotonic() + GlobalConfig.worker_lease_idle_seconds
        inflight: Dict[asyncio.Future, tuple] = {}
        worker_dead = False
        # Adaptive depth: a deep window on SLOW tasks would serialize work
        # one lease could have spread across workers (the queue drains into
        # this window and _maybe_grow_leases sees nothing left to grow
        # for).  Start at 1 — identical to unpipelined behavior — and
        # deepen only once completions prove sub-``fast_ms`` latency,
        # where hiding the push RTT is the whole win.
        depth = 1
        lat_ewma: Optional[float] = None

        async def _reap(fut: asyncio.Future) -> bool:
            """Handle one completed push; returns True if lease is dead."""
            nonlocal worker_dead, depth, lat_ewma
            spec, attempts_left, t_push, occ = inflight.pop(fut)
            # Normalize by the window occupancy at push time: at depth d a
            # push waits behind ~d-1 earlier tasks in the serial worker, so
            # raw push-to-reply latency scales with d and comparing it to
            # fast_s directly would flap the depth between max and 1.
            dt = (time.monotonic() - t_push) / max(1, occ)
            lat_ewma = dt if lat_ewma is None else 0.7 * lat_ewma + 0.3 * dt
            depth = max_depth if lat_ewma < fast_s else 1
            tid = spec.task_id.binary()
            state.busy -= 1
            self._task_sites.pop(tid, None)
            try:
                reply = fut.result()
            except rpc.RpcError as e:
                self._worker_conns.pop(worker_addr, None)
                # typed death attribution: ask the granting nodelet WHY
                # before deciding the retry (blocks this dead lease only)
                death = None
                if tid not in self._cancelled and worker_id is not None:
                    death = await self._query_death(nodelet_conn,
                                                    worker_id)
                if death:
                    state.avoid.update(death.get("avoid") or ())
                if tid in self._cancelled:
                    # force-cancel killed the worker: that IS the cancel
                    self._finish_cancel(spec)
                elif death and death.get("quarantined"):
                    # the controller just declared this signature poison:
                    # fail fast with the typed evidence trail
                    self._fail_poisoned(spec, death["quarantined"])
                elif attempts_left > 0:
                    # jittered pause before the re-lease: lets the crash
                    # report land so anti-affinity steers the retry, and
                    # decorrelates a wave of dead leases re-leasing
                    await asyncio.sleep(GlobalConfig.task_retry_delay_s
                                        * (0.5 + random.random()))
                    state.queue.appendleft((spec, attempts_left - 1))
                else:
                    why = (f" ({death['cause']}: {death['detail']})"
                           if death and death.get("cause") else "")
                    self._fail_task(spec,
                                    f"worker died executing task: "
                                    f"{e}{why}")
                worker_dead = True
                return True
            self._handle_task_reply(spec, reply, attempts_left, state)
            return False

        try:
            while True:
                # Clear BEFORE the fill scan: an enqueue that lands after
                # the scan re-sets it and the wait below returns at once.
                state.wakeup.clear()
                while state.queue and len(inflight) < depth \
                        and not worker_dead:
                    spec, attempts_left = state.queue.popleft()
                    tid = spec.task_id.binary()
                    if tid in self._cancelled:
                        self._finish_cancel(spec)  # cancelled while queued
                        continue
                    state.busy += 1
                    self._task_sites[tid] = conn
                    self._note_dispatch(spec)
                    # The queue may still hold tasks that must run
                    # CONCURRENTLY with this one; with this loop now busy,
                    # grow the pool.
                    self._maybe_grow_leases(None, state)
                    fut = asyncio.ensure_future(
                        conn.call("push_task", {"spec": spec.to_wire()},
                                  timeout=None))
                    inflight[fut] = (spec, attempts_left, time.monotonic(),
                                     len(inflight) + 1)
                if inflight:
                    # Event-driven: wake on a completion OR on new queued
                    # work (to top up a free pipeline slot) — a leased
                    # worker running a minutes-long task costs ZERO
                    # wakeups here.
                    waker = asyncio.ensure_future(state.wakeup.wait())
                    try:
                        done, _ = await asyncio.wait(
                            list(inflight) + [waker],
                            return_when=asyncio.FIRST_COMPLETED)
                    finally:
                        waker.cancel()
                    done.discard(waker)
                    for fut in done:
                        await _reap(fut)
                    if done and not worker_dead:
                        idle_deadline = time.monotonic() + \
                            GlobalConfig.worker_lease_idle_seconds
                    continue
                if worker_dead:
                    return  # lease is dead; caller re-leases
                if not state.queue:
                    # Hold the lease for new work (reuse hot path) until
                    # the idle deadline — one timed wait, not a poll.
                    remaining = idle_deadline - time.monotonic()
                    if remaining <= 0:
                        return
                    try:
                        await asyncio.wait_for(state.wakeup.wait(),
                                               timeout=remaining)
                    except asyncio.TimeoutError:
                        pass
        finally:
            # a cancelled drain (client shutdown) must not leak busy counts
            for fut in list(inflight):
                fut.cancel()
                spec, attempts_left, _, _ = inflight.pop(fut)
                state.busy -= 1
                self._task_sites.pop(spec.task_id.binary(), None)
                state.queue.appendleft((spec, attempts_left))

    def _handle_task_reply(self, spec: TaskSpec, reply: dict,
                           attempts_left: int,
                           state: Optional[_SchedulingKeyState]) -> bool:
        """Returns True if the task was re-queued for retry."""
        err = reply.get("error")
        tid = spec.task_id.binary()
        if err is None:
            # a late cancel lost the race: the stale entry must not
            # poison a future lineage resubmission of the same task_id
            self._cancelled.discard(tid)
            self._spurious_requeues.pop(tid, None)
            self._fn_requeues.pop(tid, None)
        if err is not None:
            if tid in self._cancelled:
                # an interrupted task errors out (TaskCancelledError raised
                # in the worker); surface THE CANCEL, never retry
                self._finish_cancel(spec)
                return False
            if err.get("fn_lost") and state is not None:
                # The function's kvref blob vanished (owner restart,
                # lost spill file): re-register from the cached blob and
                # requeue WITHOUT burning the task's retry budget — the
                # fault is the function table's, not the task's.
                # Bounded: a blob that stays lost fails the task with
                # the worker's typed FunctionUnavailableError traceback.
                n = self._fn_requeues.get(tid, 0)
                if n < 3 and self._reregister_function(
                        bytes.fromhex(err["fn_lost"])):
                    self._fn_requeues[tid] = n + 1
                    state.queue.append((spec, attempts_left))
                    state.wakeup.set()
                    return True
            if self._is_spurious_cancel(err) and state is not None:
                # The TAGGED injection class for a task nobody cancelled:
                # PyThreadState_SetAsyncExc landed in a pool thread that
                # already moved on to ANOTHER task.  Requeue the victim
                # WITHOUT burning its retry budget (the fault is ours, not
                # the task's), bounded against pathological repetition.
                n = self._spurious_requeues.get(tid, 0)
                if n < 5:
                    self._spurious_requeues[tid] = n + 1
                    state.queue.append((spec, attempts_left))
                    state.wakeup.set()
                    return True
            if spec.retry_exceptions and attempts_left > 0 and state is not None:
                state.queue.append((spec, attempts_left - 1))
                state.wakeup.set()
                return True
            ev = _ErrorValue(err["traceback"], err.get("pickled"),
                             err.get("fname", spec.function_name),
                             is_actor=spec.actor_id is not None,
                             actor_down=bool(err.get("dying")))
            self._store_error(spec, ev)
            return False
        for oid, ret in zip(spec.return_ids(), reply["returns"]):
            if ret.get("contained"):
                # Worker registered containment pins keyed on this return
                # oid; the owner must free_request on final release so the
                # controller cascades them (even for inline returns).
                with self._ref_lock:
                    self._containers.add(oid.binary())
            if "inline" in ret:
                self.memory_store.put(oid.binary(), ret["inline"])
                with self._ref_lock:
                    promote = oid.binary() in self._promote_on_arrival
                    self._promote_on_arrival.discard(oid.binary())
                if promote:
                    # a nested ref to this value already shipped; share it
                    self._promote_to_plasma(oid.binary())
            else:
                with self._ref_lock:
                    self._plasma_oids.add(oid.binary())
                self.memory_store.put_in_plasma_marker(oid.binary())
        for oid in spec.arg_ref_ids():
            self._remove_local_ref(oid.binary())
        self._release_extra_pins(spec)
        return False

    def _release_extra_pins(self, spec: TaskSpec):
        key = spec.task_id.binary()
        for b in self._extra_pins_map.pop(key, ()):  # idempotent (pop)
            self._remove_local_ref(b)

    def _store_error(self, spec: TaskSpec, error_value: _ErrorValue):
        data = serialization.serialize_to_bytes(error_value)
        for oid in spec.return_ids():
            self.memory_store.put(oid.binary(), data)
        for oid in spec.arg_ref_ids():
            self._remove_local_ref(oid.binary())
        self._release_extra_pins(spec)

    def _fail_task(self, spec: TaskSpec, reason: str):
        self._store_error(spec, _ErrorValue(reason, None, spec.function_name))

    async def _query_death(self, nodelet_conn, worker_id: bytes):
        """Best-effort typed death attribution from the granting
        nodelet; None when the nodelet is unreachable or the corpse was
        never classified (the caller falls back to plain retry)."""
        if nodelet_conn is None:
            return None
        try:
            r = await nodelet_conn.call(
                "worker_death_info",
                {"worker_id": worker_id, "timeout": 2.0}, timeout=10)
        except (rpc.RpcError, OSError, asyncio.TimeoutError):
            return None
        return r if isinstance(r, dict) and not r.get("unknown") else None

    def _fail_poisoned(self, spec: TaskSpec, record: dict):
        """Fulfill a quarantined task's refs with the typed
        PoisonTaskError carrying the evidence trail."""
        self._poison_sigs[spec.function_name] = record
        err = exceptions.PoisonTaskError(
            record.get("sig", spec.function_name),
            record.get("evidence"), record.get("until", 0.0))
        try:
            pickled = serialization.dumps_function(err)
        except Exception:
            pickled = None
        self._store_error(spec, _ErrorValue(str(err), pickled,
                                            spec.function_name))

    # ---------------------------------------------------------------- cancel
    def cancel(self, ref: "ObjectRef", *, force: bool = False) -> bool:
        """Cancel the task that produces ``ref`` (reference:
        `CoreWorker::CancelTask` / `ray.cancel`).  Queued tasks unschedule
        immediately; running tasks get an in-band interrupt
        (TaskCancelledError raised in the worker thread / asyncio task),
        or — with ``force`` — their worker process is killed.  Returns
        False when the task already finished (no-op, like the reference).
        Getting a cancelled ref raises TaskCancelledError."""
        oid = ref.binary()
        spec = self._lineage.get(oid)
        if spec is None:
            # finished (lineage released), an actor-task ref (no lineage —
            # kill the actor instead), or a plain put: nothing to cancel
            return False
        if spec.actor_id is not None or spec.actor_creation_id is not None:
            return False  # actor work cancels by killing the actor
        if self.memory_store.peek(oid) is not None:
            return False  # result already landed
        tid = spec.task_id.binary()
        self._cancelled.add(tid)
        state = self._sched.get(spec.scheduling_key())
        if state is not None:
            for item in list(state.queue):
                if item[0].task_id.binary() == tid:
                    try:
                        state.queue.remove(item)
                    except ValueError:
                        break  # a lease loop grabbed it: fall through
                    self._finish_cancel(spec)
                    return True
        conn = self._task_sites.get(tid)
        if conn is not None:
            try:
                self.lt.run(conn.notify("cancel_task", {
                    "task_id": tid, "force": force}))
            except Exception:
                pass
        return True

    @staticmethod
    def _is_spurious_cancel(err: dict) -> bool:
        """Only OUR injected class counts — user code that legitimately
        raises TaskCancelledError (e.g. it got a cancelled ref) must keep
        normal error semantics."""
        pickled = err.get("pickled")
        if not pickled:
            return False
        try:
            return isinstance(serialization.loads_function(pickled),
                              exceptions.TaskInterruptedByCancel)
        except Exception:
            return False

    def _finish_cancel(self, spec: TaskSpec):
        """Fulfill a cancelled task's refs with TaskCancelledError and
        drop its pins."""
        self._cancelled.discard(spec.task_id.binary())
        try:
            pickled = serialization.dumps_function(
                exceptions.TaskCancelledError(
                    f"task {spec.function_name} was cancelled"))
        except Exception:
            pickled = None
        # _store_error releases the arg refs and extra pins itself
        self._store_error(spec, _ErrorValue(
            f"task {spec.function_name} was cancelled", pickled,
            spec.function_name))

    def _propagate_error(self, spec: TaskSpec, error_value):
        if isinstance(error_value, _ErrorValue):
            self._store_error(spec, error_value)
        else:
            self._fail_task(spec, f"dependency failed: {error_value!r}")

    # ---------------------------------------------------------------- actors
    def create_actor(self, spec: TaskSpec, *, name: Optional[str],
                     detached: bool, get_if_exists: bool = False) -> bytes:
        self._stamp_trace_ctx(spec)
        # creation specs carry t_submit like any task: the constructor
        # runs as a task on the placed worker, and downstream consumers
        # (serve replica cold-start attribution) measure scheduling +
        # spawn wait from this stamp
        self._stamp_submit(spec)
        reply = self.controller.call("register_actor", {
            "spec": spec.to_wire(), "name": name,
            "max_restarts": spec.max_restarts, "detached": detached,
            "get_if_exists": get_if_exists})
        if reply.get("error"):
            raise exceptions.RayTpuError(reply["error"])
        actor_id = reply["actor_id"]
        if actor_id not in self._actors:
            self._actors[actor_id] = _ActorState(actor_id, spec.function_name)
        return actor_id

    def attach_actor(self, actor_id: bytes, class_name: str):
        if actor_id not in self._actors:
            self._actors[actor_id] = _ActorState(actor_id, class_name)

    def submit_actor_task(self, actor_id: bytes, spec: TaskSpec,
                          max_task_retries: int = 0,
                          temp_refs: Optional[List["ObjectRef"]] = None
                          ) -> List[ObjectRef]:
        self._stamp_trace_ctx(spec)
        self._stamp_submit(spec)
        with self._ref_lock:
            for oid in spec.return_ids():
                self._owned.add(oid.binary())
        refs = [ObjectRef(oid, self) for oid in spec.return_ids()]
        for oid in spec.arg_ref_ids():
            self._add_local_ref(oid.binary())
        extra = [r.binary() for r in (temp_refs or [])]
        if extra:
            for b in extra:
                self._add_local_ref(b)
            self._extra_pins_map[spec.task_id.binary()] = extra
        del temp_refs
        self._enqueue_submission(
            self._submit_actor_pipeline(actor_id, spec,
                                        max_task_retries))
        return refs

    async def _submit_actor_pipeline(self, actor_id: bytes, spec: TaskSpec,
                                     attempts_left: int):
        try:
            ok = await self._resolve_dependencies(spec)
            if not ok:
                return
            state = self._actors[actor_id]
            if state.lock is None:
                state.lock = asyncio.Lock()
            async with state.lock:
                conn = await self._get_actor_conn(state)
                if conn is None:
                    self._fail_actor_task(spec, state)
                    return
                spec.d["seq"] = state.seq
                state.seq += 1
            self._note_dispatch(spec)
            try:
                reply = await conn.call("push_actor_task",
                                        {"spec": spec.to_wire()}, timeout=None)
            except rpc.RpcError:
                # Connection dropped: actor crashed or is restarting.
                state.conn = None
                state.address = None
                if attempts_left > 0:
                    await asyncio.sleep(GlobalConfig.actor_restart_delay_s)
                    await self._submit_actor_pipeline(actor_id, spec,
                                                      attempts_left - 1)
                else:
                    info = await self._wait_actor_info(actor_id, timeout=5)
                    reason = (info or {}).get("death_cause") or "connection lost"
                    self._store_error(spec, _ErrorValue(
                        f"actor died: {reason}", None, spec.function_name,
                        is_actor=True, actor_down=True))
                return
            self._handle_task_reply(spec, reply, 0, None)
        except Exception as e:
            self._fail_task(spec, f"actor submission failed: {e!r}")

    async def _wait_actor_info(self, actor_id: bytes, timeout: float = 60.0):
        """Actor-state poll that SURVIVES a controller failover: the
        raw connection dies with the leader mid-wait, so replay against
        the promoted standby instead of failing the actor submission
        (found as elastic repair's replacement rank dying with 'actor
        submission failed: ConnectionLost' when the leader was killed
        mid-repair)."""
        deadline = time.monotonic() + timeout \
            + GlobalConfig.ha_client_failover_timeout_s
        while True:
            try:
                conn = await self.controller.aconn()
                r = await conn.call(
                    "wait_actor",
                    {"actor_id": actor_id, "timeout": timeout},
                    timeout=timeout + 10)
            except (rpc.ConnectionLost, OSError, asyncio.TimeoutError):
                if time.monotonic() > deadline:
                    raise
                await asyncio.sleep(0.2)
                continue
            if isinstance(r, dict) and r.get("_not_leader"):
                if time.monotonic() > deadline:
                    raise rpc.RpcError(
                        "no leader controller emerged for wait_actor")
                await asyncio.sleep(0.2)
                continue
            return r

    async def _get_actor_conn(self, state: _ActorState):
        if state.conn is not None and not state.conn.closed:
            return state.conn
        # Poll until ALIVE or DEAD; PENDING/RESTARTING just means the actor
        # is still being (re)created — give it the full creation budget.
        deadline = time.monotonic() + GlobalConfig.actor_creation_timeout_s
        while True:
            info = await self._wait_actor_info(state.actor_id, timeout=30)
            st = info.get("state")
            if st == "ALIVE" and info.get("address"):
                state.quarantined = False
                break
            if st == "DEAD":
                state.dead_reason = info.get("death_cause") or "DEAD"
                return None
            if st == "QUARANTINED":
                state.dead_reason = info.get("death_cause") or "QUARANTINED"
                state.quarantined = True
                return None
            if time.monotonic() > deadline:
                state.dead_reason = f"still {st} after creation timeout"
                return None
        host, port = _split(info["address"])
        try:
            state.conn = await rpc.connect(host, port, retries=10)
        except rpc.ConnectionLost:
            return None
        state.address = info["address"]
        state.seq = 0  # fresh worker incarnation orders from zero
        return state.conn

    def _fail_actor_task(self, spec: TaskSpec, state: _ActorState):
        pickled = None
        if state.quarantined:
            # typed: callers distinguish a crash-loop quarantine (may
            # clear via TTL/operator) from a terminal death
            try:
                pickled = serialization.dumps_function(
                    exceptions.ActorQuarantinedError(
                        state.actor_id.hex(),
                        state.dead_reason or "crash loop"))
            except Exception:
                pickled = None
        self._store_error(spec, _ErrorValue(
            f"actor {state.actor_id.hex()[:12]} is dead: {state.dead_reason}",
            pickled, spec.function_name, is_actor=True, actor_down=True))

    def kill_actor(self, actor_id: bytes, no_restart: bool = True):
        state = self._actors.get(actor_id)
        if state is not None and state.conn is not None and not state.conn.closed:
            try:
                self.lt.run(state.conn.call("exit", {"restart": not no_restart},
                                            timeout=5))
            except rpc.RpcError:
                pass
        self.controller.call("kill_actor", {"actor_id": actor_id,
                                            "no_restart": no_restart})

    # -------------------------------------------------------------- plumbing
    async def _worker_conn(self, addr: str) -> rpc.Connection:
        conn = self._worker_conns.get(addr)
        if conn is None or conn.closed:
            conn = await rpc.connect(*_split(addr), retries=5)
            self._worker_conns[addr] = conn
        return conn

    async def _nodelet_conn(self, addr: str) -> rpc.Connection:
        if addr == self.nodelet_addr:
            return self.nodelet.conn
        conn = self._nodelet_conns.get(addr)
        if conn is None or conn.closed:
            conn = await rpc.connect(*_split(addr), retries=5)
            self._nodelet_conns[addr] = conn
        return conn

    async def _on_log(self, conn, data):
        if GlobalConfig.log_to_driver:
            print(f"({data.get('src', 'worker')}) {data.get('line', '')}",
                  flush=True)

    async def _on_nodes_pub(self, conn, data):
        for cb in list(self._node_listeners):
            try:
                cb(data)
            except Exception:
                pass

    def _on_controller_reconnect(self, bc):
        """The controller connection failed over (leader death → promoted
        standby): connection-scoped state must be re-established — the
        ``nodes`` pubsub subscription serve routers and train executors
        rely on lives on the dead TCP connection.  The promoted leader
        also holds NO spans (they never go through the WAL), so mark
        the ring dirty: the next flush re-ships this driver's full
        history to the new leader's timeline."""
        try:
            from ..util import tracing
            tracing.mark_dirty()
        except Exception:
            pass
        if not self._node_subscribed:
            return
        try:
            self.lt.spawn(bc.conn.call("subscribe", {"channel": "nodes"},
                                       timeout=10))
        except Exception:
            pass  # degraded: listeners fall back to table polling

    def subscribe_node_events(self, callback) -> None:
        """Register ``callback(event_dict)`` for controller ``nodes``
        pubsub events ({"event": "added"|"dead"|"draining", ...}).  The
        first registration subscribes this process's controller
        connection; callbacks run on the IO loop and must not block."""
        with self._node_sub_lock:
            self._node_listeners.append(callback)
            first = not self._node_subscribed
            self._node_subscribed = True
        if first:
            try:
                self.controller.call("subscribe", {"channel": "nodes"},
                                     timeout=10)
            except Exception:
                pass  # degraded: listeners fall back to table polling

    def unsubscribe_node_events(self, callback) -> None:
        """Drop a listener registered with :meth:`subscribe_node_events`
        (the controller subscription itself stays — other listeners may
        share it, and a bare subscription is one no-op push per event)."""
        with self._node_sub_lock:
            try:
                self._node_listeners.remove(callback)
            except ValueError:
                pass

    # -------------------------------------------------------------- shutdown
    def shutdown(self):
        if self._closed:
            return
        self._closed = True
        # Detach value finalizers first: after store.close() any late GC of a
        # zero-copy value must not call back into the (closed) ctypes client.
        for fin in self._value_finalizers:
            try:
                fin.detach()
            except Exception:
                pass
        self._value_finalizers.clear()
        # shutdown must not burn the HA failover budget redialing a
        # cluster that is being torn down
        try:
            self.controller.fail_fast()
        except Exception:
            pass
        self.final_span_flush()
        if self.mode == "driver":
            try:
                self.controller.call("finish_job",
                                     {"job_id": self.job_id.binary()}, timeout=5)
            except Exception:
                pass
            # the flush-loop claim is process-global; a driver that
            # reconnects (init -> shutdown -> init, i.e. every test
            # after the first) must be able to claim it again or its
            # spans never leave this process
            try:
                from ..util import tracing
                tracing.release_flusher()
            except Exception:
                pass
        for c in (self.controller, self.nodelet):
            try:
                c.close()
            except Exception:
                pass
        self.lt.stop()
        try:
            self.store.close()
        except Exception:
            pass


def _as_exception(value) -> Exception:
    if isinstance(value, Exception):
        return value
    if isinstance(value, (bytes, memoryview)):
        v = serialization.deserialize(memoryview(value))
        if isinstance(v, _ErrorValue):
            return v.unwrap()
        if isinstance(v, Exception):
            return v
    return exceptions.RayTpuError(str(value))


def _split(addr: str) -> Tuple[str, int]:
    host, port = addr.rsplit(":", 1)
    return host, int(port)


serialization.register_ref_class(ObjectRef)

_global_core: Optional[CoreClient] = None


def get_global_core() -> Optional[CoreClient]:
    return _global_core


def set_global_core(core: Optional[CoreClient]):
    global _global_core
    _global_core = core
