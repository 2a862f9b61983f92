"""Public API: init/shutdown, @remote, get/put/wait, actors.

The user-facing surface mirroring the reference's
python/ray/_private/worker.py:1031 (init), remote_function.py:239
(RemoteFunction._remote) and actor.py (ActorClass/ActorHandle), built on the
CoreClient direct task transport.
"""

from __future__ import annotations

import functools
import hashlib
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from . import exceptions
from .core import serialization
from .core.config import GlobalConfig
from .core.driver import (CoreClient, ObjectRef, ObjectRefGenerator,
                          get_global_core, set_global_core)
from .core.ids import ActorID, ObjectID, PlacementGroupID, TaskID
from .core.node import LocalCluster
from .core.task_spec import DYNAMIC_RETURNS, TaskSpec

_init_lock = threading.RLock()
_local_cluster: Optional[LocalCluster] = None
# {"JAX_PLATFORMS": what it held (None: unset)} before init() pinned this
# driver to the CPU; shutdown() puts it back
_env_before_init: Optional[Dict[str, Optional[str]]] = None


def is_initialized() -> bool:
    return get_global_core() is not None


def init(address: Optional[str] = None, *, num_cpus: Optional[int] = None,
         num_tpus: Optional[int] = None,
         resources: Optional[Dict[str, float]] = None,
         object_store_memory: Optional[int] = None,
         nodelet_addr: Optional[str] = None,
         ignore_reinit_error: bool = False,
         system_config: Optional[Dict[str, Any]] = None) -> "ClientContext":
    """Start (or connect to) a cluster and attach this process as a driver."""
    global _local_cluster, _env_before_init
    with _init_lock:
        if is_initialized():
            if ignore_reinit_error:
                return ClientContext(get_global_core())
            raise RuntimeError("ray_tpu.init() called twice "
                               "(pass ignore_reinit_error=True to allow)")
        if system_config:
            GlobalConfig.update(system_config)
        # a driver never holds the chip: it belongs to the worker that
        # reserved it (core/accelerator.py).  The node it starts is
        # described by JAX_PLATFORMS as the user had it, not by the pin.
        from .core import accelerator
        _env_before_init = {"JAX_PLATFORMS": accelerator.pin_to_cpu()}
        try:
            if address is None:
                res = dict(resources or {})
                if num_cpus is not None:
                    res["CPU"] = float(num_cpus)
                if num_tpus is not None:
                    res["TPU"] = float(num_tpus)
                _local_cluster = LocalCluster(
                    resources=res or None,
                    object_store_memory=object_store_memory or 0,
                    node_env=_env_before_init)
                controller_addr = _local_cluster.controller_addr
                nodelet_addr = _local_cluster.nodelet_addr
                store_path = _local_cluster.store_path
                node_id = _local_cluster.node_id
                session_dir = _local_cluster.session_dir
            else:
                if address == "auto":
                    # reference ray.init(address="auto"): resolve from the
                    # environment (ray-tpu exec/attach/start export these)
                    address = os.environ.get("RAY_TPU_ADDRESS")
                    if address is None:
                        raise ValueError(
                            "address='auto' needs RAY_TPU_ADDRESS in the "
                            "environment (ray-tpu exec/attach set it)")
                controller_addr = address
                if nodelet_addr is None:
                    nodelet_addr = os.environ.get("RAY_TPU_NODELET")
                if nodelet_addr is None:
                    raise ValueError(
                        "connecting to an existing cluster requires "
                        "nodelet_addr of a local nodelet")
                from .core import rpc as _rpc
                lt = _rpc.EventLoopThread("bootstrap")
                try:
                    host, port = nodelet_addr.rsplit(":", 1)
                    client = _rpc.BlockingClient.connect(lt, host, int(port))
                    info = client.call("node_info", timeout=10)
                    store_path = info["store_path"]
                    node_id = info["node_id"]
                    client.close()
                finally:
                    lt.stop()
                session_dir = os.environ.get("RAY_TPU_SESSION_DIR",
                                             "/tmp/ray_tpu")
            core = CoreClient(controller_addr=controller_addr,
                              nodelet_addr=nodelet_addr,
                              store_path=store_path, node_id=node_id,
                              session_dir=session_dir, mode="driver")
            set_global_core(core)
            _register_atexit_span_flush()
            return ClientContext(core)
        except BaseException:
            shutdown()        # also undoes the pin
            raise


_atexit_flush_registered = False


def _register_atexit_span_flush() -> None:
    """A driver that exits without calling shutdown() (script end,
    exception) still ships its final span batch — up to one
    trace_flush_interval_s of spans otherwise evaporates with the
    process.  CoreClient.shutdown() does the same flush inline for the
    orderly path; flush_batch() hands each span out once, so whichever
    runs second is a no-op."""
    global _atexit_flush_registered
    if _atexit_flush_registered:
        return
    _atexit_flush_registered = True
    import atexit

    def _flush():
        core = get_global_core()
        if core is None or core._closed:
            return
        core.final_span_flush()
    atexit.register(_flush)


def shutdown():
    global _local_cluster, _env_before_init
    with _init_lock:
        core = get_global_core()
        if core is not None:
            try:
                from . import usage
                usage.maybe_write_report(core.session_dir)
            except Exception:
                pass
        if core is not None:
            core.shutdown()
            set_global_core(None)
        if _local_cluster is not None:
            _local_cluster.shutdown()
            _local_cluster = None
        if _env_before_init is not None:
            for name, value in _env_before_init.items():
                if value is None:
                    os.environ.pop(name, None)
                else:
                    os.environ[name] = value
            _env_before_init = None


def _ensure_initialized() -> CoreClient:
    core = get_global_core()
    if core is not None:
        return core
    # Inside a worker process the runtime exports its context so nested
    # remote()/get() calls attach to the running cluster.
    info = os.environ.get("RAY_TPU_WORKER_CONTEXT")
    if info:
        import json
        ctx = json.loads(info)
        with _init_lock:
            core = get_global_core()
            if core is None:
                core = CoreClient(controller_addr=ctx["controller"],
                                  nodelet_addr=ctx["nodelet"],
                                  store_path=ctx["store"],
                                  node_id=ctx["node_id"],
                                  session_dir=ctx["session_dir"], mode="worker")
                set_global_core(core)
        return core
    init()
    return get_global_core()


class ClientContext:
    def __init__(self, core: CoreClient):
        self.core = core

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        shutdown()


# ----------------------------------------------------------------- object ops
def put(value: Any, *, xlang: bool = False) -> ObjectRef:
    """Store a value.  ``xlang=True`` uses the cross-language RTX1
    encoding (msgpack-typed values only) so C++ workers can consume the
    object (`cpp_function` / `cpp_actor` args)."""
    return _ensure_initialized().put(value, xlang=xlang)


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None):
    core = _ensure_initialized()
    single = isinstance(refs, ObjectRef)
    ref_list = [refs] if single else list(refs)
    for r in ref_list:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() expects ObjectRef(s), got {type(r)}")
    values = core.get(ref_list, timeout)
    return values[0] if single else values


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None):
    core = _ensure_initialized()
    if num_returns > len(refs):
        raise ValueError("num_returns exceeds the number of refs")
    return core.wait(list(refs), num_returns, timeout)


# ------------------------------------------------------------------- tasks
_DEFAULT_TASK_OPTIONS = dict(
    num_cpus=1.0, num_tpus=0.0, resources=None, num_returns=1,
    max_retries=None, retry_exceptions=False, scheduling_strategy=None,
    placement_group=None, placement_group_bundle_index=-1, name=None,
    runtime_env=None,
)

_DEFAULT_ACTOR_OPTIONS = dict(
    num_cpus=0.0, num_tpus=0.0, resources=None, max_restarts=0,
    max_task_retries=0, max_concurrency=1, concurrency_groups=None,
    name=None, lifetime=None,
    get_if_exists=False, scheduling_strategy=None, placement_group=None,
    placement_group_bundle_index=-1, num_returns=1, runtime_env=None,
)


def _normalize_num_returns(n) -> int:
    """"dynamic" → the sentinel; ints validated so a stray -1 can never
    silently activate the dynamic machinery."""
    if n == "dynamic":
        return DYNAMIC_RETURNS
    if isinstance(n, int) and not isinstance(n, bool) and n >= 0:
        return n
    raise ValueError(
        f"num_returns must be 'dynamic' or a non-negative int "
        f"(got {n!r})")


def _resolve_resources(opts: dict) -> Dict[str, float]:
    res = dict(opts.get("resources") or {})
    if opts.get("num_cpus"):
        res["CPU"] = float(opts["num_cpus"])
    if opts.get("num_tpus"):
        res["TPU"] = float(opts["num_tpus"])
    pg = opts.get("placement_group")
    strat = opts.get("scheduling_strategy")
    bundle = opts.get("placement_group_bundle_index", -1)
    if strat is not None and hasattr(strat, "placement_group"):
        pg = strat.placement_group
        bundle = strat.placement_group_bundle_index
    if pg is not None:
        hexid = pg.id.hex() if hasattr(pg, "id") else pg.hex()
        suffix = (f"_group_{bundle}_{hexid}" if bundle >= 0
                  else f"_group_{hexid}")
        res = {f"{k}{suffix}": v for k, v in res.items() if v > 0}
    return res


def _bundle_index(opts: dict) -> int:
    """Bundle index from either surface: the explicit option, or the
    PlacementGroupSchedulingStrategy (the way WorkerGroup and every
    reference-style caller passes it).  Reading only the option pinned
    every gang actor to bundle 0's node — on multi-node placement groups
    the rest of the gang could never place."""
    idx = opts.get("placement_group_bundle_index", -1)
    strat = opts.get("scheduling_strategy")
    if idx < 0 and strat is not None \
            and hasattr(strat, "placement_group_bundle_index"):
        idx = strat.placement_group_bundle_index
    return idx


def _strategy_dict(opts: dict) -> Dict[str, Any]:
    strat = opts.get("scheduling_strategy")
    d: Dict[str, Any] = {}
    if strat == "SPREAD":
        d["spread"] = True
    elif strat is not None and hasattr(strat, "node_id"):
        d["node_id"] = strat.node_id
        d["soft"] = getattr(strat, "soft", False)
    return d


class RemoteFunction:
    def __init__(self, fn, options: dict):
        self._fn = fn
        self._opts = {**_DEFAULT_TASK_OPTIONS, **options}
        self._fid: Optional[bytes] = None
        self._blob: Optional[bytes] = None
        functools.update_wrapper(self, fn)

    def options(self, **overrides) -> "RemoteFunction":
        rf = RemoteFunction(self._fn, {**self._opts, **overrides})
        rf._fid, rf._blob = self._fid, self._blob
        return rf

    def remote(self, *args, **kwargs):
        core = _ensure_initialized()
        if self._fid is None:
            blob = serialization.dumps_function(self._fn)
            self._fid = hashlib.sha256(blob).digest()[:20]
            self._blob = blob
        core.register_function(self._fid, self._blob)
        opts = self._opts
        max_retries = opts["max_retries"]
        if max_retries is None:
            max_retries = GlobalConfig.default_max_retries
        pg = opts.get("placement_group")
        strat = opts.get("scheduling_strategy")
        if strat is not None and hasattr(strat, "placement_group"):
            pg = strat.placement_group
        encoded_args, temp_refs = core.build_args(args, kwargs)
        spec = TaskSpec.build(
            task_id=TaskID.for_driver(core.job_id),
            job_id=core.job_id,
            function_id=self._fid,
            function_name=opts.get("name") or self._fn.__name__,
            args=encoded_args,
            # "dynamic" (reference: num_returns="dynamic"): one ref
            # resolving to an ObjectRefGenerator of worker-minted refs
            num_returns=_normalize_num_returns(opts["num_returns"]),
            resources=_resolve_resources(opts),
            owner_addr="",
            max_retries=max_retries,
            retry_exceptions=opts["retry_exceptions"],
            placement_group_id=PlacementGroupID(pg.id.binary())
            if pg is not None and hasattr(pg, "id") else None,
            bundle_index=_bundle_index(opts),
            scheduling_strategy=_strategy_dict(opts),
            runtime_env=opts.get("runtime_env"),
        )
        refs = core.submit_task(spec, temp_refs=temp_refs)
        return refs[0] if opts["num_returns"] in (1, "dynamic") else refs

    def __call__(self, *args, **kwargs):
        raise TypeError(f"Remote function {self._fn.__name__} cannot be called "
                        "directly; use .remote()")


# ------------------------------------------------------------------- actors
class ActorMethod:
    def __init__(self, handle: "ActorHandle", name: str, num_returns: int = 1,
                 concurrency_group: Optional[str] = None):
        self._handle = handle
        self._name = name
        self._num_returns = num_returns
        self._concurrency_group = concurrency_group

    def options(self, num_returns: int = 1,
                concurrency_group: Optional[str] = None):
        return ActorMethod(self._handle, self._name, num_returns,
                           concurrency_group)

    def remote(self, *args, **kwargs):
        return self._handle._submit_method(self._name, args, kwargs,
                                           self._num_returns,
                                           self._concurrency_group)


class ActorHandle:
    def __init__(self, actor_id: bytes, class_name: str,
                 method_names: List[str], max_task_retries: int = 0):
        self._actor_id = actor_id
        self._class_name = class_name
        self._method_names = method_names
        self._max_task_retries = max_task_retries

    @property
    def actor_id_hex(self) -> str:
        return self._actor_id.hex()

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        if self._method_names and name not in self._method_names:
            raise AttributeError(
                f"actor {self._class_name} has no method {name!r}")
        return ActorMethod(self, name)

    def _submit_method(self, method: str, args, kwargs, num_returns: int,
                       concurrency_group: Optional[str] = None):
        core = _ensure_initialized()
        core.attach_actor(self._actor_id, self._class_name)
        encoded_args, temp_refs = core.build_args(args, kwargs)
        spec = TaskSpec.build(
            task_id=TaskID.of(ActorID(self._actor_id)),
            job_id=core.job_id,
            function_id=b"\x00" * 20,
            function_name=method,
            args=encoded_args,
            num_returns=_normalize_num_returns(num_returns),
            resources={},
            owner_addr="",
            actor_id=ActorID(self._actor_id),
            concurrency_group=concurrency_group,
        )
        refs = core.submit_actor_task(self._actor_id, spec,
                                      self._max_task_retries,
                                      temp_refs=temp_refs)
        return refs[0] if num_returns in (1, "dynamic") else refs

    def __reduce__(self):
        return (ActorHandle, (self._actor_id, self._class_name,
                              self._method_names, self._max_task_retries))

    def __repr__(self):
        return f"ActorHandle({self._class_name}, {self._actor_id.hex()[:12]})"


class ActorClass:
    def __init__(self, cls, options: dict):
        self._cls = cls
        self._opts = {**_DEFAULT_ACTOR_OPTIONS, **options}
        self._fid: Optional[bytes] = None
        self._blob: Optional[bytes] = None

    def options(self, **overrides) -> "ActorClass":
        ac = ActorClass(self._cls, {**self._opts, **overrides})
        ac._fid, ac._blob = self._fid, self._blob
        return ac

    def remote(self, *args, **kwargs) -> ActorHandle:
        core = _ensure_initialized()
        if self._fid is None:
            blob = serialization.dumps_function(self._cls)
            self._fid = hashlib.sha256(blob).digest()[:20]
            self._blob = blob
        core.register_function(self._fid, self._blob)
        opts = self._opts
        actor_id = ActorID.of(core.job_id)
        pg = opts.get("placement_group")
        strat = opts.get("scheduling_strategy")
        if strat is not None and hasattr(strat, "placement_group"):
            pg = strat.placement_group
        encoded_args, temp_refs = core.build_args(args, kwargs)
        spec = TaskSpec.build(
            task_id=TaskID.of(actor_id),
            job_id=core.job_id,
            function_id=self._fid,
            function_name=self._cls.__name__,
            args=encoded_args,
            num_returns=0,
            resources=_resolve_resources(opts) or {"CPU": 0.0},
            owner_addr="",
            actor_creation_id=actor_id,
            max_concurrency=opts["max_concurrency"],
            concurrency_groups=opts.get("concurrency_groups"),
            max_restarts=opts["max_restarts"],
            placement_group_id=PlacementGroupID(pg.id.binary())
            if pg is not None and hasattr(pg, "id") else None,
            bundle_index=_bundle_index(opts),
            scheduling_strategy=_strategy_dict(opts),
            runtime_env=opts.get("runtime_env"),
        )
        # Creation-arg refs stay pinned for the actor's lifetime (the
        # worker resolves them whenever the actor is (re)started).
        for r in temp_refs:
            core._add_local_ref(r.binary())
        final_id = core.create_actor(
            spec, name=opts.get("name"),
            detached=opts.get("lifetime") == "detached",
            get_if_exists=opts.get("get_if_exists", False))
        methods = [m for m in dir(self._cls)
                   if not m.startswith("_") and callable(getattr(self._cls, m))]
        return ActorHandle(final_id, self._cls.__name__, methods,
                           opts.get("max_task_retries", 0))

    def __call__(self, *args, **kwargs):
        raise TypeError(f"Actor class {self._cls.__name__} cannot be "
                        "instantiated directly; use .remote()")


# --------------------------------------------------------------- C++ tasks
# Worker-side native execution (reference: cpp/src/ray/runtime/task/
# task_executor.cc executes RAY_REMOTE functions in C++ workers).  A cpp
# task's descriptor is "path/to/lib.so:Name" built against
# ray_tpu/cpp/task_api.h; the nodelet routes lang=="cpp" leases to native
# worker processes (core/nodelet.py _spawn_cpp_worker).  Arguments and
# returns cross in the RTX1 xlang format — msgpack-typed values only,
# plus ObjectRefs to other xlang objects.

def _encode_xlang_args(core, args: tuple) -> list:
    encoded = []
    for a in args:
        if isinstance(a, ObjectRef):
            encoded.append([1, a.binary()])          # ARG_REF
        else:
            encoded.append([0, serialization.serialize_xlang(a)])
    return encoded


class CppFunction:
    """Handle to a C++ function exported via RAY_TPU_REMOTE."""

    def __init__(self, library: str, symbol: str, options: dict):
        self._library = os.path.abspath(library)
        self._symbol = symbol
        self._opts = {**_DEFAULT_TASK_OPTIONS, **options}
        self._fname = f"{self._library}:{symbol}"
        self._fid = hashlib.sha256(self._fname.encode()).digest()[:20]

    def options(self, **overrides) -> "CppFunction":
        return CppFunction(self._library, self._symbol,
                           {**self._opts, **overrides})

    def remote(self, *args) -> ObjectRef:
        core = _ensure_initialized()
        opts = self._opts
        spec = TaskSpec.build(
            task_id=TaskID.for_driver(core.job_id),
            job_id=core.job_id,
            function_id=self._fid,
            function_name=self._fname,
            args=_encode_xlang_args(core, args),
            num_returns=1,
            resources=_resolve_resources(opts),
            owner_addr="",
            max_retries=opts["max_retries"] or 0,
            scheduling_strategy=_strategy_dict(opts),
            lang="cpp",
        )
        return core.submit_task(spec)[0]


class CppActorHandle:
    """Handle to a C++ actor; methods are invoked by name:
    ``handle.task("method", *args)``."""

    def __init__(self, actor_id: bytes, class_name: str):
        self._actor_id = actor_id
        self._class_name = class_name

    def task(self, method: str, *args) -> ObjectRef:
        core = _ensure_initialized()
        core.attach_actor(self._actor_id, self._class_name)
        spec = TaskSpec.build(
            task_id=TaskID.of(ActorID(self._actor_id)),
            job_id=core.job_id,
            function_id=b"\x00" * 20,
            function_name=method,
            args=_encode_xlang_args(core, args),
            num_returns=1,
            resources={},
            owner_addr="",
            actor_id=ActorID(self._actor_id),
            lang="cpp",
        )
        return core.submit_actor_task(self._actor_id, spec)[0]

    def __reduce__(self):
        return (CppActorHandle, (self._actor_id, self._class_name))

    def __repr__(self):
        return (f"CppActorHandle({self._class_name}, "
                f"{self._actor_id.hex()[:12]})")


class CppActorClass:
    def __init__(self, library: str, class_name: str, options: dict):
        self._library = os.path.abspath(library)
        self._class_name = class_name
        self._opts = {**_DEFAULT_TASK_OPTIONS, "max_concurrency": 1,
                      "max_restarts": 0, **options}
        self._fname = f"{self._library}:{class_name}"
        self._fid = hashlib.sha256(self._fname.encode()).digest()[:20]

    def options(self, **overrides) -> "CppActorClass":
        return CppActorClass(self._library, self._class_name,
                             {**self._opts, **overrides})

    def remote(self, *args) -> CppActorHandle:
        core = _ensure_initialized()
        actor_id = ActorID.of(core.job_id)
        spec = TaskSpec.build(
            task_id=TaskID.of(actor_id),
            job_id=core.job_id,
            function_id=self._fid,
            function_name=self._fname,
            args=_encode_xlang_args(core, args),
            num_returns=0,
            resources=_resolve_resources(self._opts) or {"CPU": 0.0},
            owner_addr="",
            actor_creation_id=actor_id,
            max_restarts=int(self._opts.get("max_restarts") or 0),
            scheduling_strategy=_strategy_dict(self._opts),
            lang="cpp",
        )
        final_id = core.create_actor(spec, name=self._opts.get("name"),
                                     detached=False)
        return CppActorHandle(final_id, self._class_name)


def cpp_function(library: str, symbol: str, **options) -> CppFunction:
    """A remote C++ function: ``cpp_function("libmy.so", "Add").remote(1, 2)``."""
    return CppFunction(library, symbol, options)


def cpp_actor(library: str, class_name: str, **options) -> CppActorClass:
    """A C++ actor class: ``cpp_actor("libmy.so", "Counter").remote()``."""
    return CppActorClass(library, class_name, options)


def remote(*args, **options):
    """``@remote`` / ``@remote(num_cpus=..., num_tpus=...)`` decorator."""
    def decorate(obj):
        if isinstance(obj, type):
            return ActorClass(obj, options)
        return RemoteFunction(obj, options)
    if len(args) == 1 and callable(args[0]) and not options:
        return decorate(args[0])
    if args:
        raise TypeError("remote() takes keyword options only")
    return decorate


# ----------------------------------------------------------------- cluster ops
def kill(actor: ActorHandle, *, no_restart: bool = True):
    _ensure_initialized().kill_actor(actor._actor_id, no_restart)


class RuntimeContext:
    """What `ray_tpu.get_runtime_context()` returns (reference:
    `ray.get_runtime_context()` / WorkerContext): identity and placement
    of the current driver / task / actor."""

    def __init__(self, core, spec, runtime):
        self._core = core
        self._spec = spec
        self._runtime = runtime

    @property
    def job_id(self) -> str:
        if self._spec is not None:
            # the SUBMITTING job (embedded in the task id), not the
            # worker process's own job context
            return self._spec.task_id.job_id().hex()
        return self._core.job_id.hex()

    @property
    def node_id(self) -> str:
        return self._core.node_id

    @property
    def worker_id(self) -> str:
        # inside a worker, report the id the nodelet REGISTERED (what
        # state/timeline/task tables show), not the lazily-created
        # CoreClient's random one
        wid = getattr(self._runtime, "worker_id", None)
        if wid is not None:
            return wid.hex() if isinstance(wid, bytes) else str(wid)
        return self._core.worker_id.hex()

    @property
    def task_id(self) -> Optional[str]:
        return self._spec.task_id.hex() if self._spec is not None else None

    @property
    def actor_id(self) -> Optional[str]:
        aid = getattr(self._runtime, "actor_id", None)
        return aid.hex() if aid else None

    def get_assigned_resources(self) -> Dict[str, float]:
        """The running task's resource request ({} on the driver)."""
        if self._spec is None:
            return {}
        return dict(self._spec.resources.to_dict())

    def to_dict(self) -> Dict[str, Any]:
        return {"job_id": self.job_id, "node_id": self.node_id,
                "worker_id": self.worker_id, "task_id": self.task_id,
                "actor_id": self.actor_id,
                "assigned_resources": self.get_assigned_resources()}


def get_runtime_context() -> RuntimeContext:
    """Identity/placement of the current execution context (reference:
    `ray.get_runtime_context`)."""
    from .core import worker_runtime as wr
    core = _ensure_initialized()
    return RuntimeContext(core, wr.current_task_spec(),
                          wr.current_worker_runtime())


def get_tpu_ids() -> List[int]:
    """Local indices for the TPU chips this task RESERVED (the TPU role
    of the reference's `ray.get_gpu_ids`): [] outside a task or for
    tasks that requested no TPU.

    Semantics differ from CUDA: chips are counted, not assigned.  A worker
    that holds a ``TPU`` reservation is the one process of its node whose
    JAX is on the TPU platform (core/accelerator.py), and it sees every
    local chip, so the indices are 0..n-1 into ``jax.local_devices()``.
    Reserve all of a host's chips in one worker (the SPMD layout): two
    concurrent sub-host reservations would be two processes opening the
    same chips, and the second fails at its first JAX call."""
    ctx = get_runtime_context()
    return list(range(int(ctx.get_assigned_resources().get("TPU", 0))))


def cancel(ref: ObjectRef, *, force: bool = False) -> bool:
    """Cancel the task producing ``ref`` (reference: `ray.cancel`).

    Queued tasks unschedule immediately; running tasks are interrupted
    in-band (or their worker killed with ``force=True``).  Getting a
    cancelled ref raises ``TaskCancelledError``.  Returns False when
    there is nothing to cancel: the task already finished, or the ref
    belongs to an actor task (kill the actor instead) or a put."""
    return _ensure_initialized().cancel(ref, force=force)


def get_actor(name: str) -> ActorHandle:
    core = _ensure_initialized()
    info = core.controller.call("get_named_actor", {"name": name})
    if info is None:
        raise ValueError(f"no live actor named {name!r}")
    return ActorHandle(info["actor_id"], info.get("class_name", ""), [], 0)


def nodes() -> List[dict]:
    return _ensure_initialized().controller.call("list_nodes")


def cluster_resources() -> Dict[str, float]:
    total: Dict[str, float] = {}
    for n in nodes():
        if n["alive"]:
            for k, v in n["total"].items():
                total[k] = total.get(k, 0.0) + v
    return total


def available_resources() -> Dict[str, float]:
    avail: Dict[str, float] = {}
    for n in nodes():
        if n["alive"]:
            for k, v in n["avail"].items():
                avail[k] = avail.get(k, 0.0) + v
    return avail


def timeline() -> List[dict]:
    """Chrome-trace events, cluster-wide: every process's
    task-lifecycle spans (submit → schedule → dequeue →
    fetch → exec → put, merged from the controller KV) + per-node
    finished-task spans (reference: ray.timeline / chrome_tracing_dump,
    _private/state.py:414).  ``state.timeline()`` returns the same
    spans wrapped as a ready-to-save Chrome-trace dict."""
    from .util import tracing
    return tracing.cluster_trace_events()
