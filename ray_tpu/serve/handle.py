"""ServeHandle: Python-side calls into a deployment (reference:
`serve/handle.py` RayServeHandle / DeploymentHandle)."""

from __future__ import annotations

from typing import Any, Optional

from .. import api


class _MethodCaller:
    def __init__(self, handle: "ServeHandle", method: str):
        self._handle = handle
        self._method = method

    def remote(self, *args, **kwargs):
        return self._handle._call(args, kwargs, self._method)


class ServeHandle:
    def __init__(self, router, deployment_name: str):
        self._router = router
        self._name = deployment_name

    def remote(self, *args, **kwargs):
        """Returns an ObjectRef with the response."""
        return self._call(args, kwargs, None)

    def _call(self, args, kwargs, method: Optional[str]):
        ref, replica_id = self._router.assign_request(
            self._name, args, kwargs, method)
        # completion accounting piggybacks on result retrieval
        return _TrackedRef(ref, self._router, self._name, replica_id,
                           args, kwargs, method)

    def __getattr__(self, item: str) -> _MethodCaller:
        if item.startswith("_"):
            raise AttributeError(item)
        return _MethodCaller(self, item)


def is_replica_down_error(exc: BaseException) -> bool:
    """A failure that blames the REPLICA, not the request: killed mid-
    call (redeploy/scale-down race) or its worker died.  Typed — never
    inferred from message text, which would re-run non-idempotent user
    requests whose own errors merely mention 'died'."""
    from ..exceptions import ActorDiedError, WorkerCrashedError
    return isinstance(exc, (ActorDiedError, WorkerCrashedError))


def _shed_error(exc: BaseException):
    """The typed 503 signal, whether raised router-side (zero live
    replicas, sticky owner gone) or replica-side (decode-engine
    admission backpressure, draining engine) — the latter arrives
    wrapped in the remote TaskError."""
    from ..exceptions import ReplicaUnavailableError, TaskError
    if isinstance(exc, ReplicaUnavailableError):
        return exc
    if isinstance(exc, TaskError) and isinstance(
            getattr(exc, "cause", None), ReplicaUnavailableError):
        return exc.cause
    return None


def call_with_retry(router, name: str, args, kwargs,
                    method: Optional[str] = None,
                    timeout_s: float = 60.0, attempts: int = 3,
                    sticky_replica_id: Optional[str] = None,
                    prefix_tokens=None,
                    request_id: Optional[str] = None,
                    on_assigned=None) -> Any:
    """Assign + get with replica-failure retry under ONE deadline (the
    reference router's handling of dead replicas).  A request that
    raced a replica teardown re-routes to a live replica after a table
    refresh; user errors propagate untouched on the first attempt.
    Retry attempts are spaced by capped full-jitter backoff so a burst
    of failed requests doesn't hammer the table refresh and the
    surviving replicas in lockstep.

    A typed shed (``ReplicaUnavailableError`` — zero live replicas, or
    replica-side admission backpressure) carries a server-sent
    ``Retry-After`` hint; instead of the fixed retry cadence, attempts
    after a shed are spaced by full-jitter delays sampled from that
    hint (``uniform(0, retry_after * 2**n)``, capped) — the server said
    when to come back, and jitter keeps a burst of shed clients from
    returning in lockstep.  After ``attempts`` sheds the error
    propagates (the HTTP proxy maps it to 503 + Retry-After).

    A ``sticky_replica_id`` request (decode-session ops: the KV cache
    lives on one replica) never re-routes: the replica dying took the
    session with it, so the failure propagates for the caller to
    surface (the SSE lane's failover client re-admits the session on a
    healthy replica via teacher-forced replay).

    ``request_id`` rides to the replica (its spans carry it as ``rid``);
    ``on_assigned()`` is called each time the call has been handed to a
    replica — the end of the caller's routing leg."""
    import time as _time

    from ..core.config import GlobalConfig
    from ..util.backoff import ExponentialBackoff
    deadline = _time.monotonic() + timeout_s
    bo = ExponentialBackoff(base=GlobalConfig.serve_backoff_base_s,
                            cap=GlobalConfig.serve_backoff_cap_s)
    shed_bo = None   # built lazily from the first Retry-After hint

    def _shed_wait(shed) -> bool:
        """Sleep a full-jitter delay honoring the shed's Retry-After;
        False when the deadline can't absorb another wait."""
        nonlocal shed_bo
        remaining = deadline - _time.monotonic()
        if remaining <= 0:
            return False
        if shed_bo is None:
            ra = max(float(getattr(shed, "retry_after_s", 1.0) or 1.0),
                     1e-3)
            shed_bo = ExponentialBackoff(base=ra, cap=4.0 * ra)
        _time.sleep(min(shed_bo.next_delay(), remaining))
        return True

    for attempt in range(attempts):
        budget = max(0.1, deadline - _time.monotonic())
        try:
            # prefix_tokens only when set: scripted fake routers in
            # tests predate the affinity parameter
            extra = ({"prefix_tokens": prefix_tokens}
                     if prefix_tokens is not None else {})
            if request_id:
                extra["request_id"] = request_id
            ref, rid = router.assign_request(
                name, args, kwargs, method, timeout_s=budget,
                sticky_replica_id=sticky_replica_id, **extra)
        except Exception as e:
            shed = _shed_error(e)
            if shed is None or sticky_replica_id is not None \
                    or attempt == attempts - 1 or not _shed_wait(shed):
                raise
            continue
        if on_assigned is not None:
            on_assigned()
        try:
            return api.get(ref,
                           timeout=max(0.1,
                                       deadline - _time.monotonic()))
        except Exception as e:
            shed = _shed_error(e)
            if shed is not None and sticky_replica_id is None \
                    and attempt < attempts - 1 and _shed_wait(shed):
                continue
            if attempt == attempts - 1 or not is_replica_down_error(e) \
                    or sticky_replica_id is not None \
                    or _time.monotonic() >= deadline:
                raise
            router._refresh(force=True)
            _time.sleep(min(bo.next_delay(),
                            max(0.0, deadline - _time.monotonic())))
        finally:
            router.complete(name, rid)


class _TrackedRef:
    """ObjectRef wrapper that releases the router's in-flight slot when the
    result is fetched."""

    def __init__(self, ref, router, name, replica_id,
                 args=(), kwargs=None, method=None):
        self._ref = ref
        self._router = router
        self._name = name
        self._replica_id = replica_id
        self._args = args
        self._kwargs = kwargs or {}
        self._method = method
        self._done = False

    def result(self, timeout_s: float = 60.0) -> Any:
        import time as _time
        t0 = _time.monotonic()
        try:
            try:
                return api.get(self._ref, timeout=timeout_s)
            finally:
                self._release()
        except Exception as e:
            remaining = timeout_s - (_time.monotonic() - t0)
            if not is_replica_down_error(e) or remaining <= 0:
                raise
            self._router._refresh(force=True)
            return call_with_retry(self._router, self._name, self._args,
                                   self._kwargs, self._method,
                                   timeout_s=remaining, attempts=2)

    def _release(self):
        if not self._done:
            self._done = True
            self._router.complete(self._name, self._replica_id)

    @property
    def ref(self):
        return self._ref
