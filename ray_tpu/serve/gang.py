"""Gang replicas: one Serve replica that SPANS multiple processes/hosts.

SURVEY.md §7 hard-part (5) and the BASELINE north star #5: a replica that
*is* a multi-host sharded program.  The reference has nothing like this —
its replica is one actor (`serve/_private/replica.py:250`), and its
reconcile loop (`serve/_private/deployment_state.py:958`) only manages
single-process replicas.  TPU-native serving of a TP-sharded model needs a
*gang*: one worker per TPU host, all joined into one `jax.distributed`
runtime, hosting ONE pjit program whose shards live across the gang.

Design:

  * the controller reserves a placement group (one bundle per gang member;
    `tpu_slice_placement_group` shape for TPU slices) and spawns
    ``gang_size`` `GangReplicaWorker` actors into it,
  * every member joins a mesh gang (`parallel.coordinator.join_mesh_gang`
    — controller-KV rendezvous → `jax.distributed.initialize` → one global
    `Mesh` spanning the members' devices),
  * the member whose gang rank is 0 is the **leader**: the routing table
    entry for the replica carries only the leader's handle, so the router
    addresses the whole gang as one unit (in-flight caps, round-robin, and
    autoscaling all see one replica),
  * `handle_request` on the leader fans the request out to the followers
    and executes its own shard; every member enters the same jitted
    computation and XLA's collectives (ICI on TPU, Gloo on the CPU test
    mesh) rendezvous the gang inside the program.  The leader's return
    value (replicated or leader-addressable out_shardings) answers the
    request.

The user callable reads its gang context (mesh, rank, world size) via
`get_gang_context()` in ``__init__`` and pjit-shards its model over
``ctx.mesh``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

_CTX: Optional["GangContext"] = None


@dataclasses.dataclass
class GangContext:
    """What a deployment callable sees when it runs inside a gang."""

    mesh: Any                 # jax.sharding.Mesh spanning the gang
    rank: int                 # this member's gang rank (0 = leader)
    world_size: int
    group_name: str
    deployment_name: str
    replica_id: str


def get_gang_context() -> Optional[GangContext]:
    """The current gang context, or None outside a gang replica."""
    return _CTX


class GangReplicaWorker:
    """One member of a gang replica.  Rank 0 doubles as the leader."""

    def __init__(self, deployment_name: str, replica_id: str, rank: int,
                 world_size: int, group_name: str, callable_blob: bytes,
                 init_args: tuple, init_kwargs: Dict[str, Any],
                 user_config: Any, mesh_text: Optional[str]):
        global _CTX
        import inspect

        from ..core.serialization import loads_function
        from ..parallel.coordinator import join_mesh_gang
        from ..parallel.mesh import MeshSpec

        self.deployment_name = deployment_name
        self.replica_id = replica_id
        self.rank = rank
        self.world_size = world_size
        self._group_name = group_name
        self._peers: List[Any] = []   # leader only: follower handles
        spec = MeshSpec.parse(mesh_text) if mesh_text else None
        mesh = join_mesh_gang(group_name, world_size, rank=rank, spec=spec)
        _CTX = GangContext(mesh=mesh, rank=rank, world_size=world_size,
                           group_name=group_name,
                           deployment_name=deployment_name,
                           replica_id=replica_id)
        fc = loads_function(callable_blob)
        if inspect.isclass(fc):
            self._callable = fc(*init_args, **init_kwargs)
            self._is_function = False
        else:
            self._callable = fc
            self._is_function = True
        self._num_ongoing = 0
        self._total = 0
        # SPMD ordering machinery: every member must enter the compiled
        # program in the same request order or the collectives cross-match.
        # The leader serializes (lock held across fan-out + own execute, so
        # its send order IS its execution order); followers execute strictly
        # by the leader-assigned sequence number.
        import threading
        self._exec_lock = threading.Lock()
        self._seq = 0
        self._next_seq = 0
        self._num_executing = 0
        self._seq_cv = threading.Condition()
        if user_config is not None:
            self.reconfigure(user_config)

    # -- wiring ------------------------------------------------------------
    def set_peers(self, handles: List[Any]) -> bool:
        """Leader only: handles of ranks 1..world_size-1, in rank order."""
        self._peers = handles
        return True

    def ready(self) -> bool:
        return True

    def reconfigure(self, user_config: Any) -> bool:
        target = self._callable
        if not self._is_function and hasattr(target, "reconfigure"):
            target.reconfigure(user_config)
        return True

    # -- request path ------------------------------------------------------
    def handle_request(self, args: tuple, kwargs: Dict[str, Any],
                       method: Optional[str] = None,
                       request_id: Optional[str] = None) -> Any:
        """Leader entry point: fan out to followers, compute own shard.

        Followers are invoked asynchronously BEFORE the leader executes so
        all members enter the jitted program (whose collectives block until
        the whole gang arrives).  Per-caller actor ordering guarantees every
        member sees requests in the same sequence — the SPMD requirement."""
        from .. import api
        self._num_ongoing += 1
        self._total += 1
        try:
            with self._exec_lock:
                seq = self._seq
                self._seq += 1
                futs = [p.participate.remote(seq, args, kwargs, method)
                        for p in self._peers]
                result = self._execute(args, kwargs, method)
            # Surface follower failures (a dead member means the gang's
            # program can no longer run; the controller replaces the whole
            # replica).
            api.get(futs, timeout=300.0)
            return result
        finally:
            self._num_ongoing -= 1

    def participate(self, seq: int, args: tuple, kwargs: Dict[str, Any],
                     method: Optional[str]) -> bool:
        """Follower side of one request: run the same computation, strictly
        in leader-assigned sequence order (concurrent actor threads would
        otherwise race into the collectives out of order)."""
        import time as _time
        with self._seq_cv:
            # The deadline bounds *stall*, not total wait: it resets while
            # _next_seq advances AND while an earlier request of this gang
            # member is still executing (a single long request — compile,
            # long-context generation — is progress, not a gap).  Only a
            # true fan-out gap (nothing running, nothing advancing for the
            # full window) trips it.
            from ..core.config import GlobalConfig
            stall_s = GlobalConfig.serve_gang_stall_timeout_s
            deadline = _time.monotonic() + stall_s
            last_seen = self._next_seq
            while seq != self._next_seq:
                if self._next_seq != last_seen or self._num_executing > 0:
                    last_seen = self._next_seq
                    deadline = _time.monotonic() + stall_s
                if _time.monotonic() > deadline:
                    # a gap in the sequence (leader failed mid-fan-out):
                    # fail loudly instead of wedging this thread forever
                    raise RuntimeError(
                        f"gang member {self.rank} stuck waiting for seq "
                        f"{self._next_seq} (got {seq}); leader fan-out "
                        "gap — replica needs replacement")
                self._seq_cv.wait(timeout=30.0)
            self._num_executing += 1
        try:
            self._execute(args, kwargs, method)
        finally:
            with self._seq_cv:
                self._num_executing -= 1
                self._next_seq = seq + 1
                self._seq_cv.notify_all()
        return True

    def _execute(self, args: tuple, kwargs: Dict[str, Any],
                 method: Optional[str]) -> Any:
        import asyncio
        import inspect
        target = self._callable
        if not self._is_function and method:
            target = getattr(target, method)
        elif not self._is_function:
            target = target.__call__
        result = target(*args, **kwargs)
        if inspect.iscoroutine(result):
            result = asyncio.run(result)
        return result

    def stats(self) -> Dict[str, Any]:
        return {"replica_id": self.replica_id, "rank": self.rank,
                "world_size": self.world_size,
                "num_ongoing": self._num_ongoing, "total": self._total}

    def shutdown_gang(self) -> bool:
        from ..parallel.coordinator import leave_mesh_gang
        try:
            leave_mesh_gang(self._group_name)
        except Exception:
            pass
        return True


def start_gang_replica(name: str, rid: str, entry: Dict[str, Any],
                       cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Controller-side: materialize one gang replica.

    Reserves the PG, spawns the members bundle-by-bundle, wires leader →
    followers, and blocks until every member finished its mesh join (the
    deployment is not routable before the program can run).  Returns the
    replica record for the routing table: ``handle`` is the LEADER."""
    from .. import api
    from ..util.placement_group import placement_group

    gang_size = int(cfg.get("gang_size", 1))
    strategy = cfg.get("gang_strategy", "PACK")
    opts = dict(cfg.get("ray_actor_options") or {})
    bundle_res = {"CPU": float(opts.get("num_cpus", 1.0))}
    for k, v in (opts.get("resources") or {}).items():
        bundle_res[k] = float(v)
    pg = placement_group([dict(bundle_res) for _ in range(gang_size)],
                         strategy=strategy, name=f"serve_gang_{rid}")
    pg.ready(timeout_seconds=120.0)

    group_name = f"serve_gang_{rid}"
    members = []
    for rank in range(gang_size):
        handle = api.remote(GangReplicaWorker).options(
            max_concurrency=int(cfg.get("max_concurrent_queries", 8)) + 4,
            num_cpus=bundle_res["CPU"],
            resources={k: v for k, v in bundle_res.items() if k != "CPU"},
            placement_group=pg, placement_group_bundle_index=rank,
            runtime_env=opts.get("runtime_env"),
            lifetime="detached",  # serve owns the lifecycle, not the job
        ).remote(name, rid, rank, gang_size, group_name,
                 entry["callable_blob"], entry["init_args"],
                 entry["init_kwargs"], cfg.get("user_config"),
                 cfg.get("gang_mesh"))
        members.append(handle)
    # Constructors run concurrently (the mesh join is a barrier); readiness
    # of all members implies jax.distributed linked the gang.
    from ..core.config import GlobalConfig
    api.get([m.ready.remote() for m in members],
            timeout=GlobalConfig.serve_gang_ready_timeout_s)
    api.get(members[0].set_peers.remote(members[1:]), timeout=60.0)
    return {"id": rid, "handle": members[0], "gang": members, "pg": pg}


def stop_gang_replica(rep: Dict[str, Any]) -> None:
    from .. import api
    from ..util.placement_group import remove_placement_group
    for m in rep.get("gang", []):
        try:
            api.kill(m)
        except Exception:
            pass
    pg = rep.get("pg")
    if pg is not None:
        try:
            remove_placement_group(pg)
        except Exception:
            pass
