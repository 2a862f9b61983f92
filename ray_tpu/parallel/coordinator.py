"""Multi-host SPMD mesh bootstrap over the cluster runtime.

The reference rendezvouses NCCL ranks through a TCP store created by rank 0
(`train/torch/config.py:69-113`) or a named unique-id actor
(`util/collective/collective_group/nccl_collective_group.py:29-34`).  The
TPU-native equivalent is a `jax.distributed`-style bring-up: every host in a
gang calls `join_mesh`, rank assignment and the coordinator address rendezvous
through the controller KV, then `jax.distributed.initialize` links the hosts
into one XLA runtime so a global `Mesh` spans the slice (collectives compile
onto ICI; cross-slice onto DCN).

On a single host (tests, one-chip dev) the gang degenerates gracefully: no
distributed init, the mesh is built from local devices.
"""

from __future__ import annotations

import socket
import time
from typing import Optional

from .mesh import MeshSpec, create_mesh
from ..api import _ensure_initialized

_NS = "mesh_gang"


def _kv(core):
    return core.controller


def join_mesh_gang(group_name: str, world_size: int,
                   rank: Optional[int] = None,
                   *, coordinator_port: int = 0,
                   timeout_s: float = 120.0,
                   spec: Optional[MeshSpec] = None):
    """Join the named gang and return a live `jax.sharding.Mesh` spanning it.

    Every member (one process per TPU host, gang-scheduled through a
    placement group) calls this with the same ``group_name``/``world_size``.
    Rank 0 (first to arrive, or explicit ``rank=0``) publishes the
    coordinator address; all call `jax.distributed.initialize`; the returned
    mesh covers all hosts' devices.
    """
    from ..core import accelerator
    core = _ensure_initialized()
    if world_size <= 1:
        accelerator.open_reserved_chip()   # timed: ``setup:chip_open``
        return create_mesh(spec)

    if rank is None:
        # First-come rank assignment: claim the lowest unclaimed slot with a
        # real compare-and-set (kv_put overwrite=False is atomic inside the
        # controller's single event loop) — no check-then-put race.
        claim = f"{socket.gethostname()}:{id(core)}".encode()
        for r in range(world_size):
            key = f"{group_name}/rank/{r}".encode()
            if _kv(core).call("kv_put", {"ns": _NS, "key": key,
                                         "value": claim,
                                         "overwrite": False}):
                rank = r
                break
        if rank is None:
            raise TimeoutError(f"could not claim a rank in {group_name}: "
                               f"all {world_size} slots taken")

    addr_key = f"{group_name}/coordinator".encode()
    if rank == 0:
        port = coordinator_port or _free_port()
        addr = f"{_local_ip()}:{port}"
        _kv(core).call("kv_put", {"ns": _NS, "key": addr_key,
                                  "value": addr.encode()})
    else:
        addr = _wait_for_key(core, addr_key, timeout_s)

    import jax
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=world_size,
                               process_id=rank)
    accelerator.open_reserved_chip()
    return create_mesh(spec)


def leave_mesh_gang(group_name: str) -> None:
    core = _ensure_initialized()
    for key in _kv(core).call("kv_keys",
                              {"ns": _NS, "prefix": group_name.encode()}):
        _kv(core).call("kv_del", {"ns": _NS, "key": key})
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def _wait_for_key(core, key: bytes, timeout_s: float) -> str:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        val = _kv(core).call("kv_get", {"ns": _NS, "key": key})
        if val:
            return val.decode()
        time.sleep(0.1)
    raise TimeoutError(f"rendezvous key {key!r} not published")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _local_ip() -> str:
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
