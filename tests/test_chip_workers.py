"""One process for each chip, as the nodelet and the controller keep it:
which worker a `TPU` reservation gets, when the reservation is available
again, and what a frozen host or a lagging controller does to a node.

No chip is needed.  A node started with ``JAX_PLATFORMS=tpu``, detection
off and a `TPU` given by hand runs `TPU` work in workers of the TPU
platform; work that never touches JAX runs there like anywhere."""

import os
import signal
import threading
import time

import pytest

import ray_tpu
from ray_tpu import api, state
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.config import GlobalConfig
from ray_tpu.util.placement_group import (placement_group,
                                          remove_placement_group)

_POOL = 3


@pytest.fixture
def chip_node_cluster():
    cluster = Cluster()
    cluster.add_node(num_cpus=8, num_tpus=1, env={
        "JAX_PLATFORMS": "tpu", "RAY_TPU_TPU_AUTODETECT": "0",
        "RAY_TPU_WORKER_POOL_MAX_SIZE": str(_POOL)})
    cluster.connect()
    try:
        yield cluster
    finally:
        cluster.shutdown()


def _available(name):
    (node,) = state.list_nodes()
    return node["avail"].get(name, 0.0)


def _wait_for(what, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while not what():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.1)


def test_a_pool_full_of_cpu_workers_does_not_starve_a_tpu_lease(
        chip_node_cluster):
    @ray_tpu.remote(num_cpus=1)
    def hold(seconds):
        time.sleep(seconds)
        return os.environ["JAX_PLATFORMS"], os.getpid()

    # as many CPU workers as the pool may hold, all idle afterwards (idle
    # workers are never reaped)
    held = ray_tpu.get([hold.remote(1.0) for _ in range(_POOL)], timeout=120)
    assert {platform for platform, _ in held} == {"cpu"}
    assert len({pid for _, pid in held}) == _POOL

    @ray_tpu.remote(num_tpus=1)
    def on_chip():
        return os.environ["JAX_PLATFORMS"], os.getpid()

    platform, pid = ray_tpu.get(on_chip.remote(), timeout=60)
    assert platform == "tpu" and pid not in {p for _, p in held}
    # a returned lease leaves its worker idle in the TPU pool, where the
    # next TPU work finds it: the same process, not a second one
    assert ray_tpu.get(on_chip.remote(), timeout=60) == (platform, pid)


def test_a_returned_bundle_takes_its_chip_worker_with_it(chip_node_cluster):
    """`TPU` is available again only when no process that ran under the
    reservation can still have the chip open.  An actor that outlives
    its placement group is killed with the bundle, and the bundle's
    resources (CPU included) come back once it is reaped."""
    pg = placement_group([{"CPU": 1, "TPU": 1}])
    assert pg.wait(30)

    @ray_tpu.remote(num_cpus=1, num_tpus=1)
    class Holder:
        def where(self):
            return os.environ["JAX_PLATFORMS"], os.getpid()

    holder = Holder.options(placement_group=pg,
                            placement_group_bundle_index=0).remote()
    platform, pid = ray_tpu.get(holder.where.remote(), timeout=60)
    assert platform == "tpu"
    assert _available("TPU") == 0.0

    remove_placement_group(pg)             # the actor was not killed first
    _wait_for(lambda: not os.path.exists(f"/proc/{pid}"))
    _wait_for(lambda: _available("TPU") == 1.0 and _available("CPU") == 8.0)
    # and the next claimant gets a process of its own
    @ray_tpu.remote(num_tpus=1)
    def on_chip():
        return os.getpid()

    assert ray_tpu.get(on_chip.remote(), timeout=60) != pid


def test_a_frozen_host_is_not_read_as_a_dead_node():
    """On the one-chip machine every process of the host freezes for up to
    ~7 s while a process that holds the chip starts or is torn down (my
    chip runs, PR 21: both daemons' loop lag peaked together at 7.0 and
    7.1 s).  The controller must not read its own freeze as five seconds
    of silence from the node and kill everything on it."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class Survivor:
            def pid(self):
                return os.getpid()

        actor = Survivor.remote()
        pid = ray_tpu.get(actor.pid.remote(), timeout=60)
        cluster = api._local_cluster
        daemons = [cluster.controller_proc.proc.pid,
                   cluster.nodelet_proc.proc.pid]
        for daemon in daemons:
            os.kill(daemon, signal.SIGSTOP)
        time.sleep(GlobalConfig.node_death_timeout_s + 1.5)
        for daemon in daemons:
            os.kill(daemon, signal.SIGCONT)
        time.sleep(GlobalConfig.node_death_timeout_s / 2)
        assert [n["alive"] for n in state.list_nodes()] == [True]
        # the actor that was there before the freeze is still the same one
        assert ray_tpu.get(actor.pid.remote(), timeout=60) == pid
        assert [a["state"] for a in state.list_actors()] == ["ALIVE"]
    finally:
        ray_tpu.shutdown()


def test_a_lagging_controller_still_finds_a_dead_node():
    """Silence is counted in the time the controller was able to listen,
    so a controller whose loop wakes late every single time still runs a
    silent node out of its time-out."""
    timeout_s = 2.0
    cluster = Cluster(heartbeat_timeout_s=timeout_s)
    silent = cluster.add_node(num_cpus=1)
    cluster.connect()
    controller = cluster.controller_proc.proc.pid
    lagging = threading.Event()
    lagging.set()

    def lag():
        # every wake-up of the health check (period: a third of the
        # time-out) comes more than a period late
        while lagging.is_set():
            os.kill(controller, signal.SIGSTOP)
            time.sleep(timeout_s * 0.8)
            os.kill(controller, signal.SIGCONT)
            time.sleep(0.3)

    stopper = threading.Thread(target=lag, daemon=True)
    try:
        assert [n["alive"] for n in state.list_nodes()] == [True]
        # silent, with its connection to the controller still open
        os.kill(silent.handle.proc.pid, signal.SIGSTOP)
        stopper.start()
        _wait_for(lambda: [n["alive"] for n in state.list_nodes()]
                  == [False], timeout_s=40.0)
    finally:
        lagging.clear()
        stopper.join()
        os.kill(silent.handle.proc.pid, signal.SIGCONT)
        cluster.shutdown()
