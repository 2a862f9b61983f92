"""Object-broadcast benchmark across local nodelets.

Fills the reference's release-benchmark row "broadcast a 1 GiB object"
(`/root/reference/release/benchmarks/README.md:16-19` — 1 GiB to 50+
nodes) at this harness's scale: one driver `put` on the head node's
shm store, one actor pinned to each OTHER nodelet `get`s it, so every
byte crosses the C++ transfer plane (store-to-store TCP,
`ray_tpu/core/object_store/transfer.cc`) exactly once per receiving
node.  All nodelets share this machine, so the number is a
single-machine upper bound on the per-link plane, not a network claim
— the useful signals are scaling shape (per-node bandwidth as receiver
count grows) and the zero-copy path holding up at GiB sizes.

Prints a markdown table + one JSON line; writes BROADCAST_BENCH.json.
"""

import json
import os
import sys
import time

os.environ.setdefault("RAY_TPU_DASHBOARD_AGENT", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np                                             # noqa: E402

import ray_tpu                                                 # noqa: E402
from ray_tpu.cluster_utils import Cluster                      # noqa: E402
from ray_tpu.util.scheduling_strategies import (               # noqa: E402
    NodeAffinitySchedulingStrategy)


@ray_tpu.remote
class Receiver:
    def fetch(self, wrapped_ref):
        # actor-side get: pulls the object into THIS node's store via
        # the transfer plane, returns (first+last byte, elapsed seconds).
        # The ref rides NESTED in a list — a top-level ref arg would be
        # auto-resolved (and transferred) before the timer starts.
        t0 = time.perf_counter()
        arr = ray_tpu.get(wrapped_ref[0], timeout=300.0)
        dt = time.perf_counter() - t0
        return int(arr[0]), int(arr[-1]), dt


def bench(n_receivers: int, size_mb: int, cluster: Cluster) -> dict:
    size = size_mb * 1024 * 1024
    payload = np.arange(size, dtype=np.uint8)  # wraps mod 256; non-zero
    ref = ray_tpu.put(payload)
    receivers = [
        Receiver.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=cluster.nodes[i + 1].node_id)).remote()
        for i in range(n_receivers)]
    # readiness barrier so spawn time stays out of the bandwidth number
    ray_tpu.get([r.fetch.remote([ray_tpu.put(np.zeros(1, np.uint8))])
                 for r in receivers], timeout=120.0)
    t0 = time.perf_counter()
    out = ray_tpu.get([r.fetch.remote([ref]) for r in receivers],
                      timeout=600.0)
    wall = time.perf_counter() - t0
    for first, last, _ in out:
        assert first == 0 and last == (size - 1) % 256, "payload corrupt"
    per_node = [dt for _, _, dt in out]
    total_gb = n_receivers * size / 1e9
    row = {
        "receivers": n_receivers, "size_mb": size_mb,
        "wall_s": round(wall, 3),
        "aggregate_GBps": round(total_gb / wall, 2),
        "per_node_GBps_median": round(
            size / 1e9 / sorted(per_node)[len(per_node) // 2], 2),
    }
    for r in receivers:
        ray_tpu.kill(r)
    del ref
    return row


def main() -> None:
    n_workers = 4
    cluster = Cluster()
    # head (driver attach) + workers; stores sized for the 1 GiB row
    for _ in range(n_workers + 1):
        cluster.add_node(num_cpus=2,
                         object_store_memory=1536 * 1024 * 1024)
    cluster.connect(cluster.nodes[0])
    rows = []
    try:
        for n_recv, size_mb in ((1, 64), (4, 64), (1, 1024), (4, 1024)):
            rows.append(bench(n_recv, size_mb, cluster))
            print(f"# {rows[-1]}", flush=True)
    finally:
        cluster.shutdown()
    print("\n| receivers | size | wall s | aggregate GB/s | per-node GB/s |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['receivers']} | {r['size_mb']} MiB | {r['wall_s']} "
              f"| {r['aggregate_GBps']} | {r['per_node_GBps_median']} |")
    result = {
        "metric": "broadcast_1gib_4node_aggregate_GBps",
        "value": rows[-1]["aggregate_GBps"], "unit": "GB/s",
        # reference row is feasibility at 50 nodes, not a bandwidth
        # number; vs_baseline 1.0 = the capability row is filled
        "vs_baseline": 1.0,
        "detail": {"rows": rows, "plane": "store-to-store TCP "
                   "(transfer.cc), single machine"},
    }
    print(json.dumps(result))
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BROADCAST_BENCH.json"), "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    sys.exit(main())
