"""Runtime self-metrics battery (reference: the predefined metric set of
src/ray/stats/metric_defs.cc, exported per component and aggregated)."""

import time

import pytest

import ray_tpu
from ray_tpu import state


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_cluster_metrics_exposition(cluster):
    @ray_tpu.remote
    def f(x):
        return x + 1

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    assert ray_tpu.get([f.remote(i) for i in range(20)], timeout=60) == \
        list(range(1, 21))
    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"

    def check(text: str) -> None:
        # exposition format sanity
        assert "# TYPE ray_tpu_tasks_finished_total counter" in text
        assert "# TYPE ray_tpu_worker_pool_size gauge" in text
        # the elastic-recovery battery is registered wherever the train
        # driver runs: recovery-time histogram + lost-steps/repairs counters
        assert "# TYPE ray_tpu_train_repairs_total counter" in text
        assert "# TYPE ray_tpu_train_repair_lost_steps_total counter" in text
        assert "# TYPE ray_tpu_train_repair_seconds histogram" in text
        # the controller-HA battery (core/ha.py): failover counter +
        # outage histogram + WAL replication lag gauge
        assert "# TYPE ray_tpu_controller_failovers_total counter" in text
        assert "# TYPE ray_tpu_controller_failover_seconds histogram" in text
        assert ("# TYPE ray_tpu_controller_wal_replication_lag_records gauge"
                in text)
        # the partition-tolerance battery: suspect-quarantine transitions,
        # the fetch-ladder rung counter, and the connectivity-matrix gauge
        assert "# TYPE ray_tpu_node_suspect_transitions_total counter" in text
        assert "# TYPE ray_tpu_object_fetch_fallbacks_total counter" in text
        assert "# TYPE ray_tpu_peer_unreachable_pairs gauge" in text
        # the PR-10 attribution battery: per-op RPC handler counters (folded
        # from the rpc.py dispatch table), WAL append/fsync timing, and the
        # scheduler wave instruments
        assert "# TYPE ray_tpu_rpc_handler_calls_total counter" in text
        assert "# TYPE ray_tpu_rpc_handler_seconds_total counter" in text
        assert "# TYPE ray_tpu_rpc_handler_bytes_total counter" in text
        assert "# TYPE ray_tpu_controller_wal_appends_total counter" in text
        assert ("# TYPE ray_tpu_controller_wal_fsync_seconds_total counter"
                in text)
        assert "# TYPE ray_tpu_scheduler_waves_total counter" in text
        assert ("# TYPE ray_tpu_scheduler_queue_depth_at_grant histogram"
                in text)
        assert "# TYPE ray_tpu_scheduler_wave_batch_size histogram" in text

        def sample_sum(name: str) -> float:
            total = 0.0
            for line in text.splitlines():
                if line.startswith(name) and not line.startswith("#"):
                    total += float(line.rsplit(" ", 1)[1])
            return total

        # the battery reflects the work above
        assert sample_sum("ray_tpu_tasks_finished_total") >= 20
        assert sample_sum("ray_tpu_scheduler_leases_granted_total") >= 1
        assert sample_sum("ray_tpu_rpc_handler_calls_total") >= 20
        assert sample_sum("ray_tpu_scheduler_waves_total") >= 1
        assert sample_sum("ray_tpu_controller_wal_appends_total") >= 1
        assert sample_sum("ray_tpu_workers_spawned_total") >= 1
        assert sample_sum("ray_tpu_actors_created_total") >= 1
        assert sample_sum("ray_tpu_nodes_alive") >= 1
        assert sample_sum("ray_tpu_object_store_capacity_bytes") > 0
        # ≥20 distinct metric families defined (the battery, not a token few)
        families = {line.split(" ")[2] for line in text.splitlines()
                    if line.startswith("# TYPE ray_tpu_")}
        assert len(families) >= 20, sorted(families)

    # one scrape may miss a registry (`cluster_metrics_text` passes over
    # a controller or node whose reply is late) and counters fold on a
    # period: take the text again until every assertion holds
    deadline = time.monotonic() + 60
    while True:
        try:
            return check(state.cluster_metrics_text())
        except AssertionError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
