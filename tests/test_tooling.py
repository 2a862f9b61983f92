"""Tooling tests: state API, metrics, jobs, CLI, microbenchmark,
autoscaler (reference model: state API tests, `test_metrics_agent.py`,
job manager tests, `test_autoscaler_fake_multinode.py`)."""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import jobs, metrics, state


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_state_api(cluster):
    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    ray_tpu.get(a.ping.remote(), timeout=30.0)
    nodes = state.list_nodes()
    assert nodes and nodes[0]["alive"]
    actors = state.list_actors()
    assert any(x.get("class_name") == "A" for x in actors)
    summary = state.cluster_summary()
    assert summary["nodes"]["alive"] >= 1


def test_metrics_prometheus():
    c = metrics.Counter("req_total", "requests", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    g = metrics.Gauge("queue_len", "depth")
    g.set(7)
    h = metrics.Histogram("latency_s", "latency", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = metrics.prometheus_text()
    assert 'req_total{route="/a"} 3.0' in text
    assert "queue_len 7.0" in text
    assert 'latency_s_bucket{le="0.1"} 1' in text
    assert 'latency_s_bucket{le="+Inf"} 3' in text
    assert "latency_s_count 3" in text

    import urllib.request
    port = metrics.serve_metrics()
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics").read().decode()
    assert "req_total" in body


def test_job_submission(cluster, tmp_path):
    script = tmp_path / "job.py"
    script.write_text("print('hello from job'); import sys; sys.exit(0)\n")
    job_id = jobs.submit_job(f"{sys.executable} {script}")
    status = jobs.wait_job(job_id, timeout_s=60.0)
    assert status == jobs.SUCCEEDED
    assert "hello from job" in jobs.get_job_logs(job_id)
    assert any(j["job_id"] == job_id for j in jobs.list_jobs())

    bad = jobs.submit_job(f"{sys.executable} -c 'import sys; sys.exit(3)'")
    assert jobs.wait_job(bad, timeout_s=60.0) == jobs.FAILED


def test_microbenchmark_runs(cluster):
    from ray_tpu.microbenchmark import run_microbenchmarks
    res = run_microbenchmarks(min_time=0.2)
    assert res["tasks_per_s"] > 10
    assert res["actor_calls_per_s"] > 10
    assert res["put_1kb_per_s"] > 10


_AUTOSCALER_SCRIPT = """
import time
from ray_tpu import state
from ray_tpu.autoscaler import LocalNodeProvider, StandardAutoscaler, \\
    request_resources
from ray_tpu.cluster_utils import Cluster

cluster = Cluster()
cluster.add_node(num_cpus=1)
cluster.connect()
try:
    provider = LocalNodeProvider(
        cluster.session_dir, cluster.controller_addr,
        node_types={"worker": {"CPU": 2.0}})
    scaler = StandardAutoscaler(provider, max_workers=2,
                                idle_timeout_s=0.5)
    request_resources([{"CPU": 2.0}])
    actions = scaler.update()
    assert len(actions["launched"]) == 1, actions
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if sum(1 for n in state.list_nodes() if n["alive"]) >= 2:
            break
        time.sleep(0.2)
    assert sum(1 for n in state.list_nodes() if n["alive"]) >= 2
    scaler.update()      # marks the new node idle-since-now
    time.sleep(0.7)
    actions = scaler.update()
    assert len(actions["terminated"]) == 1, actions
    assert provider.non_terminated_nodes() == []
    print("AUTOSCALER_OK")
finally:
    cluster.shutdown()
"""


def test_autoscaler_scales_up_and_down(tmp_path):
    # own cluster + driver: run in a subprocess so the module fixture's
    # runtime isn't disturbed
    script = tmp_path / "autoscale.py"
    script.write_text(_AUTOSCALER_SCRIPT)
    repo_root = os.path.abspath(os.path.dirname(__file__) + "/..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo_root + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, str(script)],
                         capture_output=True, text=True, env=env,
                         timeout=120, cwd=repo_root)
    assert "AUTOSCALER_OK" in out.stdout, out.stdout + out.stderr


def test_cli_microbenchmark_and_help(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "microbenchmark" in out.stdout
