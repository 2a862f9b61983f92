"""Each kind of cell rehearsed end to end on the CPU at a tiny size, entered
as tests/test_chip_smoke.py enters chip_smoke.main(rehearsal=...): the
command line has no CPU mode, and a run of it without a TPU exits non-zero."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,trace", rehearse.cases())
def test_cell_kind_rehearsed_on_the_cpu(cell, trace):
    m = rehearse.manifest()
    chips = m.cell(cell)["chips"]
    e2e = {s["name"] for s in m.metrics_for(cell, False)}
    lines = rehearse.rehearse(cell, trace, seed=2**31 + 17 + trace)
    last = lines[-1]
    assert KEYS <= set(last)
    # a string: pytest cuts the repr of a list short, and the numbers
    # beside their limits are what a failure has to show
    assert last["correct"] is True, json.dumps(lines)[-2500:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"          # said, not assumed
    assert last["device"]["count"] == chips
    # set-up by phase before the last line, and each number by its limit
    phases = next(ln["setup_phases"] for ln in lines if "setup_phases" in ln)
    assert set(phases) == {"runtime_up_s", "worker_ready_s", "weights_s",
                           "warmup_s", "settle_s", "setup_s"}
    assert sum(v for k, v in phases.items() if k != "setup_s") == \
        pytest.approx(phases["setup_s"])
    compared = next(ln["compared"] for ln in lines if "compared" in ln)
    assert compared and all(r["inside"] for r in compared)
    got = last["metrics"]
    if trace:
        assert got["compiles_in_window"]["value"] == 0
        assert got["setup.runtime_up_s"]["value"] > 0
        assert ("gap_p95_ms" in got) == cell.endswith("serve-open")
        assert not e2e & set(got)
    else:
        assert got["setup_s"]["value"] == pytest.approx(phases["setup_s"])
        assert set(got) == e2e and len(e2e) == 2
        assert all(v["value"] > 0 and v["unit"] for v in got.values())


def test_command_line_needs_a_tpu():
    out = subprocess.run(
        [sys.executable, "-m", "perfbench", "--workload",
         mf.Manifest().data["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=mf.ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and '"metrics"' not in out.stdout
    assert "needs the TPU" in out.stderr


def test_no_result_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system to measure."""
    m = mf.Manifest()
    shutil.copy(m.path, tmp_path / "BENCHMARK.json")
    for p in m.data["paths"]:
        shutil.copytree(os.path.join(mf.ROOT, p), tmp_path / p)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench", "--workload",
         m.data["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode != 0 and '"metrics"' not in out.stdout
