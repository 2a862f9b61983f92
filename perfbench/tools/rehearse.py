"""One cell of the CPU rehearsal (testdata/rehearsal/) end to end at its
tiny size, entered as tests/test_chip_smoke.py enters
chip_smoke.main(rehearsal=...): the command line has no CPU mode.  Finds
wrong paths, arguments and control flow before a chip call; its timings mean
nothing.

    python3 -m perfbench.tools.rehearse <cell> [--trace 0|1] [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from typing import Any, Dict, List, Optional

from perfbench import manifest as mf

REHEARSAL = os.path.join("perfbench", "testdata", "rehearsal")


def manifest() -> mf.Manifest:
    return mf.Manifest(os.path.join(mf.ROOT, REHEARSAL, "BENCHMARK.json"),
                       os.path.join(mf.ROOT, REHEARSAL, "traffic"))


def cases() -> List[tuple]:
    """(cell, trace) for every ``cases/<cell>.json`` of the rehearsal: the
    runs tests/benchmark makes.  A cell added there is rehearsed with no
    edit to a test."""
    out = []
    folder = os.path.join(mf.ROOT, REHEARSAL, "cases")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            out += [(name[:-len(".json")], tr)
                    for tr in json.load(f)["trace"]]
    return out


def rehearse(cell: str, trace: int, seed: int, seconds: float = 3.0,
             manifest_path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Runs the cell in a process of its own on as many virtual CPU devices
    as it asks chips; returns the JSON lines it printed, the result last.
    ``manifest_path`` is a manifest to run it under in place of the
    rehearsal's own.  Each run writes to a directory of its own: a run
    empties its cell's output directory first, and two test files that
    rehearse one cell at the same time would empty each other's trace."""
    path = manifest_path or os.path.join(mf.ROOT, REHEARSAL, "BENCHMARK.json")
    chips = mf.Manifest(path).cell(cell)["chips"]
    arg = {"manifest": path,
           "traffic_dir": os.path.join(REHEARSAL, "traffic"),
           "init_kwargs": {"num_cpus": 4,
                           "resources": {"TPU": float(chips)}}}
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=mf.ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    os.makedirs(os.path.join(mf.ROOT, ".perfbench_out"), exist_ok=True)
    out_dir = tempfile.mkdtemp(prefix="rehearsal-", dir=os.path.join(
        mf.ROOT, ".perfbench_out"))
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys; from perfbench import runner; "
             f"runner.OUT_DIR = {out_dir!r}; "
             f"sys.exit(runner.main({argv!r}, rehearsal={arg!r}))"],
            cwd=mf.ROOT, capture_output=True, text=True, timeout=420,
            env=env)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(f"rehearsal of {cell} exited {out.returncode}: "
                           + out.stdout + out.stderr[-3000:])
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    a = ap.parse_args(argv)
    for line in rehearse(a.cell, a.trace, a.seed):
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
