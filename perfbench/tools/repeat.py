"""Run a cell several times, one new process each, and make the driver's
comparison by hand: per run the set-up by phase, the metrics and the verdict;
per set the median and the spread (quartile distance over the median) of
every metric.  Everything is also written to chiprun_out/<tag>.jsonl.

    python3 -m perfbench.tools.repeat --workload <cell> --seconds 45 \
        --seeds 2200000001,2200000002,... [--sets 2] [--trace 0] [--tag name]

With ``--sets 2`` the same seeds are run twice, set after set, as the
driver's two sets are.  This process never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from perfbench import manifest as mf
from perfbench import stats


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "-m", "perfbench", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=mf.ROOT, capture_output=True, text=True)
    row = {"seed": seed, "rc": p.returncode, "wall_s": time.time() - t0}
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    for ln in lines:
        d = json.loads(ln)
        if "setup_phases" in d:
            row["phases"] = d["setup_phases"]
        elif "compared" in d:
            row["compared"] = d["compared"]
            row["sanity"] = d["sanity"]
        elif "metrics" in d:
            row["result"] = d
    if p.returncode != 0 or "result" not in row:
        row["stderr"] = p.stderr[-3000:]
    return row


def summarise(rows: list) -> dict:
    good = [r for r in rows if "result" in r]
    out = {"runs": len(rows), "with_result": len(good),
           "all_correct": all(r["result"]["correct"] for r in good)}
    names = sorted({k for r in good for k in r["result"]["metrics"]})
    for name in names:
        vals = [r["result"]["metrics"][name]["value"] for r in good
                if name in r["result"]["metrics"]]
        out[name] = {"median": statistics.median(vals),
                     "min": min(vals), "max": max(vals),
                     "spread": stats.spread(vals) if len(vals) >= 2
                     and statistics.median(vals) else None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default="repeat")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    out_dir = os.path.join(mf.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, a.tag + ".jsonl")
    sets = []
    with open(path, "a") as f:
        for s in range(a.sets):
            rows = []
            for seed in seeds:
                row = one(a.workload, seed, a.seconds, a.trace)
                row["set"] = s
                rows.append(row)
                f.write(json.dumps(row) + "\n")
                f.flush()
                brief = {"set": s, "seed": seed, "rc": row["rc"],
                         "wall_s": round(row["wall_s"], 1)}
                if "phases" in row:
                    brief["phases"] = {k: round(v, 2)
                                       for k, v in row["phases"].items()}
                if "result" in row:
                    brief["correct"] = row["result"]["correct"]
                    brief["metrics"] = {
                        k: round(v["value"], 4)
                        for k, v in row["result"]["metrics"].items()}
                    brief["peak_gb"] = round(row["result"]["device"][
                        "memory_peak_bytes"] / 1e9, 3)
                    brief["compared"] = {
                        c["number"]: c["value"] for c in row["compared"]}
                else:
                    brief["stderr"] = row.get("stderr", "")[-1500:]
                print(json.dumps(brief), flush=True)
            sets.append(summarise(rows))
        summary = {"workload": a.workload, "seconds": a.seconds,
                   "sets": sets}
        f.write(json.dumps({"summary": summary}) + "\n")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
