"""Reduce the trace a ``--trace 1`` run of a cell left in .perfbench_out and
write programs, the 60 largest operations and the idle gaps to
chiprun_out/<cell>.trace.json (the raw trace is too large to bring back).

    python3 -m perfbench.tools.trace_dump <cell>
"""

import json
import os
import sys

from perfbench import manifest as mf
from perfbench import runner, xplane


def main(cell: str) -> int:
    trace_dir = os.path.join(runner.OUT_DIR, cell, "trace")
    r = xplane.reduce_dir(trace_dir)
    from jax.profiler import ProfileData
    seen = set()
    for plane in ProfileData.from_file(xplane.find(trace_dir)).planes:
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                key = e.name[:40]
                if "custom" in e.name and key not in seen:
                    seen.add(key)
                    print("CUSTOM", e.name[:600])
    top = sorted(r["ops"].items(), key=lambda kv: -kv[1])[:60]
    out = {"cell": cell, "n_devices": r["n_devices"], "busy_s": r["busy_s"],
           "window_s": r["window_s"], "programs": r["programs"],
           "ops": top, "idle_gaps": r["idle_gaps"],
           "collective_exposed_s": r["collective_exposed_s"]}
    path = os.path.join(mf.ROOT, "chiprun_out", cell + ".trace.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("busy_s", "window_s", "programs")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
